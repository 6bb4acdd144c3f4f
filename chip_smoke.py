#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py [--seed N]        # one TPU v5e chip (the driver's run)
    python3 chip_smoke.py --chips 4 [...]   # one four-chip host (run by hand)

One chip: Llama-3.2-3B (published widths and depth, bf16, random weights
from --seed) served the way `roundtable gateway` serves it — a scratch
project's `.roundtable/config.json` → load_config → gateway_cmd.
_build_scheduler → Gateway.start_in_thread on a loopback port — and
asked over HTTP from this same process: a non-streaming chat completion,
an SSE chat completion, and a three-knight discussion of two rounds on
one session whose first round joins the batch while the SSE stream is
still decoding (the mixed prefill/decode dispatch the ragged kernel
exists for). Then it checks, and fails on, every hidden fallback, and
scores the first greedy token of each request against a dense forward of
the engine's own weights.

Four chips (`--chips 4`, and nothing of the above): Llama-3-8B in bf16 at
`mesh: {"model": 4}` — 16 GB of weights one chip cannot hold — built
through the born-sharded init and served through the same scheduler
entry (acquire_scheduler); before it, the same model cut to 8 layers is
served over the four chips and scored against the dense forward of the
same weights gathered onto one chip.

Every line of standard output is one JSON object. The last one is
`{"ok": true, "device": {...}}` and is printed only when every phase
passed; any failure is an uncaught exception (nonzero exit, no such
line). The script never forces a platform: JAX finding no TPU is a
failure. It is one process — the process that holds the chip — and
starts no other.

No warm-up pass: each request carries a deadline that survives the cold
compile of its programs (REQUEST_DEADLINE_S), so only the shapes this
traffic needs are ever compiled. The watchdog stays unarmed, as it is by
default.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import socket
import sys
import tempfile
import threading
import time
from typing import Any, Optional

ONE_CHIP_ENGINE = {
    "model": "llama-3.2-3b-instruct", "dtype": "bfloat16",
    "kv_layout": "paged", "page_size": 128, "num_pages": 256,
    "mesh": {"model": 1}, "attn": "auto"}
FOUR_CHIP_ENGINE = dict(ONE_CHIP_ENGINE, model="llama-3-8b-instruct",
                        mesh={"model": 4})
COMPARISON_LAYERS = 8        # the four-chip arm that one chip can check

KNIGHTS = ("Lancelot", "Galahad", "Percival")
MAX_NEW = 32                 # per request ...
LIVE_STREAM_NEW = 256        # ... but for the stream the round joins
REQUEST_DEADLINE_S = 900.0   # survives a cold 28/32-layer compile
REFERENCE_T = 1024           # one padded shape for every reference
# The served first token must be (nearly) the reference argmax. Random
# weights make the top of a 128k-way argmax a near-tie, and the served
# path (bf16 Pallas kernels over the paged pool, chunked) rounds
# differently from the dense bf16 reference, so the test is not equality
# but: the served token's reference logit lies within LOGIT_TOL_SIGMAS
# standard deviations (of that prompt's reference logits over the
# vocabulary) of the reference maximum. The top-1/top-2 gap of N
# Gaussian logits is about sigma / sqrt(2 ln N) — 0.2 sigma at 128k —
# and a wrong token sits ~4 sigma down, so 0.25 sigma admits bf16
# near-ties and nothing else.
LOGIT_TOL_SIGMAS = 0.25

PREAMBLE = (
    "You sit at the round table. The question before the knights is "
    "whether the session journal should fsync before or after the "
    "terminal event is streamed to the client, given that a crash "
    "between the two must never lose an acknowledged turn, and that "
    "the median gap between tokens is what the users of this system "
    "feel. Weigh durability against latency, name the failure each "
    "ordering admits, and say which one you would ship and why. ")
PERSONA = (
    "You are {name}, knight number {i}. Speak plainly, in your own "
    "voice, and disagree with the others where you must. {name}: ")
ROUND_TWO = (
    "\n\nRound two. The other knights have spoken. {name}, answer the "
    "strongest objection to your position, then score the emerging "
    "consensus from one to ten and say what would raise it. {name}: ")


def emit(phase: str, **fields: Any) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


class Timer:
    def __init__(self) -> None:
        self.t0 = time.monotonic()

    def lap(self) -> float:
        now = time.monotonic()
        dt, self.t0 = now - self.t0, now
        return round(dt, 2)


# ---------------------------------------------------------------------
# device
# ---------------------------------------------------------------------


def device_phase(expect_count: int) -> dict:
    """The device as JAX reports it; fails unless it is `expect_count`
    TPU chips whose kind the peaks table knows."""
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (platform "
            f"{device['platform']!r}) — this script only runs on the chip")
    if device["count"] != expect_count:
        raise SystemExit(
            f"chip_smoke: needs {expect_count} chip(s), JAX reports "
            f"{device['count']}")
    from theroundtaible_tpu.native.loader import (native_available,
                                                  native_origin)
    from theroundtaible_tpu.utils.perfmodel import detect_chip

    spec, source = detect_chip()
    if source != "detected":
        raise RuntimeError(
            f"device kind {device['kind']!r} is not in the peaks table "
            f"(perfmodel.detect_chip → {source!r})")
    stats = devices[0].memory_stats() or {}
    native_available()
    emit("device", **device, chip=spec.name, chip_source=source,
         hbm_gbps=spec.hbm_gbps, bf16_peak_tflops=spec.bf16_peak_tflops,
         bytes_limit=stats.get("bytes_limit"),
         native_library=native_origin() or "unavailable")
    return device


# ---------------------------------------------------------------------
# a minimal raw-socket HTTP/SSE client (http.client buffers SSE)
# ---------------------------------------------------------------------


class Http:
    """One POST; anything but a 200 raises with the server's reason (a
    shed or a refusal carries it in the body)."""

    def __init__(self, port: int, path: str, body: dict) -> None:
        self.sock = socket.create_connection(
            ("127.0.0.1", port), timeout=REQUEST_DEADLINE_S + 120)
        payload = json.dumps(body).encode("utf-8")
        self.sock.sendall(
            (f"POST {path} HTTP/1.1\r\nHost: chip-smoke\r\n"
             f"Content-Length: {len(payload)}\r\n\r\n").encode("latin-1")
            + payload)
        self.f = self.sock.makefile("rb")
        status = int(self.f.readline().split()[1])
        self.headers: dict[str, str] = {}
        while True:
            line = self.f.readline().decode("latin-1").strip()
            if not line:
                break
            k, _, v = line.partition(":")
            self.headers[k.lower()] = v.strip()
        if status != 200:
            reason = self.json()
            self.close()
            raise RuntimeError(f"POST {path}: HTTP {status} {reason}")

    def __enter__(self) -> "Http":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def json(self) -> dict:
        n = int(self.headers.get("content-length", "0"))
        return json.loads(self.f.read(n).decode("utf-8")) if n else {}

    def events(self):
        """Each SSE event's data string, until the server closes."""
        data: list[str] = []
        for raw in self.f:
            line = raw.decode("utf-8").rstrip("\n")
            if line.startswith("data: "):
                data.append(line[6:])
            elif line == "" and data:
                yield "\n".join(data)
                data = []

    def close(self) -> None:
        try:
            self.f.close()
            self.sock.close()
        except OSError:
            pass


def _chat_body(session: str, content: str, max_tokens: int,
               stream: bool) -> dict:
    return {"model": "assistant", "session": session, "temperature": 0,
            "stream": stream, "max_tokens": max_tokens,
            "deadline_s": REQUEST_DEADLINE_S,
            "messages": [{"role": "user", "content": content}]}


def chat_completion(port: int, session: str, content: str,
                    max_tokens: int) -> dict:
    with Http(port, "/v1/chat/completions",
              _chat_body(session, content, max_tokens, False)) as conn:
        return conn.json()


def chat_completion_sse(port: int, session: str, content: str,
                        max_tokens: int) -> dict:
    """→ {"stream": id, "chunks": n, "finish": reason, "done": bool}."""
    out = {"stream": "", "chunks": 0, "finish": None, "done": False}
    with Http(port, "/v1/chat/completions",
              _chat_body(session, content, max_tokens, True)) as conn:
        for data in conn.events():
            if data == "[DONE]":
                out["done"] = True
                break
            chunk = json.loads(data)
            out["stream"] = chunk["id"].removeprefix("chatcmpl-")
            finish = chunk["choices"][0]["finish_reason"]
            if finish:
                out["finish"] = finish
            else:
                out["chunks"] += 1
    return out


def discussion_round(port: int, session: str,
                     turns: list[tuple[str, str]]) -> list[list[int]]:
    """One native multi-knight round → the token ids of each row."""
    rows: list[list[int]] = [[] for _ in turns]
    terminal = None
    with Http(port, "/v1/discussions", {
            "session": session, "max_new_tokens": MAX_NEW,
            "temperature": 0, "deadline_s": REQUEST_DEADLINE_S,
            "turns": [{"knight": k, "prompt": p} for k, p in turns]}
              ) as conn:
        for data in conn.events():
            ev = json.loads(data)
            if ev["type"] == "tokens":
                rows[ev["row"]].extend(ev["tokens"])
            elif ev["type"] == "summary":
                for i, row in ev["rows"].items():
                    rows[int(i)].extend(row["tokens"])
            elif ev["type"] in ("retired", "failed"):
                terminal = ev
                break
    if terminal is None or terminal["type"] != "retired":
        raise RuntimeError(f"discussion {session!r} ended with {terminal}")
    return rows


# ---------------------------------------------------------------------
# traffic and the checks on what came back
# ---------------------------------------------------------------------


def knight_prompts(round_no: int) -> list[tuple[str, str]]:
    """Three knights, a shared preamble, a transcript that grows: round
    two's prompt extends round one's, so each knight's KV is reused."""
    turns = []
    for i, name in enumerate(KNIGHTS, 1):
        prompt = PREAMBLE + PERSONA.format(name=name, i=i)
        if round_no == 2:
            prompt += ROUND_TWO.format(name=name)
        turns.append((name, prompt))
    return turns


def chat_prompt(content: str) -> str:
    """The prompt gateway/app.py builds from one user message."""
    return f"user: {content}\nassistant:"


def _need_tokens(what: str, ids: list[int], asked: int) -> None:
    """A finished request holds between one and `asked` token ids —
    fewer than asked only where the model itself said eos (the stream is
    eos-trimmed; one in 128k per token with random weights)."""
    if not 1 <= len(ids) <= asked:
        raise RuntimeError(
            f"{what}: {len(ids)} token ids came back, asked for {asked}")


def _wait_for_live_row(sched, timeout_s: float = REQUEST_DEADLINE_S
                       ) -> None:
    """Until the scheduler's batch holds a row that is past admission
    (its cold prefill compiled and ran) — the moment a join meets a
    decoding batch."""
    bound = time.monotonic() + timeout_s
    while sched.describe()["active_rows"] < 1:
        if time.monotonic() > bound:
            raise RuntimeError("the live stream never reached the batch")
        time.sleep(0.002)


def gateway_traffic(gw, sched) -> list[dict]:
    """The one-chip traffic → one record per greedy request:
    {"what", "prompt", "ids"}."""
    port = gw.port
    served: list[dict] = []

    q1 = PREAMBLE + "Answer in one paragraph."
    reply = chat_completion(port, "smoke-chat-1", q1, MAX_NEW)
    n = reply["usage"]["completion_tokens"]
    stream_id = reply["id"].removeprefix("chatcmpl-")
    ids = list(gw.streams[stream_id].history[0])
    if n != len(ids):
        raise RuntimeError(f"usage says {n} tokens, stream holds {len(ids)}")
    _need_tokens("chat completion", ids, MAX_NEW)
    served.append({"what": "chat", "prompt": chat_prompt(q1), "ids": ids})

    # The SSE stream decodes long enough for round one to join it.
    q2 = PREAMBLE + "Answer at length, and stream it."
    sse: dict = {}
    sse_error: list[BaseException] = []

    def run_sse() -> None:
        try:
            sse.update(chat_completion_sse(port, "smoke-chat-2", q2,
                                           LIVE_STREAM_NEW))
        except BaseException as e:  # noqa: BLE001 — re-raised below
            sse_error.append(e)

    live = threading.Thread(target=run_sse, name="sse-client")
    live.start()
    _wait_for_live_row(sched)
    round1 = discussion_round(port, "smoke-table", knight_prompts(1))
    live.join()
    if sse_error:
        raise sse_error[0]
    if not sse["done"] or sse["finish"] != "stop" or not sse["chunks"]:
        raise RuntimeError(f"SSE chat completion did not finish: {sse}")
    ids = list(gw.streams[sse["stream"]].history[0])
    _need_tokens("chat completion (SSE)", ids, LIVE_STREAM_NEW)
    served.append({"what": "chat-sse", "prompt": chat_prompt(q2),
                   "ids": ids})

    round2 = discussion_round(port, "smoke-table", knight_prompts(2))
    for no, rows in ((1, round1), (2, round2)):
        for (name, prompt), ids in zip(knight_prompts(no), rows):
            _need_tokens(f"round {no} {name}", ids, MAX_NEW)
            served.append({"what": f"round{no}:{name}", "prompt": prompt,
                           "ids": ids})
    return served


def scheduler_traffic(sched) -> list[dict]:
    """The four-chip traffic, straight into the scheduler: a long
    single-knight request, a three-knight round that joins it while it
    decodes, and a short request after — all greedy."""
    from theroundtaible_tpu.engine.sampling import SamplingParams

    def submit(session: str, turns, max_new: int):
        rows: list[list[int]] = [[] for _ in turns]

        def on_commit(event: dict) -> None:
            if event.get("type") == "tokens":
                rows[event["row"]].extend(event["tokens"])

        req = sched.submit_async(
            session, turns, max_new_tokens=max_new,
            timeout_s=REQUEST_DEADLINE_S, on_commit=on_commit,
            sampling_per_turn=[SamplingParams(temperature=0.0,
                                              max_new_tokens=max_new)
                               for _ in turns])
        return req, rows

    q1 = chat_prompt(PREAMBLE + "Answer at length.")
    long_req, long_rows = submit("smoke-solo-1", [("assistant", q1)],
                                 LIVE_STREAM_NEW)
    _wait_for_live_row(sched)
    table_req, table_rows = submit("smoke-table", knight_prompts(1),
                                   MAX_NEW)
    sched.wait(table_req)
    sched.wait(long_req)
    q2 = chat_prompt(PREAMBLE + "Answer in one paragraph.")
    short_req, short_rows = submit("smoke-solo-2", [("assistant", q2)],
                                   MAX_NEW)
    sched.wait(short_req)

    served = [{"what": "solo-long", "prompt": q1, "ids": long_rows[0]},
              {"what": "solo-short", "prompt": q2, "ids": short_rows[0]}]
    _need_tokens("solo-long", long_rows[0], LIVE_STREAM_NEW)
    _need_tokens("solo-short", short_rows[0], MAX_NEW)
    for (name, prompt), ids in zip(knight_prompts(1), table_rows):
        _need_tokens(f"round 1 {name}", ids, MAX_NEW)
        served.append({"what": f"round1:{name}", "prompt": prompt,
                       "ids": ids})
    return served


def _served_summary(served: list[dict]) -> list[dict]:
    return [{"what": r["what"], "completion_tokens": len(r["ids"]),
             "first_ids": r["ids"][:4]} for r in served]


def no_hidden_fallback(engine, sched) -> dict:
    """Every kernel of the served path ran as a kernel: a fallback rung
    taken, a decline, or a ragged dispatch through XLA fails the run.
    The ladder itself stays — it is safety code."""
    from theroundtaible_tpu.engine import faults
    from theroundtaible_tpu.engine.pallas import attention as pattn
    from theroundtaible_tpu.utils import telemetry

    info = engine.describe()
    sd = sched.describe()
    snap = telemetry.REGISTRY.snapshot()["counters"]
    checked = {
        "paged_decode": info["paged_decode"],
        "ragged_path": info["ragged"]["path"],
        "ragged_fallback_reason": info["ragged"]["fallback_reason"],
        "ragged_dispatches": info["ragged"]["dispatches"],
        "attn_impl": engine.cfg.attn_impl,
        "paged_degraded_reason": engine.paged_degraded_reason,
        "ragged_kernel_dispatches": pattn.ragged_kernel_dispatches(),
        "ragged_fallback_dispatches": pattn.ragged_fallback_dispatches(),
        "degradations": {k: v for k, v in snap.items()
                         if k.startswith("roundtable_degradations_total")},
        "faults_armed": bool(faults.ARMED),
        "scheduler": {k: sd[k] for k in (
            "admitted", "completed", "failed", "refused", "segments",
            "ragged_segments", "ragged_joins", "spec_segments",
            "max_occupancy", "segment_prefill_tokens",
            "segment_decode_tokens")},
        "spec_decode": {k: info["spec_decode"][k] for k in (
            "enabled", "drafter", "verify_dispatches", "drafted_tokens",
            "accepted_tokens")},
        "prefix_cache": {k: info.get("prefix_cache", {}).get(k)
                         for k in ("hits", "reused_tokens")},
        "compile_cache": info["compile_cache"],
    }
    emit("no_hidden_fallback", **checked)
    problems = [name for name, bad in (
        ("paged_decode", checked["paged_decode"] != "pool-direct"),
        ("ragged_path", checked["ragged_path"] != "pallas_ragged"),
        ("ragged_fallback_reason",
         checked["ragged_fallback_reason"] is not None),
        ("attn_impl", checked["attn_impl"] != "flash"),
        ("paged_degraded_reason",
         checked["paged_degraded_reason"] is not None),
        ("ragged_kernel_dispatches",
         checked["ragged_kernel_dispatches"] <= 0),
        ("ragged_fallback_dispatches",
         checked["ragged_fallback_dispatches"] != 0),
        ("degradations", any(checked["degradations"].values())),
        ("faults_armed", checked["faults_armed"]),
        ("scheduler.failed", sd["failed"] != 0),
        ("scheduler.refused", sd["refused"] != 0),
        ("scheduler.ragged_joins", sd["ragged_joins"] <= 0),
    ) if bad]
    if problems:
        raise RuntimeError(f"hidden fallback or failure: {problems}")
    return checked


def right_answers(engine, served: list[dict], params=None) -> list[dict]:
    """Score each request's first served token against the dense
    forward of the engine's own weights over the whole prompt: no
    cache, no kernel, no second engine (`params`: the same weights
    gathered elsewhere — the four-chip comparison's one-chip copy)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from theroundtaible_tpu.engine.models.common import forward

    cfg = dataclasses.replace(engine.cfg, attn_impl="dense")
    params = engine.params if params is None else params

    @jax.jit
    def reference(params, tokens, valid):
        positions = jnp.arange(REFERENCE_T, dtype=jnp.int32)[None]
        logits, _ = forward(params, cfg, tokens, positions, None, None,
                            valid, last_pos=valid - 1)
        return logits[0, 0].astype(jnp.float32)

    scores = []
    for rec in served:
        tokens = engine.tokenizer.encode(rec["prompt"])
        n = len(tokens)
        if n > REFERENCE_T:
            raise RuntimeError(f"{rec['what']}: prompt of {n} tokens "
                               f"outgrew the reference shape")
        padded = np.full((1, REFERENCE_T), engine.tokenizer.pad_id,
                         np.int32)
        padded[0, :n] = tokens
        logits = np.asarray(reference(params, jnp.asarray(padded),
                                      jnp.asarray([n], jnp.int32)))
        if logits.shape != (engine.cfg.vocab_size,) \
                or not np.isfinite(logits).all():
            raise RuntimeError(f"{rec['what']}: reference logits "
                               f"{logits.shape} not finite")
        served_id = int(rec["ids"][0])
        sigma = float(logits.std())
        gap = float(logits.max() - logits[served_id])
        scores.append({
            "what": rec["what"], "prompt_tokens": n,
            "served": served_id, "reference": int(logits.argmax()),
            "rank": int((logits > logits[served_id]).sum()),
            "gap": round(gap, 5), "sigma": round(sigma, 5),
            "gap_sigmas": round(gap / sigma, 5),
            "completion_tokens": len(rec["ids"])})
    emit("right_answers", tolerance_sigmas=LOGIT_TOL_SIGMAS,
         requests=scores)
    wrong = [s["what"] for s in scores
             if s["gap_sigmas"] > LOGIT_TOL_SIGMAS]
    if wrong:
        raise RuntimeError(
            f"served first token outside {LOGIT_TOL_SIGMAS} sigma of "
            f"the reference maximum: {wrong}")
    return scores


def compiles_and_memory() -> dict:
    import jax

    from theroundtaible_tpu.engine import (compile_watch,
                                           get_compile_cache_decision)
    from theroundtaible_tpu.utils import telemetry

    s = compile_watch.summary()
    per_device = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        per_device.append({"id": d.id,
                           "bytes_in_use": stats.get("bytes_in_use"),
                           "peak_bytes_in_use":
                               stats.get("peak_bytes_in_use")})
    out = {
        "compiles": s["compiles"],
        "compile_seconds": round(telemetry.REGISTRY.counter_total(
            "roundtable_compile_seconds_total"), 2),
        "persistent_cache_hits": s["cache_hits"],
        "persistent_cache_misses": s["cache_misses"],
        "observatory": s["mode"],
        "cache_dir": (get_compile_cache_decision() or {}).get("dir"),
        "devices": per_device,
    }
    emit("compiles_and_memory", **out)
    return out


# ---------------------------------------------------------------------
# one chip: the gateway path
# ---------------------------------------------------------------------


def write_project(root: str, engine_cfg: dict) -> None:
    """A scratch project whose three knights share one tpu-llm seat."""
    config = {
        "version": "1.0", "project": "chip-smoke", "language": "en",
        "knights": [{"name": name, "adapter": "tpu-llm",
                     "capabilities": ["logic"], "priority": i}
                    for i, name in enumerate(KNIGHTS, 1)],
        "rules": {"max_rounds": 2, "consensus_threshold": 9,
                  "timeout_per_turn_seconds": int(REQUEST_DEADLINE_S),
                  "escalate_to_user_after": 3, "auto_execute": False,
                  "ignore": [".git"], "parallel_rounds": True},
        "chronicle": ".roundtable/chronicle.md",
        "adapter_config": {"tpu-llm": engine_cfg},
    }
    os.makedirs(os.path.join(root, ".roundtable"), exist_ok=True)
    with open(os.path.join(root, ".roundtable", "config.json"), "w",
              encoding="utf-8") as f:
        json.dump(config, f, indent=2)


def serve_through_gateway(engine_cfg: dict) -> dict:
    """config.json → load_config → _build_scheduler → Gateway → HTTP,
    then the checks. Returns the wall seconds of each phase."""
    from theroundtaible_tpu.commands.gateway_cmd import _build_scheduler
    from theroundtaible_tpu.core.config import load_config
    from theroundtaible_tpu.gateway import Gateway

    walls: dict[str, float] = {}
    clock = Timer()
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as project:
        write_project(project, engine_cfg)
        sched = _build_scheduler(load_config(project), None)
        engine = sched.engine
        walls["build"] = clock.lap()
        emit("build", model=engine.cfg.name, layers=engine.cfg.num_layers,
             embed_dim=engine.cfg.embed_dim, heads=engine.cfg.num_heads,
             kv_heads=engine.cfg.num_kv_heads,
             head_dim=engine.cfg.head_dim, mlp_dim=engine.cfg.mlp_dim,
             vocab=engine.cfg.vocab_size, params=engine.num_params,
             mesh=dict(engine.mesh.shape), seconds=walls["build"])
        gw = Gateway(sched, host="127.0.0.1", port=0)
        gw.start_in_thread()
        try:
            served = gateway_traffic(gw, sched)
            walls["serve"] = clock.lap()
            front = gw.describe()
            emit("serve", requests=_served_summary(served),
                 gateway={k: front[k] for k in (
                     "admitted", "shed", "expired", "dropped_events")},
                 seconds=walls["serve"])
            if front["shed"] or front["expired"]:
                raise RuntimeError("the gateway shed or expired a request")
            no_hidden_fallback(engine, sched)
            right_answers(engine, served)
            walls["check"] = clock.lap()
        finally:
            gw.stop()
            sched.close()
    compiles_and_memory()
    return walls


# ---------------------------------------------------------------------
# four chips: the sharded path and what it is compared with
# ---------------------------------------------------------------------


def _engine_from(engine_cfg: dict, num_layers: Optional[int] = None):
    """The engine from_config would build, with depth optionally cut —
    constructed directly, so no config key exists for the cut."""
    import jax.numpy as jnp

    from theroundtaible_tpu.engine.engine import InferenceEngine
    from theroundtaible_tpu.engine.models.registry import get_model_config

    if num_layers is None:
        return InferenceEngine.from_config(engine_cfg)
    cfg = dataclasses.replace(get_model_config(engine_cfg["model"]),
                              num_layers=num_layers)
    return InferenceEngine(
        cfg, mesh_shape=engine_cfg["mesh"], dtype=jnp.bfloat16,
        seed=engine_cfg["seed"], attn=engine_cfg["attn"],
        kv_layout=engine_cfg["kv_layout"],
        page_size=engine_cfg["page_size"],
        num_pages=engine_cfg["num_pages"])


def placement(engine) -> dict:
    """Per-device bytes against an even share of the weights plus the
    pool: proved, not assumed. `shard_bytes` adds up the shards each
    device holds of the parameters and the pool; `bytes_in_use` is what
    the device itself reports (everything resident on it; None where
    the backend keeps no such count, as the CPU rehearsal's does) and is
    what the limits are held against where it exists."""
    import jax

    devices = list(engine.mesh.devices.flatten())
    leaves = jax.tree_util.tree_leaves((engine.params, engine.kv.pools))
    held = dict.fromkeys(devices, 0)
    for leaf in leaves:
        if len(leaf.sharding.device_set) != len(devices):
            raise RuntimeError("a leaf is not spread over the whole mesh")
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    total = sum(x.nbytes for x in leaves)
    share = total / len(devices)
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in devices]
    judged = in_use if all(in_use) else list(held.values())
    out = {"resident_bytes_total": total, "even_share": int(share),
           "shard_bytes": list(held.values()), "bytes_in_use": in_use,
           "max_over_min": round(max(judged) / min(judged), 3),
           "max_over_share": round(max(judged) / share, 3)}
    emit("placement", model=engine.cfg.name, layers=engine.cfg.num_layers,
         **out)
    if out["max_over_min"] >= 1.5 or out["max_over_share"] >= 1.25:
        raise RuntimeError(f"parameters or pool not spread evenly: {out}")
    return out


def _free_everything() -> None:
    """Drop every device buffer of the arm that just finished (its
    engine, pool, programs and gathered copy) before the next build."""
    import jax

    gc.collect()
    for arr in jax.live_arrays():
        arr.delete()
    jax.clear_caches()
    gc.collect()


def serve_sharded(engine_cfg: dict, *,
                  comparison_layers: int = COMPARISON_LAYERS) -> dict:
    import jax

    from theroundtaible_tpu.engine.scheduler import acquire_scheduler

    walls: dict[str, float] = {}
    clock = Timer()

    # The comparison: depth cut so one chip can hold a gathered copy.
    engine = _engine_from(engine_cfg, comparison_layers)
    sched, _ = acquire_scheduler(engine)
    walls["build_comparison"] = clock.lap()
    try:
        placement(engine)
        served = scheduler_traffic(sched)
        no_hidden_fallback(engine, sched)
        gathered = jax.device_put(engine.params, jax.devices()[0])
        right_answers(engine, served, params=gathered)
    finally:
        sched.close()
    walls["comparison"] = clock.lap()
    del engine, sched, gathered, served
    _free_everything()
    emit("freed", bytes_in_use=[
        (d.memory_stats() or {}).get("bytes_in_use")
        for d in jax.devices()])

    # The model itself: published widths AND depth.
    engine = _engine_from(engine_cfg)
    sched, _ = acquire_scheduler(engine)
    walls["build"] = clock.lap()
    emit("build", model=engine.cfg.name, layers=engine.cfg.num_layers,
         params=engine.num_params, mesh=dict(engine.mesh.shape),
         seconds=walls["build"])
    try:
        placement(engine)
        served = scheduler_traffic(sched)
        walls["serve"] = clock.lap()
        emit("serve", requests=_served_summary(served),
             seconds=walls["serve"])
        no_hidden_fallback(engine, sched)
    finally:
        sched.close()
    compiles_and_memory()
    return walls


# ---------------------------------------------------------------------


def main(argv: Optional[list[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the sharded Llama-3-8B path and its "
                         "comparison, and no one-chip phase")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    t0 = time.monotonic()
    device = device_phase(args.chips)
    if args.chips == 1:
        walls = serve_through_gateway(dict(ONE_CHIP_ENGINE, seed=args.seed))
    else:
        walls = serve_sharded(dict(FOUR_CHIP_ENGINE, seed=args.seed))
    emit("walls", **walls, total=round(time.monotonic() - t0, 2))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
