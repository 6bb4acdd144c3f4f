"""Gateway chaos + overload acceptance (ISSUE 16) — GATEWAY_r16.json.

Runs entirely on CPU against real child gateway processes (the
tests/_gateway_main.py entry), with ROUNDTABLE_RECOMPILE_STRICT=1
armed across every child including the post-crash restart:

(a) **kill -9 mid-stream**: 3 concurrent discussion streams, SIGKILL
    the serving process after each client has read part of its stream,
    restart with `--resume`, reconnect every client via Last-Event-ID
    — zero lost, zero duplicated tokens, greedy parity against an
    uninterrupted reference run of the same prompts.
(b) **open-loop overload**: a burst of requests against a gateway
    capped at ROUNDTABLE_GATEWAY_MAX_INFLIGHT=2 — the excess must shed
    with 429 + Retry-After + a machine-readable reason while the
    admitted requests' p95 TTFT stays bounded.
(c) **preflight invariants**: `roundtable lint` exits 0.

`--smoke` shrinks (a) to one stream and (b) to a small burst for a
CPU preflight step; the full run writes
GATEWAY_r16.json at the repo root.

`--replicas 2` (ISSUE 17) switches to the router acceptance: a
rolling restart of replica r0 under open-loop multi-turn client load
(zero failed sessions, zero lost/duplicated tokens, greedy parity
across the roll) plus the aggregate-tok/s scaling point at 1 and 2
replicas — written to ROUTER_r17.json. `--smoke --replicas 2` shrinks
it to one client and skips the scaling sweep for the CPU preflight.

`--trace` (ISSUE 20) switches to the end-to-end tracing acceptance —
TRACE_r20.json: a chaos run (device_lost cross-replica failover, then
kill -9 + `--resume`, under concurrent streams) where every client
request stitches to ONE on-disk trace across both process generations
with per-leg stage sums within 5% of the leg wall and zero orphan
legs; an open-loop loadgen sweep whose per-session records join to
retained server-side traces with per-stage p95 attribution; and the
SLO burn monitor staying quiet on a under-SLO baseline while firing
exactly once on an induced breach. `--trace --smoke` shrinks it to
one stream + one sweep point for a CPU preflight.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))

PROMPTS = [
    "The round table met at dawn to discuss the castle walls and the "
    "eastern gate.",
    "A different discussion entirely, about dragons and the kingdom's "
    "gold reserves.",
    "The quartermaster tallies grain, arrows and oil for the winter "
    "siege preparations.",
]


# --- minimal raw-socket HTTP/SSE client (stdlib only) ----------------


class Conn:
    def __init__(self, port, method, path, body=None, headers=None,
                 timeout=180.0):
        self.sock = socket.create_connection(("127.0.0.1", port),
                                             timeout=timeout)
        payload = (json.dumps(body).encode("utf-8")
                   if body is not None else b"")
        head = (f"{method} {path} HTTP/1.1\r\nHost: b\r\n"
                f"Content-Length: {len(payload)}\r\n")
        for k, v in (headers or {}).items():
            head += f"{k}: {v}\r\n"
        self.sock.sendall(head.encode("latin-1") + b"\r\n" + payload)
        self.f = self.sock.makefile("rb")
        self.status = int(self.f.readline().split()[1])
        self.headers = {}
        while True:
            ln = self.f.readline().decode("latin-1").strip()
            if not ln:
                break
            k, _, v = ln.partition(":")
            self.headers[k.lower()] = v.strip()

    def events(self):
        eid, data = None, []
        for raw in self.f:
            ln = raw.decode("utf-8").rstrip("\n")
            if ln.startswith("id: "):
                eid = ln[4:]
            elif ln.startswith("data: "):
                data.append(ln[6:])
            elif ln.startswith(":"):
                continue
            elif ln == "" and data:
                yield eid, "\n".join(data)
                eid, data = None, []

    def body_json(self):
        n = int(self.headers.get("content-length", "0"))
        return json.loads(self.f.read(n).decode("utf-8")) if n else {}

    def close(self):
        try:
            self.f.close()
            self.sock.close()
        except OSError:
            pass


def read_stream(port, path, body=None, method="POST", headers=None):
    """(meta, [(eid, token_event)...], terminal) for one full stream."""
    c = Conn(port, method, path, body=body, headers=headers)
    assert c.status == 200, f"{c.status}: {c.body_json()}"
    meta, toks, terminal = None, [], None
    for eid, data in c.events():
        ev = json.loads(data)
        if ev["type"] == "stream":
            meta = ev
        elif ev["type"] in ("tokens", "summary"):
            toks.append((eid, ev))
        else:
            terminal = ev
            break
    c.close()
    return meta, toks, terminal


def flat_tokens(toks):
    out = []
    for _eid, ev in toks:
        if ev["type"] == "tokens":
            out.extend(ev["tokens"])
        else:
            for _i, d in sorted(ev["rows"].items()):
                out.extend(d["tokens"])
    return out


# --- child lifecycle -------------------------------------------------


def spawn_gateway(jdir, resume=None, extra_env=None, replicas=None):
    cmd = [sys.executable, os.path.join(REPO, "tests",
                                        "_gateway_main.py"),
           "--journal", str(jdir)]
    if resume:
        cmd += ["--resume", str(resume)]
    if replicas is not None:
        cmd += ["--replicas", str(replicas)]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               ROUNDTABLE_RECOMPILE_STRICT="1",
               ROUNDTABLE_DISABLE_TPU_DETECT="1",
               **(extra_env or {}))
    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    port, deadline = None, time.monotonic() + 300
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            break
        if line.startswith("PORT="):
            port = int(line.strip().split("=", 1)[1])
            break
    if port is None:
        proc.kill()
        raise RuntimeError("gateway child never started listening")
    threading.Thread(target=lambda: [None for _ in proc.stdout],
                     daemon=True).start()
    return proc, port


# --- (a) kill -9 chaos ----------------------------------------------


def run_chaos(workdir, n_streams, max_new):
    jdir = os.path.join(workdir, "chaos-journal")
    sessions = [(f"c{i}", PROMPTS[i % len(PROMPTS)])
                for i in range(n_streams)]

    proc, port = spawn_gateway(jdir)
    refs, metas, seen = [], [], []
    conns = []
    t_kill = None
    try:
        # uninterrupted reference (same process = same weights).
        for name, prompt in sessions:
            _m, toks, term = read_stream(
                port, "/v1/discussions",
                {"session": f"ref-{name}", "max_new_tokens": max_new,
                 "turns": [{"knight": "lancelot", "prompt": prompt}]})
            assert term["type"] == "retired"
            refs.append(flat_tokens(toks))

        for name, prompt in sessions:
            c = Conn(port, "POST", "/v1/discussions",
                     body={"session": name, "max_new_tokens": max_new,
                           "turns": [{"knight": "lancelot",
                                      "prompt": prompt}]})
            assert c.status == 200
            conns.append(c)
        for c in conns:
            it = c.events()
            meta = json.loads(next(it)[1])
            metas.append(meta)
            got, last_id = [], None
            for eid, data in it:
                ev = json.loads(data)
                if ev["type"] in ("tokens", "summary"):
                    got.extend(flat_tokens([(eid, ev)]))
                    last_id = eid
                if len(got) >= 2:
                    break
            assert last_id is not None, "no tokens before the crash"
            seen.append((got, last_id))
        t_kill = time.monotonic()
    finally:
        proc.kill()  # SIGKILL mid-stream
        proc.wait(30)
        for c in conns:
            c.close()

    proc2, port2 = spawn_gateway(jdir, resume=jdir)
    t_up = time.monotonic() - t_kill
    lost = dup = 0
    reconnect_walls = []
    try:
        for (name, _p), meta, (got, last_id), ref in zip(
                sessions, metas, seen, refs):
            t0 = time.monotonic()
            _m2, toks2, term2 = read_stream(
                port2, f"/v1/streams/{meta['stream']}", method="GET",
                headers={"Last-Event-ID": last_id})
            reconnect_walls.append(round(time.monotonic() - t0, 3))
            assert term2 and term2["type"] == "retired", \
                f"{name}: resumed stream did not retire"
            full = got + flat_tokens(toks2)
            if full != ref:
                if len(full) < len(ref) or full[:len(ref)] != ref:
                    lost += 1
                else:
                    dup += 1
    finally:
        proc2.kill()
        proc2.wait(30)

    return {
        "streams": n_streams,
        "max_new_tokens": max_new,
        "tokens_seen_before_kill": [len(g) for g, _ in seen],
        "restart_to_listening_wall_s": round(t_up, 3),
        "reconnect_walls_s": reconnect_walls,
        "streams_lost_tokens": lost,
        "streams_duplicated_tokens": dup,
        "greedy_token_parity": lost == 0 and dup == 0,
    }


# --- (b) open-loop overload -----------------------------------------


def run_overload(workdir, burst, max_inflight):
    jdir = os.path.join(workdir, "overload-journal")
    proc, port = spawn_gateway(
        jdir, extra_env={
            "ROUNDTABLE_GATEWAY_MAX_INFLIGHT": str(max_inflight)})
    admitted_ttfts, sheds, bad_sheds = [], [], []
    lock = threading.Lock()

    def one(i):
        t0 = time.monotonic()
        try:
            c = Conn(port, "POST", "/v1/discussions",
                     body={"session": f"ol{i}", "max_new_tokens": 8,
                           "turns": [{"knight": "lancelot",
                                      "prompt": PROMPTS[0]}]})
            if c.status == 200:
                ttft = None
                for eid, data in c.events():
                    ev = json.loads(data)
                    if ev["type"] in ("tokens", "summary"):
                        ttft = time.monotonic() - t0
                    if ev["type"] in ("retired", "failed"):
                        break
                c.close()
                with lock:
                    admitted_ttfts.append(ttft)
            else:
                payload = c.body_json()
                retry = c.headers.get("retry-after")
                c.close()
                entry = {"status": c.status,
                         "reason": payload.get("reason"),
                         "retry_after": retry}
                ok = (c.status in (429, 503) and retry is not None
                      and bool(payload.get("reason")))
                with lock:
                    (sheds if ok else bad_sheds).append(entry)
        except Exception as e:  # noqa: BLE001 — recorded, not fatal
            with lock:
                bad_sheds.append({"error": repr(e)})

    try:
        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(burst)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
    finally:
        proc.kill()
        proc.wait(30)

    ttfts = sorted(t for t in admitted_ttfts if t is not None)
    p95 = (ttfts[min(int(len(ttfts) * 0.95), len(ttfts) - 1)]
           if ttfts else None)
    reasons = {}
    for s in sheds:
        reasons[s["reason"]] = reasons.get(s["reason"], 0) + 1
    return {
        "burst": burst,
        "max_inflight": max_inflight,
        "admitted": len(admitted_ttfts),
        "shed": len(sheds),
        "shed_reasons": reasons,
        "malformed_sheds": bad_sheds,
        "admitted_ttft_p95_s": round(p95, 3) if p95 else None,
        "admitted_ttft_max_s": round(ttfts[-1], 3) if ttfts else None,
        "sheds_well_formed": not bad_sheds,
    }


# --- (c) router: rolling restart + replica scaling (ISSUE 17) --------


def post_json(port, path, body):
    c = Conn(port, "POST", path, body=body)
    status, payload = c.status, c.body_json()
    c.close()
    return status, payload


def stream_turn(port, body, tries=24):
    """One discussion turn as an open-loop client: retries classified
    sheds (429/503 + Retry-After) and reconnects mid-stream failures
    through the Last-Event-ID resume ladder. Returns (tokens,
    reconnects, sheds) or (None, ...) when every try failed."""
    toks, meta, last_id = [], None, None
    reconnects = sheds = 0
    for _ in range(tries):
        try:
            if meta is None:
                c = Conn(port, "POST", "/v1/discussions", body=body)
            else:
                hdrs = ({"Last-Event-ID": last_id} if last_id else {})
                c = Conn(port, "GET",
                         f"/v1/streams/{meta['stream']}",
                         headers=hdrs)
        except OSError:
            time.sleep(0.5)
            continue
        if c.status != 200:
            retry = c.headers.get("retry-after")
            c.body_json()
            c.close()
            if meta is None:
                sheds += 1
                time.sleep(min(float(retry or 0.5), 1.0))
            else:
                reconnects += 1
                time.sleep(0.5)
            continue
        if meta is not None:
            reconnects += 1
        terminal = None
        for eid, data in c.events():
            ev = json.loads(data)
            if ev["type"] == "stream":
                meta = ev
            elif ev["type"] in ("tokens", "summary"):
                toks.append((eid, ev))
                last_id = eid
            else:
                terminal = ev
                break
        c.close()
        if terminal and terminal["type"] == "retired":
            return flat_tokens(toks), reconnects, sheds
        time.sleep(0.5)  # failed/truncated: reconnect and resume
    return None, reconnects, sheds


def run_roll(workdir, n_streams, max_new, turns):
    """Rolling restart of replica r0 in a 2-replica fleet while every
    client is mid-discussion (open-loop: each session runs `turns`
    sequential turns). Zero failed sessions, zero lost/duplicated
    tokens, greedy parity against an unrolled reference fleet."""
    jdir = os.path.join(workdir, "roll-journal")
    proc, port = spawn_gateway(
        jdir, replicas=2,
        extra_env={"ROUNDTABLE_ROUTER_ROLL_TIMEOUT_S": "120"})
    refs = []
    outs = [[None] * turns for _ in range(n_streams)]
    stats = [{"reconnects": 0, "sheds": 0} for _ in range(n_streams)]
    roll_status, roll_payload = None, None
    try:
        for i in range(n_streams):
            per = []
            for t in range(turns):
                _m, toks, term = read_stream(
                    port, "/v1/discussions",
                    {"session": f"ref-roll{i}",
                     "max_new_tokens": max_new,
                     "turns": [{"knight": "lancelot",
                                "prompt": PROMPTS[(i + t)
                                                  % len(PROMPTS)]}]})
                assert term["type"] == "retired"
                per.append(flat_tokens(toks))
            refs.append(per)

        def client(i):
            for t in range(turns):
                got, rc, sh = stream_turn(
                    port, {"session": f"roll{i}",
                           "max_new_tokens": max_new,
                           "turns": [{"knight": "lancelot",
                                      "prompt": PROMPTS[(i + t)
                                                        % len(PROMPTS)]
                                      }]})
                outs[i][t] = got
                stats[i]["reconnects"] += rc
                stats[i]["sheds"] += sh

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n_streams)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        time.sleep(0.3)  # land the roll while turn 1 is in flight
        roll_status, roll_payload = post_json(
            port, "/v1/admin/roll", {"replica": "r0"})
        for t in threads:
            t.join(600)
        wall = time.monotonic() - t0
    finally:
        proc.kill()
        proc.wait(30)

    failed_sessions = sum(
        1 for per in outs if any(g is None for g in per))
    lost = dup = 0
    for per, ref_per in zip(outs, refs):
        for got, ref in zip(per, ref_per):
            if got is None or got == ref:
                continue
            if len(got) < len(ref) or got[:len(ref)] != ref:
                lost += 1
            else:
                dup += 1
    rolled = (roll_payload or {}).get("rolled") or []
    return {
        "streams": n_streams,
        "turns_per_session": turns,
        "max_new_tokens": max_new,
        "roll_status": roll_status,
        "roll_reports": rolled,
        "roll_ok": (roll_status == 200
                    and all(r.get("ok") for r in rolled)),
        "failed_sessions": failed_sessions,
        "turns_lost_tokens": lost,
        "turns_duplicated_tokens": dup,
        "reconnects": [s["reconnects"] for s in stats],
        "sheds_retried": [s["sheds"] for s in stats],
        "greedy_token_parity": (failed_sessions == 0 and lost == 0
                                and dup == 0),
        "wall_s": round(wall, 3),
    }


def measure_throughput(workdir, replicas, n_streams, max_new):
    """Aggregate decode tok/s over `n_streams` concurrent sessions —
    the 1 -> 2 replica scaling point. CPU walls: the shape of the
    harness, not a TPU throughput claim (cpu_wall_caveat)."""
    jdir = os.path.join(workdir, f"scale-{replicas}-journal")
    proc, port = spawn_gateway(jdir, replicas=replicas)
    try:
        # Warm the compile caches on EVERY replica so the measured
        # window is decode: the warm streams run at the same
        # concurrency as the measurement, so load-based placement
        # spreads them (and their compiles) across the fleet.
        warm = [threading.Thread(
            target=lambda i=i: read_stream(
                port, "/v1/discussions",
                {"session": f"warm{i}", "max_new_tokens": 4,
                 "turns": [{"knight": "lancelot",
                            "prompt": PROMPTS[0]}]}))
            for i in range(n_streams)]
        for t in warm:
            t.start()
        for t in warm:
            t.join(600)
        counts = [0] * n_streams

        def one(i):
            _m, toks, term = read_stream(
                port, "/v1/discussions",
                {"session": f"s{i}", "max_new_tokens": max_new,
                 "turns": [{"knight": "lancelot",
                            "prompt": PROMPTS[i % len(PROMPTS)]}]})
            if term and term["type"] == "retired":
                counts[i] = len(flat_tokens(toks))

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(n_streams)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(600)
        wall = time.monotonic() - t0
    finally:
        proc.kill()
        proc.wait(30)
    total = sum(counts)
    return {
        "replicas": replicas,
        "streams": n_streams,
        "tokens": total,
        "wall_s": round(wall, 3),
        "agg_tok_s": round(total / wall, 2) if wall > 0 else None,
    }


def main_router(args) -> int:
    """--replicas 2 mode: ROUTER_r17.json (ISSUE 17 acceptance)."""
    import tempfile
    n_streams = 1 if args.smoke else 3
    max_new = 8 if args.smoke else 24
    turns = 2 if args.smoke else 3

    t0 = time.monotonic()
    with tempfile.TemporaryDirectory(prefix="rtbench-") as workdir:
        roll = run_roll(workdir, n_streams, max_new, turns)
        scaling = None
        if not args.smoke:
            scaling = [measure_throughput(workdir, n, 4, 24)
                       for n in (1, 2)]

    meets = (roll["roll_ok"] and roll["greedy_token_parity"]
             and roll["failed_sessions"] == 0)
    if not args.smoke:
        lint = subprocess.run(
            [sys.executable, "-m", "theroundtaible_tpu", "lint"],
            cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True)
        meets = (meets and lint.returncode == 0
                 and all(s["agg_tok_s"] for s in scaling))
    record = {
        "metric": "router_rolling_restart",
        "value": roll["wall_s"],
        "unit": "roll_under_load_wall_s",
        "detail": {
            "rolling_restart": roll,
            "replica_scaling": scaling,
            "lint_exit": None if args.smoke else lint.returncode,
            "acceptance": {
                "criterion": "rolling restart of one replica in a "
                             "2-replica fleet under open-loop gateway "
                             "load: zero failed sessions, zero "
                             "lost/duplicated tokens, greedy parity "
                             "across the roll; aggregate tok/s "
                             "recorded at 1 and 2 replicas",
                "meets": meets,
            },
            "cpu_wall_caveat": True,
            "platform": "cpu",
            "wall_s": round(time.monotonic() - t0, 1),
        },
    }
    print(json.dumps(record, indent=1))
    if args.smoke:
        return 0 if meets else 1
    out = args.out or os.path.join(REPO, "ROUTER_r17.json")
    with open(out, "w", encoding="utf-8") as f:
        json.dump(record, f, indent=1)
        f.write("\n")
    print(f"wrote {out}", file=sys.stderr)
    return 0 if meets else 1


# --- (d) end-to-end tracing (ISSUE 20) -------------------------------


def _leg_gap_ok(leg, frac=0.05, floor=0.02):
    """The acceptance invariant: a leg's stage sum telescopes to its
    wall — within 5% (or a small absolute floor for sub-second legs)."""
    return abs(leg.get("stage_gap_s", 0.0)) <= max(
        frac * leg.get("wall_s", 0.0), floor)


def _trace_env(tdir):
    return {"ROUNDTABLE_TRACE_DIR": tdir,
            "ROUNDTABLE_TRACE_SAMPLE": "1",
            "ROUNDTABLE_TELEMETRY": "1"}


def run_trace_chaos(workdir, n_streams, max_new):
    """One trace per client request across the full recovery ladder:
    leg 1 dies with its replica (device_lost), leg 2 is the failover
    restore on the survivor (replica_crossed), kill -9 lands between
    legs, and leg 3 is the post-`--resume` committed replay in a NEW
    process. Every leg is tail-retained or head-sampled at 1.0, so the
    on-disk trace file stitches all generations."""
    from theroundtaible_tpu.utils import tracing

    jdir = os.path.join(workdir, "trace-journal")
    tdir = os.path.join(workdir, "trace-retained")
    env = dict(_trace_env(tdir), ROUNDTABLE_FAULTS="device_lost:1")
    proc, port = spawn_gateway(jdir, replicas=2, extra_env=env)

    clients = [{"session": f"tr{i}", "trace": None, "stream": None,
                "tokens": 0, "failed_leg": False, "last_id": None,
                "walls_s": []} for i in range(n_streams)]
    try:
        conns = []
        t_open = time.monotonic()
        for i, cl in enumerate(clients):
            c = Conn(port, "POST", "/v1/discussions",
                     body={"session": cl["session"],
                           "max_new_tokens": max_new,
                           "turns": [{"knight": "lancelot",
                                      "prompt": PROMPTS[
                                          i % len(PROMPTS)]}]})
            assert c.status == 200
            conns.append(c)
        # Leg 1: read each stream to its terminal. The armed
        # device_lost kills whichever replica dispatches next, so its
        # streams terminate `failed` (their legs finish `interrupted`,
        # flagged, WRITTEN); survivor streams retire clean.
        for cl, c in zip(clients, conns):
            it = c.events()
            meta = json.loads(next(it)[1])
            cl["trace"], cl["stream"] = meta["trace"], meta["stream"]
            assert cl["trace"], "metadata event carries no trace id"
            for eid, data in it:
                ev = json.loads(data)
                if ev["type"] in ("tokens", "summary"):
                    cl["tokens"] += len(flat_tokens([(eid, ev)]))
                    cl["last_id"] = eid
                elif ev["type"] == "failed":
                    cl["failed_leg"] = True
                    break
                elif ev["type"] == "retired":
                    break
            c.close()
            cl["walls_s"].append(round(time.monotonic() - t_open, 3))
        # Leg 2: failed clients reconnect INSIDE the same process —
        # the router failover restores them on the survivor, which is
        # the guaranteed replica_crossed leg. Read to retirement so
        # the leg record flushes before the SIGKILL.
        for cl in clients:
            if not cl["failed_leg"]:
                continue
            t0, deadline = time.monotonic(), time.monotonic() + 90
            done = False
            while not done and time.monotonic() < deadline:
                hdrs = ({"Last-Event-ID": cl["last_id"]}
                        if cl["last_id"] else None)
                try:
                    meta2, toks2, term2 = read_stream(
                        port, f"/v1/streams/{cl['stream']}",
                        method="GET", headers=hdrs)
                except (AssertionError, OSError):
                    time.sleep(0.5)   # failover still settling
                    continue
                assert meta2["trace"] == cl["trace"], \
                    "failover leg minted a NEW trace id"
                cl["tokens"] += len(flat_tokens(toks2))
                if toks2:
                    cl["last_id"] = toks2[-1][0]
                done = term2 is not None and term2["type"] == "retired"
            assert done, f"{cl['session']} never recovered in leg 2"
            cl["walls_s"].append(round(time.monotonic() - t0, 3))
    finally:
        proc.kill()   # SIGKILL between legs: kill -9 crossing
        proc.wait(30)

    # Leg 3: a NEW process resumes the journal; every client
    # reconnects and replays its committed turn under the SAME trace.
    proc2, port2 = spawn_gateway(jdir, resume=jdir, replicas=2,
                                 extra_env=_trace_env(tdir))
    try:
        for cl in clients:
            t0 = time.monotonic()
            meta3, toks3, term3 = read_stream(
                port2, f"/v1/streams/{cl['stream']}", method="GET")
            assert term3 and term3["type"] == "retired", \
                f"{cl['session']}: post-restart replay did not retire"
            assert meta3["trace"] == cl["trace"], \
                "post-restart leg minted a NEW trace id"
            replayed = len(flat_tokens(toks3))
            assert replayed >= cl["tokens"], \
                f"{cl['session']}: replay lost tokens"
            cl["walls_s"].append(round(time.monotonic() - t0, 3))
    finally:
        proc2.kill()
        proc2.wait(30)

    # Judge the retained traces.
    traces = tracing.load_traces(tdir)
    want = {cl["trace"] for cl in clients}
    orphans = sorted(set(traces) - want)
    stitched, gap_violations, crossed = [], [], 0
    max_gap_frac = 0.0
    for cl in clients:
        legs = traces.get(cl["trace"], [])
        for leg in legs:
            if not _leg_gap_ok(leg):
                gap_violations.append(
                    {"trace": cl["trace"],
                     "gap_s": leg.get("stage_gap_s"),
                     "wall_s": leg.get("wall_s")})
            if leg.get("wall_s", 0.0) > 0:
                max_gap_frac = max(
                    max_gap_frac, abs(leg.get("stage_gap_s", 0.0))
                    / leg["wall_s"])
        s = tracing.stitch(legs)
        if "replica_crossed" in s["flags"]:
            crossed += 1
        stitched.append({
            "session": cl["session"], "trace": cl["trace"],
            "legs": s["legs"], "pids": len(s["pids"]),
            "outcome": s["outcome"], "flags": s["flags"],
            "wall_s": s["wall_s"], "stage_sum_s": s["stage_sum_s"],
            "ttft_s": s["ttft_s"], "stages": s["stages"],
            "client_leg_walls_s": cl["walls_s"],
        })
    # Structural orphan check: every retained trace roots in a
    # `request` leg; later legs are `resume` joins, never new roots.
    malformed = [
        tid for tid, legs in traces.items()
        if legs[0].get("kind") != "request"
        or any(leg.get("kind") not in ("request", "resume")
               for leg in legs)]
    one_per_client = (
        len(want) == n_streams
        and all(s["legs"] >= 2 and s["pids"] >= 2 for s in stitched))
    return {
        "streams": n_streams,
        "max_new_tokens": max_new,
        "stitched": stitched,
        "one_stitched_trace_per_client": one_per_client,
        "replicas_crossed": crossed,
        "stage_gap_violations": gap_violations,
        "max_leg_gap_frac": round(max_gap_frac, 4),
        "orphan_traces": orphans,
        "malformed_traces": malformed,
        "zero_orphans": not orphans and not malformed,
        "stage_sum_within_5pct": not gap_violations,
    }


def run_trace_sweep(workdir, smoke):
    """Open-loop loadgen sweep against a traced child gateway: every
    per-session client record carries the trace id from the SSE
    events, and joins to a server-side retained leg — the per-stage
    p95 table attributes the sweep's TTFT tail to named stages."""
    from theroundtaible_tpu.loadgen.arrivals import make_arrivals
    from theroundtaible_tpu.loadgen.driver import GatewayDriver
    from theroundtaible_tpu.loadgen.sweep import run_point
    from theroundtaible_tpu.loadgen.workload import WorkloadMix
    from theroundtaible_tpu.utils import tracing

    jdir = os.path.join(workdir, "sweep-journal")
    tdir = os.path.join(workdir, "sweep-retained")
    proc, port = spawn_gateway(
        jdir, extra_env=dict(_trace_env(tdir),
                             ROUNDTABLE_GATEWAY_MAX_INFLIGHT="4"))
    rates = [2.0, 6.0] if smoke else [2.0, 6.0, 12.0]
    duration_s = 2.0 if smoke else 5.0
    points = []
    try:
        mix = WorkloadMix(max_new_tokens=4, max_turns=1,
                          prompt_words=(3, 12))
        process = make_arrivals("poisson", 7)
        driver = GatewayDriver(port)
        for i, rate in enumerate(rates):
            p = run_point(driver, process, mix, rate_rps=rate,
                          duration_s=duration_s, seed=7,
                          point_index=i + 1, n_devices=1)
            points.append({
                "offered_rps": p["offered_rps"],
                "admitted": p["admitted"], "shed": p["shed"],
                "ttft_p95_s": p.get("ttft_p95_s"),
                "exemplar_traces": p.get("exemplar_traces", []),
            })
    finally:
        proc.kill()
        proc.wait(30)

    legs = [leg for l in tracing.load_traces(tdir).values()
            for leg in l]

    def p95(vals):
        if not vals:
            return None
        v = sorted(vals)
        return round(v[min(int(len(v) * 0.95), len(v) - 1)], 6)

    from theroundtaible_tpu.utils.tracing import STAGES
    stage_p95 = {
        s: p95([leg["stages"][s] for leg in legs
                if s in leg.get("stages", {})])
        for s in STAGES}
    exemplars = [t for p in points for t in p["exemplar_traces"]]
    joined = [t for t in exemplars
              if t in {leg["trace_id"] for leg in legs}]
    return {
        "points": points,
        "retained_legs": len(legs),
        "stage_p95_s": {k: v for k, v in stage_p95.items()
                        if v is not None},
        "stage_gap_p95_s": p95([abs(leg.get("stage_gap_s", 0.0))
                                for leg in legs]),
        "exemplars_joined": f"{len(joined)}/{len(exemplars)}",
        "exemplars_join_retained": (bool(exemplars)
                                    and len(joined) == len(exemplars)),
    }


def run_burn_probe(workdir):
    """The SLO burn monitor's two-sided acceptance in-process: quiet
    on an under-SLO baseline, exactly one flight dump on an induced
    sustained breach (multiwindow rule + per-window cooldown)."""
    os.environ["ROUNDTABLE_TELEMETRY_DIR"] = os.path.join(workdir,
                                                          "dumps")
    from theroundtaible_tpu.utils import tracing

    baseline = tracing.SloBurnMonitor(0.5, error_budget=0.05,
                                      fast_window_s=60,
                                      slow_window_s=600)
    for _ in range(32):
        baseline.note_ttft(0.01)
    induced = tracing.SloBurnMonitor(0.001, error_budget=0.05,
                                     fast_window_s=60,
                                     slow_window_s=600)
    for _ in range(32):
        induced.note_ttft(0.4, trace_id="bench-induced")
    return {
        "baseline_breaches": baseline.breaches,
        "induced_breaches": induced.breaches,
        "induced_dump": os.path.basename(induced.last_dump_path),
        "induced_burn": induced.burn_rates(),
        "quiet_on_baseline": baseline.breaches == 0,
        "fires_once_on_breach": (induced.breaches == 1
                                 and bool(induced.last_dump_path)),
    }


def main_trace(args) -> int:
    """--trace mode: TRACE_r20.json (ISSUE 20 acceptance)."""
    import tempfile
    n_streams = 1 if args.smoke else 3
    max_new = 8 if args.smoke else 24

    t0 = time.monotonic()
    with tempfile.TemporaryDirectory(prefix="trbench-") as workdir:
        chaos = run_trace_chaos(workdir, n_streams, max_new)
        sweep = run_trace_sweep(workdir, args.smoke)
        burn = run_burn_probe(workdir)

    meets = (chaos["one_stitched_trace_per_client"]
             and chaos["stage_sum_within_5pct"]
             and chaos["zero_orphans"]
             and chaos["replicas_crossed"] >= 1
             and sweep["exemplars_join_retained"]
             and burn["quiet_on_baseline"]
             and burn["fires_once_on_breach"])
    if not args.smoke:
        lint = subprocess.run(
            [sys.executable, "-m", "theroundtaible_tpu", "lint"],
            cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True)
        meets = meets and lint.returncode == 0
    record = {
        "metric": "request_tracing",
        "value": chaos["max_leg_gap_frac"],
        "unit": "max_leg_stage_gap_frac",
        "detail": {
            "chaos": chaos,
            "loadgen_sweep": sweep,
            "slo_burn": burn,
            "lint_exit": None if args.smoke else lint.returncode,
            "acceptance": {
                "criterion": "device_lost failover + kill -9 + "
                             "--resume under concurrent streams: one "
                             "stitched on-disk trace per client "
                             "request across process generations, "
                             "per-leg stage sum within 5% of the leg "
                             "wall, zero orphan legs, >=1 "
                             "replica_crossed leg; loadgen exemplar "
                             "traces join retained server legs with "
                             "per-stage p95 attribution; burn monitor "
                             "quiet on baseline, fires once on an "
                             "induced breach",
                "meets": meets,
            },
            "cpu_wall_caveat": True,
            "platform": "cpu",
            "wall_s": round(time.monotonic() - t0, 1),
        },
    }
    print(json.dumps(record, indent=1))
    if args.smoke:
        return 0 if meets else 1
    out = args.out or os.path.join(REPO, "TRACE_r20.json")
    with open(out, "w", encoding="utf-8") as f:
        json.dump(record, f, indent=1)
        f.write("\n")
    print(f"wrote {out}", file=sys.stderr)
    return 0 if meets else 1


# --- driver ----------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="1-stream chaos + small burst; no artifact")
    ap.add_argument("--replicas", type=int, default=1,
                    help=">1 switches to the router acceptance "
                         "(rolling restart + scaling, ROUTER_r17.json)")
    ap.add_argument("--trace", action="store_true",
                    help="end-to-end tracing acceptance "
                         "(chaos stitch + sweep attribution + burn "
                         "monitor, TRACE_r20.json)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    if args.trace:
        return main_trace(args)
    if args.replicas > 1:
        return main_router(args)
    args.out = args.out or os.path.join(REPO, "GATEWAY_r16.json")

    import tempfile
    n_streams = 1 if args.smoke else 3
    # full mode spans two 64-token decode segments so the SIGKILL
    # lands on an UNCOMMITTED turn (reconnect leg 3: greedy
    # regeneration), not just a journaled one (leg 2).
    max_new = 12 if args.smoke else 96
    burst = 4 if args.smoke else 12

    t0 = time.monotonic()
    with tempfile.TemporaryDirectory(prefix="gwbench-") as workdir:
        chaos = run_chaos(workdir, n_streams, max_new)
        overload = run_overload(workdir, burst, max_inflight=2)

    lint = subprocess.run(
        [sys.executable, "-m", "theroundtaible_tpu", "lint"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True)

    meets = (chaos["greedy_token_parity"]
             and overload["sheds_well_formed"]
             and overload["shed"] > 0
             and lint.returncode == 0)
    record = {
        "metric": "gateway_slo_serving",
        "value": chaos["restart_to_listening_wall_s"],
        "unit": "restart_to_listening_wall_s",
        "detail": {
            "chaos_kill9": chaos,
            "open_loop_overload": overload,
            "recompile_strict_armed": True,
            "lint_exit": lint.returncode,
            "acceptance": {
                "criterion": "kill -9 under concurrent streams, "
                             "restart --resume, every client "
                             "reconnects via Last-Event-ID with zero "
                             "lost/duplicated tokens and greedy "
                             "parity; overload sheds carry 429 + "
                             "Retry-After + machine-readable reason "
                             "while admitted p95 TTFT stays bounded; "
                             "lint exits 0 with strict recompile "
                             "armed across the restart",
                "meets": meets,
            },
            "cpu_wall_caveat": True,
            "platform": "cpu",
            "wall_s": round(time.monotonic() - t0, 1),
        },
    }
    print(json.dumps(record, indent=1))
    if args.smoke:
        return 0 if meets else 1
    with open(args.out, "w", encoding="utf-8") as f:
        json.dump(record, f, indent=1)
        f.write("\n")
    print(f"wrote {args.out}", file=sys.stderr)
    return 0 if meets else 1


if __name__ == "__main__":
    sys.exit(main())
