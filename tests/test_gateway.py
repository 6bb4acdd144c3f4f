"""Streaming serving gateway suite (ISSUE 16).

Covers the acceptance criteria on the CPU backend:
- OpenAI-compatible `/v1/chat/completions` over a REAL socket, with the
  streamed deltas byte-identical to the non-streaming response (greedy
  determinism end to end through the committed-token seam);
- native `/v1/discussions` multi-knight streams with crash-consistent
  event ids (`turn:c0,c1,...` — one id is the whole multi-row
  watermark) and `Last-Event-ID` reconnects that lose and duplicate
  NOTHING;
- SLO-driven admission: shed with 429/503 + Retry-After +
  machine-readable reason at the inflight cap / drain gate, deadline
  propagation failing an already-spent budget fast (408, its own
  classified error kind, zero prefill consumed);
- `pause_admission(reason)` threading verbatim into SchedulerRefused
  and `describe()["admission"]`;
- the factored `resume_from_journal` library seam (`commands.serve`
  re-export identity) and post-restart stream restoration from the
  intent journal (reconnect ladder leg 2);
- the RT-GAUGE-LEAK contract on `roundtable_gateway_inflight_streams`
  and the describe()/SURFACE_BINDINGS drift bound;
- the kill -9 chaos acceptance (slow): 3 concurrent streams, SIGKILL,
  restart `--resume`, every client reconnects via Last-Event-ID with
  greedy token parity vs the uninterrupted run.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import time

import pytest

jax = pytest.importorskip("jax")

from theroundtaible_tpu.core.errors import classify_error
from theroundtaible_tpu.engine import deadlines, faults
from theroundtaible_tpu.engine.engine import InferenceEngine
from theroundtaible_tpu.engine.models.registry import get_model_config
from theroundtaible_tpu.engine.scheduler import (DeadlineExpired,
                                                 SchedulerRefused,
                                                 SessionScheduler)
from theroundtaible_tpu.engine.session_journal import SessionJournal
from theroundtaible_tpu.gateway import Gateway
from theroundtaible_tpu.gateway.admission import AdmissionController
from theroundtaible_tpu.gateway.streams import (format_event_id,
                                                parse_event_id)
from theroundtaible_tpu.utils import telemetry

MODEL_KW = dict(max_seq_len=512)

PROMPT = ("The round table met at dawn to discuss the castle walls "
          "and the eastern gate.")
PROMPT2 = ("A different discussion entirely, about dragons and the "
           "kingdom's gold reserves.")


@pytest.fixture(autouse=True)
def clean_faults():
    faults.disarm()
    deadlines.reset_rungs()
    deadlines.disarm_watchdog()
    deadlines.clear_hang_log()
    deadlines.end_drain()
    yield
    faults.disarm()
    deadlines.reset_rungs()
    deadlines.disarm_watchdog()
    deadlines.clear_hang_log()
    deadlines.end_drain()


def make_engine(**kw):
    """The suite's subject is not the kernels: the gather view (XLA
    alone) over small pages."""
    cfg = get_model_config("tiny-gemma", **MODEL_KW)
    kw.setdefault("num_slots", 8)
    kw.setdefault("attn", "dense")
    kw.setdefault("page_size", 32)
    return InferenceEngine(cfg, **kw)


@pytest.fixture(scope="module")
def shared_engine():
    return make_engine()


@pytest.fixture(scope="module")
def unit_engine():
    """A second engine for scheduler-level unit tests, so they never
    share slot capacity with the module gateway's live scheduler."""
    return make_engine()


@pytest.fixture(scope="module")
def gw(shared_engine, tmp_path_factory):
    jdir = tmp_path_factory.mktemp("gw-journal")
    sched = SessionScheduler(shared_engine,
                             journal=SessionJournal(jdir))
    g = Gateway(sched, port=0, intent_dir=str(jdir))
    g.start_in_thread()
    yield g
    g.stop()
    sched.close()


# ---------------------------------------------------------------------
# A minimal raw-socket HTTP/SSE client (http.client buffers SSE).
# ---------------------------------------------------------------------


class Conn:
    def __init__(self, port, method, path, body=None, headers=None,
                 timeout=120.0):
        self.sock = socket.create_connection(("127.0.0.1", port),
                                             timeout=timeout)
        payload = (json.dumps(body).encode("utf-8")
                   if body is not None else b"")
        head = (f"{method} {path} HTTP/1.1\r\nHost: t\r\n"
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
        """Yield (event_id, data_str) per SSE event until EOF."""
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
    """Full native-stream read: returns (meta, token_events, terminal)
    where token_events is [(event_id, payload_dict), ...]."""
    c = Conn(port, method, path, body=body, headers=headers)
    assert c.status == 200, c.body_json()
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


def row_tokens(toks, rows):
    """Per-row concatenated token ids from a token-event list."""
    out = [[] for _ in range(rows)]
    for _eid, ev in toks:
        if ev["type"] == "tokens":
            out[ev["row"]].extend(ev["tokens"])
        else:  # summary
            for i, d in ev["rows"].items():
                out[int(i)].extend(d["tokens"])
    return out


# ---------------------------------------------------------------------
# chat completions
# ---------------------------------------------------------------------


@pytest.mark.gateway
class TestChatCompletions:
    def test_stream_matches_nonstream(self, gw):
        """Greedy determinism through the whole stack: the SSE deltas
        concatenate to exactly the non-streaming response for the same
        prompt (different sessions, same prefill)."""
        body = {"model": "lancelot", "max_tokens": 8,
                "messages": [{"role": "user", "content": PROMPT}]}
        c = Conn(gw.port, "POST", "/v1/chat/completions",
                 body=dict(body, session="chat-ns"))
        assert c.status == 200
        full = c.body_json()
        c.close()
        text = full["choices"][0]["message"]["content"]
        assert full["choices"][0]["finish_reason"] == "stop"
        assert full["usage"]["completion_tokens"] > 0

        c = Conn(gw.port, "POST", "/v1/chat/completions",
                 body=dict(body, session="chat-st", stream=True))
        assert c.status == 200
        assert c.headers["content-type"].startswith("text/event-stream")
        deltas, done, finish = [], False, None
        for _eid, data in c.events():
            if data == "[DONE]":
                done = True
                break
            chunk = json.loads(data)
            choice = chunk["choices"][0]
            deltas.append(choice["delta"].get("content", ""))
            if choice["finish_reason"]:
                finish = choice["finish_reason"]
        c.close()
        assert done and finish == "stop"
        assert "".join(deltas) == text

    @pytest.mark.gateway(allow_no_stream=True)
    def test_healthz_and_metrics(self, gw):
        c = Conn(gw.port, "GET", "/healthz")
        h = c.body_json()
        c.close()
        assert c.status == 200 and h["ok"] and not h["draining"]
        c = Conn(gw.port, "GET", "/metrics")
        assert c.status == 200
        text = c.f.read().decode("utf-8")
        c.close()
        assert "roundtable_gateway_admitted_total" in text


# ---------------------------------------------------------------------
# native discussions: event ids, reconnect, gauge hygiene
# ---------------------------------------------------------------------


@pytest.mark.gateway
class TestDiscussions:
    def test_multi_row_event_ids_and_gauge(self, gw):
        """Two knights stream through one id-sequence; the event ids
        carry the cumulative per-row watermark; the per-stream inflight
        gauge dies with the stream (RT-GAUGE-LEAK)."""
        body = {"session": "disc-ids", "max_new_tokens": 6,
                "turns": [{"knight": "lancelot", "prompt": PROMPT},
                          {"knight": "galahad", "prompt": PROMPT2}]}
        meta, toks, terminal = read_stream(gw.port, "/v1/discussions",
                                           body)
        assert meta is not None and meta["knights"] == ["lancelot",
                                                        "galahad"]
        assert terminal is not None and terminal["type"] == "retired"
        per_row = row_tokens(toks, 2)
        assert all(len(r) > 0 for r in per_row)

        # ids: parseable, same turn, and EXACT per event — each id's
        # counts equal precisely the tokens delivered up to and
        # including that event (not the whole batch's post-state), so
        # a client cut off anywhere holds a watermark that skips
        # nothing on reconnect.
        running = [0, 0]
        for eid, ev in toks:
            parsed = parse_event_id(eid, 2)
            assert parsed is not None and parsed[0] == meta["turn"]
            if ev["type"] == "tokens":
                running[ev["row"]] += len(ev["tokens"])
            else:  # summary
                for i, d in ev["rows"].items():
                    running[int(i)] += len(d["tokens"])
            assert parsed[1] == running, (
                f"event id {eid} counts tokens the client has not "
                f"received yet (delivered so far: {running})")
        assert running == [len(r) for r in per_row]

        # the stream retired -> its gauge series must be GONE.
        sid = meta["stream"]
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if telemetry.REGISTRY.gauge_value(
                    "roundtable_gateway_inflight_streams",
                    request=sid) is None:
                break
            time.sleep(0.05)
        assert telemetry.REGISTRY.gauge_value(
            "roundtable_gateway_inflight_streams", request=sid) is None

    def test_reconnect_watermark_no_loss_no_dup(self, gw):
        """A client that saw a mid-stream event id reconnects with it
        as Last-Event-ID and receives EXACTLY the rest: prefix + resume
        == the full stream, token for token."""
        body = {"session": "disc-rc", "max_new_tokens": 6,
                "turns": [{"knight": "lancelot", "prompt": PROMPT},
                          {"knight": "galahad", "prompt": PROMPT2}]}
        meta, toks, terminal = read_stream(gw.port, "/v1/discussions",
                                           body)
        assert terminal["type"] == "retired"
        full = row_tokens(toks, 2)
        assert toks, "stream produced no token events"

        # Watermark = after the FIRST token event.
        mid_id = toks[0][0]
        mid = parse_event_id(mid_id, 2)[1]
        prefix = [full[i][:mid[i]] for i in range(2)]

        meta2, toks2, terminal2 = read_stream(
            gw.port, f"/v1/streams/{meta['stream']}", method="GET",
            headers={"Last-Event-ID": mid_id})
        assert meta2["stream"] == meta["stream"]
        assert terminal2["type"] == "retired"
        resumed = row_tokens(toks2, 2)
        assert [p + r for p, r in zip(prefix, resumed)] == full, \
            "reconnect lost or duplicated tokens"
        assert gw.resumed_streams >= 1

    def test_restart_reconnect_serves_committed_turn(self, gw):
        """Reconnect ladder leg 2 in-process: a FRESH Gateway (empty
        stream table, reloaded intent journal — the post-restart state)
        serves a finished stream's tokens straight from the session
        journal's committed record."""
        body = {"session": "disc-restart", "max_new_tokens": 6,
                "turns": [{"knight": "lancelot", "prompt": PROMPT}]}
        meta, toks, terminal = read_stream(gw.port, "/v1/discussions",
                                           body)
        assert terminal["type"] == "retired"
        full = row_tokens(toks, 1)

        gw2 = Gateway(gw.sched, port=0,
                      intent_dir=str(gw.intents.root))
        gw2.start_in_thread()
        try:
            meta2, toks2, terminal2 = read_stream(
                gw2.port, f"/v1/streams/{meta['stream']}",
                method="GET")
            assert terminal2["type"] == "retired"
            assert row_tokens(toks2, 1) == full
            # and with the final watermark: nothing re-sent.
            final_id = format_event_id(meta["turn"],
                                       [len(full[0])])
            _m, toks3, terminal3 = read_stream(
                gw2.port, f"/v1/streams/{meta['stream']}",
                method="GET", headers={"Last-Event-ID": final_id})
            assert toks3 == [] and terminal3["type"] == "retired"
        finally:
            gw2.stop()

    def test_restart_regenerates_uncommitted_turn(self, gw,
                                                  unit_engine,
                                                  tmp_path):
        """Reconnect ladder leg 3 in-process: the stream's intent
        record survived but its turn is NOT in the session journal
        (the crash landed mid-round) — the restore re-submits from the
        recorded prompts and greedy regeneration reproduces the
        IDENTICAL token stream, the client's watermark skipping what
        it already saw."""
        body = {"session": "disc-leg3", "max_new_tokens": 6,
                "turns": [{"knight": "lancelot", "prompt": PROMPT2}]}
        meta, toks, terminal = read_stream(gw.port, "/v1/discussions",
                                           body)
        assert terminal["type"] == "retired"
        full = row_tokens(toks, 1)
        mid_id = toks[0][0]
        mid = parse_event_id(mid_id, 1)[1]

        # A scheduler whose session journal never saw the turn: the
        # committed-record leg is unavailable, so the restore MUST
        # regenerate (a different engine instance, same deterministic
        # weights — exactly the post-restart situation).
        sched2 = SessionScheduler(
            unit_engine, journal=SessionJournal(tmp_path / "empty"))
        gw3 = Gateway(sched2, port=0, intent_dir=str(gw.intents.root))
        gw3.start_in_thread()
        try:
            _m, toks3, term3 = read_stream(
                gw3.port, f"/v1/streams/{meta['stream']}",
                method="GET", headers={"Last-Event-ID": mid_id})
            assert term3 is not None and term3["type"] == "retired"
            resumed = row_tokens(toks3, 1)
            assert full[0][:mid[0]] + resumed[0] == full[0], \
                "leg-3 regeneration lost or duplicated tokens"
        finally:
            gw3.stop()
            sched2.close()

    @pytest.mark.gateway(allow_no_stream=True)
    def test_unknown_stream_404(self, gw):
        c = Conn(gw.port, "GET", "/v1/streams/deadbeef00000000")
        assert c.status == 404
        assert c.body_json()["reason"] == "unknown_stream"
        c.close()

    @pytest.mark.gateway(allow_no_stream=True)
    def test_restart_refuses_sampled_uncommitted(self, unit_engine,
                                                 tmp_path):
        """Reconnect ladder leg 3 only holds for GREEDY streams: an
        intent recorded with temperature > 0 whose turn never committed
        cannot regenerate byte-identically, so the reconnect is refused
        (409 nondeterministic_stream) instead of splicing a different
        token stream onto the client's watermark."""
        from theroundtaible_tpu.gateway.resume import StreamIntentJournal
        jdir = tmp_path / "sampled-intents"
        rec = StreamIntentJournal(jdir).record(
            "samp000000000001", session="s-sampled",
            knights=["lancelot"], prompts=[PROMPT], turn=0, max_new=4,
            temperature=0.8)
        assert rec is not None and rec["temperature"] == 0.8
        sched = SessionScheduler(
            unit_engine, journal=SessionJournal(tmp_path / "empty-j"))
        gws = Gateway(sched, port=0, intent_dir=str(jdir))
        gws.start_in_thread()
        try:
            c = Conn(gws.port, "GET", "/v1/streams/samp000000000001")
            assert c.status == 409
            assert c.body_json()["reason"] == "nondeterministic_stream"
            c.close()
        finally:
            gws.stop()
            sched.close()

    @pytest.mark.gateway(allow_no_stream=True)
    def test_late_pump_failure_no_second_head(self, gw):
        """A pump-path failure AFTER the SSE head went out must never
        write a second HTTP status line onto the same socket — the
        error arrives as a terminal `failed` SSE event mid-stream."""
        gwx = Gateway(gw.sched, port=0)

        def boom(_state, _ev):
            raise RuntimeError("pump exploded")

        gwx._native_payload = boom
        gwx.start_in_thread()
        c = None
        try:
            c = Conn(gwx.port, "POST", "/v1/discussions",
                     body={"session": "late-fail", "max_new_tokens": 2,
                           "turns": [{"knight": "lancelot",
                                      "prompt": PROMPT}]})
            assert c.status == 200  # the one and only response head
            raw = c.f.read()
            assert b"HTTP/1.1" not in raw, \
                "second HTTP head written mid-SSE-stream"
            datas = [json.loads(ln[6:].decode("utf-8"))
                     for ln in raw.split(b"\n")
                     if ln.startswith(b"data: ")]
            assert any(d.get("type") == "failed"
                       and d.get("kind") == "internal"
                       for d in datas)
        finally:
            if c is not None:
                c.close()
            gwx.stop()


# ---------------------------------------------------------------------
# admission: shed ladder, drain, deadline propagation
# ---------------------------------------------------------------------


@pytest.mark.gateway(allow_no_stream=True)
class TestAdmission:
    def test_inflight_cap_sheds_429(self, gw):
        """An at-cap gateway sheds with 429 + Retry-After + a
        machine-readable reason, and the counters move."""
        capped = Gateway(gw.sched, port=0,
                         admission=AdmissionController(
                             gw.sched, max_inflight=1))
        capped.start_in_thread()
        first = None
        try:
            shed0 = telemetry.REGISTRY.counter_total(
                "roundtable_gateway_shed_total", reason="inflight_cap")
            # Fill the one slot with a long stream; its metadata event
            # arriving proves the stream is registered inflight.
            first = Conn(capped.port, "POST", "/v1/discussions",
                         body={"session": "cap-a",
                               "max_new_tokens": 64,
                               "turns": [{"knight": "lancelot",
                                          "prompt": PROMPT}]})
            assert first.status == 200
            meta = json.loads(next(first.events())[1])
            assert meta["type"] == "stream"

            c = Conn(capped.port, "POST", "/v1/chat/completions",
                     body={"messages": [{"role": "user",
                                         "content": "hi"}]})
            assert c.status == 429
            payload = c.body_json()
            c.close()
            assert payload["reason"] == "inflight_cap"
            assert int(c.headers["retry-after"]) >= 1
            assert capped.admission.shed == 1
            assert telemetry.REGISTRY.counter_total(
                "roundtable_gateway_shed_total",
                reason="inflight_cap") == shed0 + 1
            assert capped.describe()["shed"] == 1
        finally:
            if first is not None:
                first.close()
            capped.stop()

    def test_drain_sheds_503(self, gw):
        """fleet drain / paused admission → 503 draining; a custom
        pause reason is machine-distinguishable."""
        gw.sched.pause_admission("fleet.drain")
        try:
            c = Conn(gw.port, "POST", "/v1/discussions",
                     body={"turns": [{"knight": "k", "prompt": "x"}]})
            assert c.status == 503
            assert c.body_json()["reason"] == "draining"
            assert "retry-after" in c.headers
            c.close()
            h = Conn(gw.port, "GET", "/healthz")
            assert h.body_json()["draining"] is True
            h.close()
        finally:
            gw.sched.reopen_admission()

        gw.sched.pause_admission("maintenance")
        try:
            c = Conn(gw.port, "POST", "/v1/discussions",
                     body={"turns": [{"knight": "k", "prompt": "x"}]})
            assert c.status == 503
            assert c.body_json()["reason"] == "paused:maintenance"
            c.close()
        finally:
            gw.sched.reopen_admission()

    def test_deadline_expired_sheds_408(self, gw):
        """A spent client deadline never reaches the scheduler: 408
        with the deadline_expired reason, expired counter moves."""
        e0 = telemetry.REGISTRY.counter_total(
            "roundtable_gateway_expired_total",
            reason="deadline_expired")
        c = Conn(gw.port, "POST", "/v1/chat/completions",
                 body={"messages": [{"role": "user", "content": "hi"}]},
                 headers={"X-Roundtable-Deadline-S": "0"})
        assert c.status == 408
        assert c.body_json()["reason"] == "deadline_expired"
        c.close()
        assert telemetry.REGISTRY.counter_total(
            "roundtable_gateway_expired_total",
            reason="deadline_expired") == e0 + 1

    def test_queued_counter_counts_queue_path(self, unit_engine):
        """An admission that parks behind a NONEMPTY scheduler queue
        is the queue path: Decision.queued rides into note_admitted and
        moves roundtable_gateway_queued_total in lockstep."""

        class _StubSched:
            paused = None

            def __init__(self, engine, depth):
                self.engine = engine
                self._depth = depth

            def describe(self):
                return {"admission": {"queued": self._depth}}

        q0 = telemetry.REGISTRY.counter_total(
            "roundtable_gateway_queued_total", reason="behind_queue")
        adm = AdmissionController(_StubSched(unit_engine, 3),
                                  max_inflight=8, max_queue_depth=16)
        d = adm.decide(rows=1, inflight=1)
        assert d.admit and d.queued
        adm.note_admitted(queued=d.queued)
        assert adm.admitted == 1 and adm.queued == 1
        assert adm.describe()["queued"] == 1
        assert telemetry.REGISTRY.counter_total(
            "roundtable_gateway_queued_total",
            reason="behind_queue") == q0 + 1

        # Empty scheduler queue: admitted immediately, NOT queued.
        adm2 = AdmissionController(_StubSched(unit_engine, 0),
                                   max_inflight=8, max_queue_depth=16)
        d2 = adm2.decide(rows=1, inflight=1)
        assert d2.admit and not d2.queued
        adm2.note_admitted(queued=d2.queued)
        assert adm2.queued == 0

    def test_priority_scales_caps(self, gw):
        """Low-priority traffic sheds at half the configured caps;
        high priority bypasses the soft p95 signal."""
        adm = AdmissionController(gw.sched, max_inflight=4,
                                  p95_slo_s=0.001)
        # low: cap halves to 2 → inflight 2 sheds.
        d = adm.decide(rows=1, inflight=2, priority="low")
        assert not d.admit and d.reason == "inflight_cap"
        assert adm.decide(rows=1, inflight=2,
                          priority="normal").admit
        # soft p95 over SLO sheds normal but not high priority.
        for _ in range(16):
            adm.note_ttft(1.0)
        d = adm.decide(rows=1, inflight=0, priority="normal")
        assert not d.admit and d.reason == "slo_p95" and d.status == 429
        assert adm.decide(rows=1, inflight=0, priority="high").admit


# ---------------------------------------------------------------------
# scheduler-level: deadline fast-fail, pause-reason threading
# ---------------------------------------------------------------------


@pytest.mark.gateway(allow_no_stream=True)
class TestSchedulerSeam:
    def test_spent_budget_fails_fast_no_prefill(self, unit_engine):
        """submit_async with an already-expired Budget raises
        DeadlineExpired (its OWN classified kind) before any prefill
        dispatch — zero segment tokens consumed, nothing queued."""
        sched = SessionScheduler(unit_engine)
        try:
            d0 = sched.describe()
            assert d0["deadline_expired"] == 0
            with pytest.raises(DeadlineExpired) as ei:
                sched.submit_async(
                    "dead", [("lancelot", PROMPT)], max_new_tokens=4,
                    budget=deadlines.Budget.root(0.0, rung="turn"))
            assert classify_error(ei.value) == "deadline_expired"
            d = sched.describe()
            assert d["deadline_expired"] == 1
            assert d["segment_prefill_tokens"] == \
                d0["segment_prefill_tokens"], "prefill was consumed"
            assert d["admission"]["queued"] == 0
            assert d["active_rows"] == 0
            assert telemetry.REGISTRY.counter_total(
                "roundtable_sched_deadline_expired_total") >= 1
        finally:
            sched.close()

    def test_pause_reasons_thread_into_refusal(self, unit_engine):
        """Every pause reason rides verbatim on SchedulerRefused.reason
        for shed-style submitters and shows in describe()["admission"]:
        drain, quiesce, and a caller-defined gate."""
        sched = SessionScheduler(unit_engine)
        try:
            for reason in ("fleet.drain", "quiesce", "gateway.shed"):
                sched.pause_admission(reason)
                adm = sched.describe()["admission"]
                assert adm["paused"] == reason and not adm["open"]
                with pytest.raises(SchedulerRefused) as ei:
                    sched.submit_async("pz", [("k", "hi")],
                                       max_new_tokens=2,
                                       queue_when_paused=False)
                assert ei.value.reason == reason
                sched.reopen_admission()
                assert sched.describe()["admission"]["open"]
            # bare refusals still carry no reason tag.
            assert SchedulerRefused("plain").reason is None
        finally:
            sched.close()


# ---------------------------------------------------------------------
# resume seam + surface bindings + status view
# ---------------------------------------------------------------------


@pytest.mark.gateway(allow_no_stream=True)
class TestSeams:
    def test_resume_library_seam_identity(self):
        """The CLI path re-exports the library function — one resume
        implementation, byte-identical behavior (the supervision suite
        regression-tests it through the commands.serve import)."""
        from theroundtaible_tpu.commands.serve import \
            resume_from_journal as cli_fn
        from theroundtaible_tpu.engine.recovery import \
            resume_from_journal as lib_fn
        assert cli_fn is lib_fn

    def test_replay_through_library_seam(self, unit_engine, tmp_path):
        """A journaled round replays through engine.recovery directly
        onto a fresh scheduler (the gateway's boot path)."""
        from theroundtaible_tpu.engine.recovery import resume_from_journal

        j = SessionJournal(tmp_path)
        sched = SessionScheduler(unit_engine, journal=j)
        try:
            sched.submit("lib-replay", [("lancelot", PROMPT)],
                         max_new_tokens=4, timeout_s=120)
        finally:
            sched.close()
        sched2 = SessionScheduler(unit_engine)
        try:
            report = resume_from_journal(str(tmp_path),
                                         scheduler=sched2)
            assert report["sessions"] == 1
            assert report["turns"] == 1
            assert report["scheduler"] is sched2
            assert sched2.journal is not None
        finally:
            sched2.close()

    def test_describe_keys_bound_to_surface(self, gw):
        from theroundtaible_tpu.utils.telemetry import SURFACE_BINDINGS
        assert set(gw.describe()) <= set(SURFACE_BINDINGS["gateway"])

    def test_status_gateway_renders(self, gw, capsys):
        from theroundtaible_tpu.commands.status import status_command
        # Seed one series so the render has a reason table even when
        # this test runs alone (counters are global and additive).
        telemetry.inc("roundtable_gateway_admitted_total", reason="ok")
        assert status_command(gateway_view=True) == 0
        out = capsys.readouterr().out
        assert "Serving gateway" in out
        assert "Admitted" in out

    def test_intent_record_roundtrips_adapters_temperature(
            self, tmp_path):
        """The intent record persists the full generation identity —
        adapters + temperature included — so leg-3 resume replays the
        SAME stream, not a base-model/greedy approximation of it."""
        from theroundtaible_tpu.gateway.resume import StreamIntentJournal
        j = StreamIntentJournal(tmp_path)
        rec = j.record("r1", session="s", knights=["k"],
                       prompts=["p"], turn=2, max_new=4,
                       adapters=["persona-a"], temperature=0.5)
        loaded = j.load()["r1"]
        assert loaded == rec
        assert loaded["adapters"] == ["persona-a"]
        assert loaded["temperature"] == 0.5

    def test_intent_journal_compacts(self, unit_engine, tmp_path):
        """A long-lived gateway bounds the intent journal + cache:
        past the cap, records whose turn committed in the session
        journal compact away (newest half of the cap kept for leg-2
        reconnects); uncommitted intents — a crash needs them for
        leg-3 regeneration — always survive."""
        j = SessionJournal(tmp_path)
        sched = SessionScheduler(unit_engine, journal=j)
        try:
            sched.submit("compact-s", [("lancelot", PROMPT)],
                         max_new_tokens=2, timeout_s=120)
            gwc = Gateway(sched, port=0, intent_dir=str(tmp_path))
            for i in range(12):  # committed (turn 0 is journaled)
                sid = f"done{i:04d}"
                gwc._intent_cache[sid] = gwc.intents.record(
                    sid, session="compact-s", knights=["lancelot"],
                    prompts=[PROMPT], turn=0, max_new=2)
            # uncommitted (turn 9 never ran)
            gwc._intent_cache["live0001"] = gwc.intents.record(
                "live0001", session="compact-s", knights=["lancelot"],
                prompts=[PROMPT], turn=9, max_new=2)
            gwc.intent_cap = 8
            gwc._compact_intents()
            assert "live0001" in gwc._intent_cache
            kept = [s for s in gwc._intent_cache
                    if s.startswith("done")]
            assert kept == [f"done{i:04d}" for i in range(8, 12)]
            # disk and cache agree about who can still reconnect.
            assert set(gwc.intents.load()) == set(gwc._intent_cache)
            # below the cap again: a second pass is a no-op.
            n = len(gwc._intent_cache)
            gwc._compact_intents()
            assert len(gwc._intent_cache) == n
        finally:
            sched.close()

    def test_event_id_roundtrip(self):
        assert parse_event_id(format_event_id(3, [5, 7]), 2) \
            == (3, [5, 7])
        assert parse_event_id("3:5,7", 3) is None   # row mismatch
        assert parse_event_id("junk", 2) is None
        assert parse_event_id("-1:0,0", 2) is None


# ---------------------------------------------------------------------
# THE chaos acceptance: kill -9 under concurrent streams
# ---------------------------------------------------------------------


def _spawn_gateway(jdir, resume=None):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cmd = [sys.executable,
           os.path.join(repo, "tests", "_gateway_main.py"),
           "--journal", str(jdir)]
    if resume:
        cmd += ["--resume", str(resume)]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               ROUNDTABLE_RECOMPILE_STRICT="1")
    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    port = None
    deadline = time.monotonic() + 240
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            break
        if line.startswith("PORT="):
            port = int(line.strip().split("=", 1)[1])
            break
    assert port is not None, "gateway child never started listening"

    def _drain(stream):  # keep the child's pipe from filling up
        for _line in stream:
            pass

    import threading
    threading.Thread(target=_drain, args=(proc.stdout,),
                     daemon=True).start()
    return proc, port


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.gateway(allow_no_stream=True)  # the CHILD streams the
# tokens over its socket; this process only reads them.
def test_kill9_streams_resume_with_token_parity(tmp_path):
    """THE crash acceptance: kill -9 the gateway mid-stream under 3
    concurrent sessions, restart it with --resume, and reconnect every
    client via Last-Event-ID — zero lost, zero duplicated tokens, and
    greedy parity with an uninterrupted reference run."""
    jdir = tmp_path / "journal"
    sessions = [("c0", PROMPT), ("c1", PROMPT2),
                ("c2", PROMPT + " Galahad raises the matter of the "
                                "moat.")]
    # Two 64-token decode segments: the first commit streams 64 tokens,
    # then the SIGKILL lands while the turn is still UNCOMMITTED — the
    # resume must regenerate (leg 3), not just replay a journaled turn.
    max_new = 96

    proc, port = _spawn_gateway(jdir)
    conns, metas, seen = [], [], []
    try:
        # Reference run FIRST (same child process = same weights):
        # uninterrupted streams on shadow sessions with the same
        # prompts — greedy, so the crashed sessions must match.
        refs = []
        for name, prompt in sessions:
            _m, toks, term = read_stream(
                port, "/v1/discussions",
                {"session": f"ref-{name}", "max_new_tokens": max_new,
                 "turns": [{"knight": "lancelot", "prompt": prompt}]})
            assert term["type"] == "retired"
            refs.append(row_tokens(toks, 1)[0])
            assert refs[-1], "reference stream produced nothing"

        # Open 3 live streams and read only PART of each (the crash
        # happens mid-stream from the clients' point of view).
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
            assert meta["type"] == "stream"
            metas.append(meta)
            got, last_id = [], None
            for eid, data in it:
                ev = json.loads(data)
                if ev["type"] in ("tokens", "summary"):
                    got.extend(row_tokens([(eid, ev)], 1)[0])
                    last_id = eid
                if len(got) >= 2:
                    break
            assert last_id is not None, "no tokens before the crash"
            seen.append((got, last_id))
    finally:
        proc.kill()  # SIGKILL — no atexit, no flush, no goodbye
        proc.wait(30)
        for c in conns:
            c.close()

    # Restart with --resume: committed turns replay into KV, the
    # intent journal restores the crashed streams (leg 3: greedy
    # re-generation), and every client resumes at its watermark.
    proc2, port2 = _spawn_gateway(jdir, resume=jdir)
    try:
        for (name, _p), meta, (got, last_id), ref in zip(
                sessions, metas, seen, refs):
            _m2, toks2, term2 = read_stream(
                port2, f"/v1/streams/{meta['stream']}", method="GET",
                headers={"Last-Event-ID": last_id})
            assert term2 is not None and term2["type"] == "retired", \
                f"{name}: resumed stream did not retire cleanly"
            resumed = row_tokens(toks2, 1)[0]
            assert got + resumed == ref, (
                f"{name}: prefix({len(got)}) + resumed({len(resumed)}) "
                f"!= uninterrupted reference ({len(ref)}) — tokens "
                "lost or duplicated across the crash")
    finally:
        proc2.kill()
        proc2.wait(30)
