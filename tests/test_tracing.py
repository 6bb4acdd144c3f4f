"""End-to-end request-tracing suite (ISSUE 20).

Covers the tentpole + satellites on the CPU backend:
- trace-context units: traceparent parse/format round-trip, malformed
  and all-zero rejection, full-width external ids keeping low bytes;
- the RequestTrace critical-path clock: stage marks telescoping to the
  leg wall (stage_gap ~ 0 by construction), carve() re-attribution
  with clamping, ttft() as the stage sum through first_flush, finish()
  idempotence;
- tail-based retention: ordinary traces head-sample deterministically
  on the trace id at ROUNDTABLE_TRACE_SAMPLE; flagged (shed/failed/
  hung/replica_crossed/slo_violation) traces are ALWAYS retained;
  ROUNDTABLE_TRACE_KEEP prunes the retained dir;
- stitch()/load_traces(): legs aggregate across simulated process
  generations, torn tails (a leg mid-write at kill -9) are skipped;
- SloBurnMonitor: unarmed idles, MIN_SAMPLES floor, multiwindow fire
  (breach counter + slo_burn flight dump + burn gauges), one dump per
  fast window, sheds burn budget;
- propagation end to end: a client traceparent joins at the gateway
  and is echoed on the response header, the metadata event, every
  token payload, and the terminal event; live reconnect and
  post-restart restore legs rejoin the SAME trace id and stitch on
  disk; shed errors carry the trace; cross-replica failover keeps one
  trace id across the replica crossing and flags the leg;
- TTFT histogram exemplars link a bucket to a concrete trace id.
"""

import json
import os
import threading
import time

import pytest

jax = pytest.importorskip("jax")

from theroundtaible_tpu.engine import deadlines, faults
from theroundtaible_tpu.engine.scheduler import SessionScheduler
from theroundtaible_tpu.engine.session_journal import SessionJournal
from theroundtaible_tpu.engine.supervisor import (EngineSupervisor,
                                                  set_supervisor)
from theroundtaible_tpu.gateway import Gateway
from theroundtaible_tpu.utils import telemetry, tracing

from test_gateway import (Conn, make_engine, read_stream,  # noqa: E402
                          row_tokens)

PROMPT = ("The round table met at dawn to discuss the castle walls "
          "and the eastern gate.")


@pytest.fixture(autouse=True)
def trace_env(tmp_path, monkeypatch):
    """Every test gets its own retained-trace dir and flight-dump dir
    plus a clean in-process ring, so retention assertions are exact."""
    tdir = tmp_path / "traces"
    monkeypatch.setenv("ROUNDTABLE_TRACE_DIR", str(tdir))
    monkeypatch.setenv("ROUNDTABLE_TELEMETRY_DIR",
                       str(tmp_path / "dumps"))
    tracing.store().reset()
    yield tdir
    tracing.store().reset()


def _wait_record(trace_id, timeout=10.0):
    """The gateway finishes a leg from its pump thread; poll the ring
    briefly instead of racing it."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        for rec in tracing.store().recent():
            if rec.get("trace_id") == trace_id:
                return rec
        time.sleep(0.05)
    raise AssertionError(f"no finished leg for trace {trace_id}")


def _wait_legs(trace_id, n, timeout=10.0):
    deadline = time.monotonic() + timeout
    legs = []
    while time.monotonic() < deadline:
        legs = tracing.load_traces().get(trace_id, [])
        if len(legs) >= n:
            return legs
        time.sleep(0.05)
    raise AssertionError(
        f"trace {trace_id}: wanted {n} retained legs, got {len(legs)}")


# ---------------------------------------------------------------------
# trace context (the W3C-style header)
# ---------------------------------------------------------------------


@pytest.mark.tracing(allow_local=True)
class TestTraceContext:
    def test_round_trip(self):
        tid = tracing.mint_trace_id()
        hdr = tracing.format_traceparent(tid, "1234567890ab")
        assert tracing.parse_traceparent(hdr) == (tid, "1234567890ab")

    def test_full_width_external_id_keeps_low_bytes(self):
        ext = "a1b2c3d4e5f60718" * 2          # full 32-hex external id
        hdr = f"00-{ext}-00f067aa0ba902b7-01"
        parsed = tracing.parse_traceparent(hdr)
        assert parsed == (ext[-16:], "67aa0ba902b7")

    def test_rejections(self):
        good_tail = "4bf92f3577b34da6a3ce929d0e0e4736-00f067aa0ba902b7"
        assert tracing.parse_traceparent(None) is None
        assert tracing.parse_traceparent("") is None
        assert tracing.parse_traceparent("not-a-header") is None
        assert tracing.parse_traceparent(f"ff-{good_tail}-01") is None
        assert tracing.parse_traceparent(
            f"00-{'0' * 32}-00f067aa0ba902b7-01") is None
        assert tracing.parse_traceparent(
            f"00-4bf92f3577b34da6a3ce929d0e0e4736-{'0' * 16}-01") \
            is None
        # case-insensitive + surrounding whitespace tolerated
        assert tracing.parse_traceparent(
            f"  00-{good_tail.upper()}-01  ") is not None

    def test_format_pads_to_w3c_widths(self):
        hdr = tracing.format_traceparent("abc", "d")
        ver, trace, span, flags = hdr.split("-")
        assert (ver, flags) == ("00", "01")
        assert len(trace) == 32 and trace.endswith("abc")
        assert len(span) == 16 and span.endswith("d")


# ---------------------------------------------------------------------
# the critical-path clock
# ---------------------------------------------------------------------


@pytest.mark.tracing(allow_local=True)
class TestRequestTraceClock:
    def _backdate(self, tr, seconds):
        # Attribute a known duration to the NEXT stage mark without
        # sleeping: stage() measures now - _last, finish() measures
        # now - t0, so shift both clocks to keep wall == stage sum.
        tr._last -= seconds
        tr.t0 -= seconds

    def test_stage_sum_telescopes_to_wall(self):
        tr = tracing.RequestTrace(kind="request", session="u-wall")
        for name, secs in (("admission", 0.02), ("placement", 0.01),
                           ("prefill", 0.05), ("first_flush", 0.005)):
            self._backdate(tr, secs)
            tr.stage(name)
        rec = tr.finish("ok")
        assert rec["stage_sum_s"] == pytest.approx(rec["wall_s"],
                                                   abs=1e-4)
        assert abs(rec["stage_gap_s"]) < 1e-4
        assert set(rec["stages"]) <= set(tracing.STAGES)

    def test_carve_reattributes_and_clamps(self):
        tr = tracing.RequestTrace(kind="request", session="u-carve")
        self._backdate(tr, 0.2)
        tr.stage("prefill")
        before = sum(tr.stages.values())
        tr.carve("prefill", "queue_wait", 0.08)
        assert tr.stages["queue_wait"] == pytest.approx(0.08)
        assert tr.stages["prefill"] == pytest.approx(before - 0.08,
                                                     abs=1e-3)
        assert sum(tr.stages.values()) == pytest.approx(before)
        # clamped: a split can never create time the lump didn't hold
        tr.carve("prefill", "queue_wait", 999.0)
        assert tr.stages["prefill"] == 0.0
        assert sum(tr.stages.values()) == pytest.approx(before)
        # no-ops
        tr.carve("prefill", "queue_wait", None)
        tr.carve("prefill", "queue_wait", -1.0)
        assert sum(tr.stages.values()) == pytest.approx(before)
        tr.finish("ok")

    def test_ttft_is_stage_sum_through_first_flush(self):
        tr = tracing.RequestTrace(kind="request", session="u-ttft")
        for name, secs in (("admission", 0.02), ("placement", 0.01),
                           ("prefill", 0.1), ("first_flush", 0.005)):
            self._backdate(tr, secs)
            tr.stage(name)
        tr.carve("prefill", "queue_wait", 0.04)
        want = 0.02 + 0.01 + 0.1 + 0.005       # carve moves, not adds
        assert tr.ttft() == pytest.approx(want, abs=5e-3)
        # decode_stream never counts toward TTFT
        self._backdate(tr, 1.0)
        rec = tr.finish("ok")
        assert rec["ttft_s"] == pytest.approx(want, abs=5e-3)
        assert rec["stages"]["decode_stream"] >= 1.0

    def test_finish_is_idempotent(self):
        tr = tracing.RequestTrace(kind="request", session="u-idem")
        rec = tr.finish("ok")
        again = tr.finish("failed:late")
        assert again is rec or again == rec
        assert again["outcome"] == "ok"
        ring = [r for r in tracing.store().recent()
                if r["trace_id"] == tr.trace_id]
        assert len(ring) == 1

    def test_flags_deduplicate(self):
        tr = tracing.RequestTrace(kind="request", session="u-flag")
        tr.flag("hung")
        tr.flag("hung")
        tr.flag("slo_violation")
        assert tr.finish("hung")["flags"] == ["hung", "slo_violation"]


# ---------------------------------------------------------------------
# tail-based retention
# ---------------------------------------------------------------------


@pytest.mark.tracing(allow_local=True)
class TestRetention:
    def test_head_sampling_is_deterministic(self, monkeypatch):
        tid = tracing.mint_trace_id()
        monkeypatch.setenv("ROUNDTABLE_TRACE_SAMPLE", "1")
        assert tracing.head_sampled(tid)
        monkeypatch.setenv("ROUNDTABLE_TRACE_SAMPLE", "0")
        assert not tracing.head_sampled(tid)
        monkeypatch.setenv("ROUNDTABLE_TRACE_SAMPLE", "0.5")
        # every leg of one trace (any process) decides identically
        assert tracing.head_sampled(tid) == tracing.head_sampled(tid)

    def test_sample_zero_drops_ok_keeps_flagged(self, trace_env,
                                                monkeypatch):
        monkeypatch.setenv("ROUNDTABLE_TRACE_SAMPLE", "0")
        ok = tracing.RequestTrace(kind="request", session="r-ok")
        ok.finish("ok")
        flagged = tracing.RequestTrace(kind="request", session="r-bad")
        flagged.flag("hung")
        flagged.finish("hung")
        retained = tracing.load_traces(str(trace_env))
        assert ok.trace_id not in retained
        assert flagged.trace_id in retained
        assert retained[flagged.trace_id][0]["flags"] == ["hung"]

    def test_sample_one_retains_ok(self, trace_env, monkeypatch):
        monkeypatch.setenv("ROUNDTABLE_TRACE_SAMPLE", "1")
        before = telemetry.REGISTRY.counter_total(
            "roundtable_traces_retained_total", outcome="ok")
        tr = tracing.RequestTrace(kind="request", session="r-keep")
        tr.finish("ok")
        assert tr.trace_id in tracing.load_traces(str(trace_env))
        assert telemetry.REGISTRY.counter_total(
            "roundtable_traces_retained_total",
            outcome="ok") == before + 1

    def test_keep_prunes_oldest(self, trace_env, monkeypatch):
        monkeypatch.setenv("ROUNDTABLE_TRACE_KEEP", "8")
        for i in range(12):
            tr = tracing.RequestTrace(kind="request", session=f"p{i}")
            tr.flag("hung")
            tr.finish("hung")
        files = [p for p in os.listdir(trace_env)
                 if p.startswith("trace-")]
        assert len(files) == 8


# ---------------------------------------------------------------------
# stitch / load across process generations
# ---------------------------------------------------------------------


@pytest.mark.tracing(allow_local=True)
class TestStitch:
    def _leg(self, tid, *, pid, start, outcome, stages, flags=(),
             ttft=None):
        rec = {"trace_id": tid, "kind": "resume" if start else
               "request", "session": "s", "outcome": outcome,
               "start": 1000.0 + start, "pid": pid,
               "wall_s": round(sum(stages.values()), 6),
               "stage_sum_s": round(sum(stages.values()), 6),
               "stage_gap_s": 0.0, "stages": stages,
               "flags": list(flags), "reconnects": 0}
        if ttft is not None:
            rec["ttft_s"] = ttft
        return rec

    def test_stitch_aggregates_legs(self):
        tid = tracing.mint_trace_id()
        legs = [
            self._leg(tid, pid=100, start=0.0, outcome="interrupted",
                      stages={"admission": 0.01, "prefill": 0.2,
                              "decode_stream": 0.5},
                      flags=["interrupted"], ttft=0.21),
            self._leg(tid, pid=200, start=5.0, outcome="ok",
                      stages={"resume_replay": 0.1,
                              "decode_stream": 0.3},
                      flags=["replica_crossed"]),
        ]
        s = tracing.stitch(legs)
        assert s["trace_id"] == tid and s["legs"] == 2
        assert s["pids"] == [100, 200]
        assert s["outcome"] == "ok"            # the LAST leg's outcome
        assert s["ttft_s"] == 0.21             # the FIRST leg's TTFT
        assert s["flags"] == ["interrupted", "replica_crossed"]
        assert s["stages"]["decode_stream"] == pytest.approx(0.8)
        assert s["wall_s"] == pytest.approx(s["stage_sum_s"])

    def test_load_traces_skips_torn_tail(self, tmp_path):
        d = tmp_path / "torn"
        d.mkdir()
        tid = tracing.mint_trace_id()
        good = self._leg(tid, pid=1, start=0.0, outcome="ok",
                         stages={"decode_stream": 0.1})
        with open(d / f"trace-{tid}.jsonl", "w") as f:
            f.write(json.dumps(good) + "\n")
            f.write('{"trace_id": "' + tid + '", "truncat')  # kill -9
        loaded = tracing.load_traces(str(d))
        assert [leg["outcome"] for leg in loaded[tid]] == ["ok"]

    def test_load_traces_missing_dir(self, tmp_path):
        assert tracing.load_traces(str(tmp_path / "nope")) == {}

    def test_cross_layer_count(self):
        a, b = tracing.mint_trace_id(), tracing.mint_trace_id()
        spans = [
            {"rung": "request", "trace_id": a},
            {"rung": "turn", "trace_id": a},      # a crosses the seam
            {"rung": "resume", "trace_id": b},    # b serving-only
            {"rung": "dispatch", "trace_id": tracing.mint_trace_id()},
        ]
        assert tracing.cross_layer_count(spans) == 1


# ---------------------------------------------------------------------
# SLO burn-rate monitor
# ---------------------------------------------------------------------


@pytest.mark.tracing(allow_local=True)
class TestBurnMonitor:
    def test_unarmed_monitor_idles(self):
        mon = tracing.SloBurnMonitor(0.0)
        assert not mon.armed
        for _ in range(20):
            mon.note_ttft(99.0)
        assert mon.breaches == 0 and mon.last_dump_path == ""

    def test_quiet_baseline_under_slo(self):
        mon = tracing.SloBurnMonitor(0.5, error_budget=0.05,
                                     fast_window_s=60,
                                     slow_window_s=600)
        for _ in range(20):
            mon.note_ttft(0.01)
        rates = mon.burn_rates()
        assert rates["fast"] == 0.0 and rates["slow"] == 0.0
        assert mon.breaches == 0

    def test_breach_fires_once_per_fast_window(self):
        b0 = telemetry.REGISTRY.counter_total(
            "roundtable_slo_breaches_total")
        mon = tracing.SloBurnMonitor(0.01, error_budget=0.5,
                                     fast_window_s=60,
                                     slow_window_s=600)
        # MIN_SAMPLES floor: 7 hot events in the fast window stay quiet
        for _ in range(mon.MIN_SAMPLES - 1):
            mon.note_ttft(1.0, trace_id="exemplar-tid")
        assert mon.breaches == 0
        mon.note_ttft(1.0, trace_id="exemplar-tid")
        assert mon.breaches == 1
        assert mon.last_dump_path and os.path.exists(mon.last_dump_path)
        with open(mon.last_dump_path) as f:
            dump = json.load(f)
        assert dump["trigger"] == "slo_burn"
        assert dump["extra"]["exemplar_trace_id"] == "exemplar-tid"
        assert dump["extra"]["burn_fast"] > mon.threshold
        # sustained breach: cooldown holds it to one dump per window
        for _ in range(10):
            mon.note_ttft(1.0)
        assert mon.breaches == 1
        assert telemetry.REGISTRY.counter_total(
            "roundtable_slo_breaches_total") == b0 + 1
        assert telemetry.REGISTRY.gauge_value(
            "roundtable_slo_burn_rate", window="fast") > mon.threshold

    def test_sheds_burn_budget(self):
        mon = tracing.SloBurnMonitor(10.0, error_budget=0.5,
                                     fast_window_s=60,
                                     slow_window_s=600)
        for _ in range(mon.MIN_SAMPLES):
            mon.note_shed()                    # bad without any TTFT
        assert mon.breaches == 1

    def test_describe_surface(self):
        mon = tracing.SloBurnMonitor(0.25, source="capacity_record")
        mon.note_ttft(0.1)
        d = mon.describe()
        assert d["armed"] is True
        assert d["p95_slo_s"] == 0.25
        assert d["source"] == "capacity_record"
        for key in ("error_budget", "threshold", "fast_window_s",
                    "slow_window_s", "burn_fast", "burn_slow",
                    "samples_fast", "samples_slow", "breaches",
                    "last_dump"):
            assert key in d, key

    def test_exemplar_links_bucket_to_trace(self):
        telemetry.observe("roundtable_test_ttft_seconds", 0.25,
                          exemplar="tid-hot")
        ex = telemetry.REGISTRY.exemplars("roundtable_test_ttft_seconds")
        assert any(v["trace_id"] == "tid-hot" for v in ex.values())


# ---------------------------------------------------------------------
# end-to-end propagation over a live gateway
# ---------------------------------------------------------------------


@pytest.fixture(scope="module")
def gw(tmp_path_factory):
    jdir = tmp_path_factory.mktemp("tr-journal")
    engine = make_engine()
    sched = SessionScheduler(engine, journal=SessionJournal(jdir))
    g = Gateway(sched, port=0, intent_dir=str(jdir))
    g.start_in_thread()
    yield g
    g.stop()
    sched.close()


@pytest.mark.tracing
@pytest.mark.gateway
class TestPropagation:
    def test_client_traceparent_joins_and_echoes(self, gw):
        """One trace id from the client's header through the metadata
        event, every token payload, the terminal event, the echoed
        Traceparent response header, the retained record, and the TTFT
        histogram exemplar."""
        tid = "feedc0dedeadbee1"
        hdr = tracing.format_traceparent(tid, "1234567890ab")
        c = Conn(gw.port, "POST", "/v1/discussions",
                 body={"session": "tr-echo", "max_new_tokens": 6,
                       "turns": [{"knight": "lancelot",
                                  "prompt": PROMPT}]},
                 headers={"Traceparent": hdr})
        assert c.status == 200
        assert tid in c.headers["traceparent"]
        meta, terminal, payload_tids = None, None, set()
        for _eid, data in c.events():
            ev = json.loads(data)
            if ev["type"] == "stream":
                meta = ev
            elif ev["type"] in ("tokens", "summary"):
                payload_tids.add(ev.get("trace"))
            else:
                terminal = ev
                break
        c.close()
        assert meta["trace"] == tid
        assert payload_tids == {tid}
        assert terminal["type"] == "retired" and terminal["trace"] == tid

        rec = _wait_record(tid)
        assert rec["outcome"] == "ok" and rec["kind"] == "request"
        assert set(rec["stages"]) <= set(tracing.STAGES)
        assert rec["ttft_s"] > 0.0
        # the acceptance invariant: stage sum within 5% of leg wall
        assert abs(rec["stage_gap_s"]) <= max(
            0.05 * rec["wall_s"], 0.01)
        legs = _wait_legs(tid, 1)
        assert legs[0]["trace_id"] == tid
        ex = telemetry.REGISTRY.exemplars(
            "roundtable_gateway_ttft_seconds")
        assert any(v["trace_id"] == tid for v in ex.values())

    def test_the_admit_span_joins_the_requests_trace(self, gw):
        """ISSUE 25: the scheduler's `admit` span — admission's host
        work, on the loop thread — is caused by the gateway's `request`
        span and shares the client's trace id; the request's held spans
        keep their records and ids."""
        tid = "feedc0dedeadbee2"
        t_a = time.monotonic()
        meta, _toks, terminal = read_stream(
            gw.port, "/v1/discussions",
            {"session": "tr-admit", "max_new_tokens": 6,
             "turns": [{"knight": "lancelot", "prompt": PROMPT}]},
            headers={"Traceparent": tracing.format_traceparent(
                tid, "1234567890ab")})
        assert terminal["type"] == "retired" and meta["trace"] == tid
        _wait_record(tid)
        deadline = time.monotonic() + 5.0
        while True:
            mine = [r for r in telemetry.spans_between(
                t_a, time.monotonic()) if r["trace_id"] == tid]
            rungs = {r["rung"] for r in mine}
            if {"request", "turn", "admit"} <= rungs \
                    or time.monotonic() > deadline:
                break
            time.sleep(0.05)
        assert {"request", "turn", "admit", "dispatch"} <= rungs
        by_rung = {r["rung"]: r for r in mine}
        assert by_rung["admit"]["parent_id"] == \
            by_rung["request"]["span_id"]
        assert by_rung["admit"]["attrs"]["session"] == "tr-admit"
        assert by_rung["turn"]["parent_id"] == \
            by_rung["request"]["span_id"]

    def test_minted_root_when_no_header(self, gw):
        meta, toks, terminal = read_stream(
            gw.port, "/v1/discussions",
            {"session": "tr-mint", "max_new_tokens": 4,
             "turns": [{"knight": "lancelot", "prompt": PROMPT}]})
        assert terminal["type"] == "retired"
        tid = meta["trace"]
        assert tid and tracing.parse_traceparent(
            tracing.format_traceparent(tid, "1" * 12)) is not None

    def test_reconnect_rejoins_same_trace(self, gw):
        body = {"session": "tr-rc", "max_new_tokens": 6,
                "turns": [{"knight": "lancelot", "prompt": PROMPT}]}
        meta, toks, terminal = read_stream(gw.port, "/v1/discussions",
                                           body)
        assert terminal["type"] == "retired" and toks
        mid_id = toks[0][0]
        meta2, _toks2, terminal2 = read_stream(
            gw.port, f"/v1/streams/{meta['stream']}", method="GET",
            headers={"Last-Event-ID": mid_id})
        assert terminal2["type"] == "retired"
        assert meta2["trace"] == meta["trace"]

    def test_restart_restore_rejoins_and_stitches(self, gw):
        """Reconnect ladder leg 2: a FRESH Gateway (post-restart state,
        same intent journal) serves the stream under the ORIGINAL
        trace id, and the resume leg appends to the same on-disk trace
        file so the legs stitch."""
        body = {"session": "tr-restart", "max_new_tokens": 6,
                "turns": [{"knight": "lancelot", "prompt": PROMPT}]}
        meta, toks, terminal = read_stream(gw.port, "/v1/discussions",
                                           body)
        assert terminal["type"] == "retired"
        tid = meta["trace"]
        _wait_record(tid)

        gw2 = Gateway(gw.sched, port=0,
                      intent_dir=str(gw.intents.root))
        gw2.start_in_thread()
        try:
            c = Conn(gw2.port, "GET", f"/v1/streams/{meta['stream']}")
            assert c.status == 200
            assert tid in c.headers["traceparent"]
            meta2 = json.loads(next(c.events())[1])
            c.close()
            assert meta2["trace"] == tid
        finally:
            gw2.stop()

        legs = _wait_legs(tid, 2)
        assert [leg["kind"] for leg in legs] == ["request", "resume"]
        assert legs[1]["stages"].get("resume_replay", 0.0) > 0.0
        stitched = tracing.stitch(legs)
        assert stitched["legs"] == 2 and stitched["trace_id"] == tid
        assert abs(stitched["wall_s"] - stitched["stage_sum_s"]) \
            <= max(0.05 * stitched["wall_s"], 0.02)

    @pytest.mark.tracing(allow_local=True)
    @pytest.mark.gateway(allow_no_stream=True)
    def test_shed_carries_trace_and_is_retained(self, gw, trace_env,
                                                monkeypatch):
        """A shed response names its trace (body + Traceparent header)
        and the trace is tail-retained even at sample rate 0."""
        monkeypatch.setenv("ROUNDTABLE_TRACE_SAMPLE", "0")
        gw.sched.pause_admission("maintenance")
        try:
            c = Conn(gw.port, "POST", "/v1/discussions",
                     body={"turns": [{"knight": "k", "prompt": "x"}]})
            assert c.status == 503
            payload = c.body_json()
            c.close()
            tid = payload["trace"]
            assert tid and tid in c.headers["traceparent"]
        finally:
            gw.sched.reopen_admission()
        legs = _wait_legs(tid, 1)
        assert "shed" in legs[0]["flags"]
        assert legs[0]["outcome"].startswith("shed:")


# ---------------------------------------------------------------------
# cross-replica failover: one trace across the crossing
# ---------------------------------------------------------------------


@pytest.mark.tracing
@pytest.mark.router
@pytest.mark.chaos
def test_failover_keeps_one_trace_and_flags_crossing(tmp_path):
    """device_lost kills the serving replica mid-stream; the client
    reconnects and is restored on the survivor — the resume leg joins
    the ORIGINAL trace id, is flagged replica_crossed, and the legs
    stitch on disk across the failure."""
    from test_router import close_fleet, make_fleet

    router = make_fleet(tmp_path / "j-trace-chaos")
    gw = Gateway(router.replicas[0].scheduler, port=0,
                 intent_dir=str(tmp_path / "j-trace-chaos"),
                 router=router)
    gw.start_in_thread()
    try:
        set_supervisor(EngineSupervisor(max_restarts=0))
        faults.arm("device_lost", count=1)
        body = {"session": "tr-chaos", "max_new_tokens": 8,
                "turns": [{"knight": "lancelot", "prompt": PROMPT}]}
        meta, toks, terminal = read_stream(gw.port, "/v1/discussions",
                                           body)
        tid = meta["trace"]
        last_id = toks[-1][0] if toks else None
        attempts = 0
        while terminal is None or terminal["type"] == "failed":
            attempts += 1
            assert attempts <= 8, f"stream never recovered: {terminal}"
            time.sleep(0.5)
            headers = ({"Last-Event-ID": last_id} if last_id
                       else None)
            try:
                meta2, toks, terminal = read_stream(
                    gw.port, f"/v1/streams/{meta['stream']}",
                    method="GET", headers=headers)
            except AssertionError:
                terminal = {"type": "failed"}   # failover settling
                continue
            assert meta2["trace"] == tid, \
                "failover leg minted a NEW trace id"
            if toks:
                last_id = toks[-1][0]
        assert terminal["type"] == "retired" and terminal["trace"] == tid
        assert router.failovers >= 1

        legs = _wait_legs(tid, 2)
        flags = set()
        for leg in legs:
            flags.update(leg["flags"])
        assert "replica_crossed" in flags
        stitched = tracing.stitch(legs)
        assert stitched["legs"] >= 2
        assert len(stitched["pids"]) >= 1
        assert stitched["outcome"] == "ok"
    finally:
        gw.stop()
        close_fleet(router)
        faults.disarm()
        deadlines.end_drain()
        set_supervisor(None)
