"""Unified telemetry suite (ISSUE 5): metrics-registry / flight-recorder
/ span-tracer units, the watchdog/breaker auto-dump seams, the
observability-surface drift lint (describe()/fleet_health keys must map
onto registry series), and the end-to-end acceptance test — a 2-knight
run_discussion under an injected `hang` fault emits a per-session spans
JSONL whose nesting matches the Budget tree and ships a flight-recorder
dump.
"""

import json
import threading
import time
from pathlib import Path

import pytest

jax = pytest.importorskip("jax")

from theroundtaible_tpu.adapters.base import KnightTurn
from theroundtaible_tpu.adapters.tpu_llm import TpuLlmAdapter
from theroundtaible_tpu.core.orchestrator import run_discussion
from theroundtaible_tpu.core.types import (
    KnightConfig,
    RoundtableConfig,
    RulesConfig,
)
from theroundtaible_tpu.engine import deadlines, faults, get_engine, \
    reset_engines
from theroundtaible_tpu.engine.faults import CircuitBreaker
from theroundtaible_tpu.utils import telemetry


@pytest.fixture(autouse=True)
def clean_telemetry(tmp_path, monkeypatch):
    """Each test gets a pristine registry, ring and dump dir, and the
    fault/watchdog machinery reset (several tests drive them)."""
    monkeypatch.setenv("ROUNDTABLE_TELEMETRY_DIR",
                       str(tmp_path / "dumps"))
    telemetry.REGISTRY.reset()
    telemetry.recorder().clear()
    telemetry.reset_spans_emitted()
    faults.disarm()
    deadlines.reset_rungs()
    deadlines.disarm_watchdog()
    deadlines.clear_hang_log()
    yield
    telemetry.REGISTRY.reset()
    telemetry.recorder().clear()
    faults.disarm()
    deadlines.reset_rungs()
    deadlines.disarm_watchdog()
    deadlines.clear_hang_log()


@pytest.fixture(autouse=True, scope="module")
def clean_engines():
    reset_engines()
    yield
    reset_engines()


def _tpu_cfg(seed, **extra):
    cfg = {
        "model": "tiny-gemma", "max_seq_len": 512, "num_slots": 4,
        "seed": seed,
        "sampling": {"temperature": 0.0, "max_new_tokens": 8},
    }
    cfg.update(extra)
    return cfg


def _discussion_config(tpu_cfg):
    return RoundtableConfig(
        version="1.0", project="t", language="en",
        knights=[KnightConfig(name="Sage", adapter="tpu-llm", priority=1),
                 KnightConfig(name="Oracle", adapter="tpu-llm",
                              priority=2)],
        rules=RulesConfig(max_rounds=1, timeout_per_turn_seconds=600,
                          parallel_rounds=True),
        chronicle="chronicle.md",
        adapter_config={"tpu-llm": tpu_cfg})


# --- metrics registry units ---


@pytest.mark.telemetry(allow_no_spans=True)
class TestRegistry:
    def test_counter_labels_and_totals(self):
        telemetry.inc("roundtable_x_total", 2, engine="a")
        telemetry.inc("roundtable_x_total", 3, engine="b")
        assert telemetry.counter_total("roundtable_x_total") == 5
        assert telemetry.counter_total("roundtable_x_total",
                                       engine="a") == 2
        assert telemetry.counter_total("roundtable_missing") == 0

    def test_gauge_set_overwrites(self):
        telemetry.set_gauge("roundtable_g", 4, engine="a")
        telemetry.set_gauge("roundtable_g", 7, engine="a")
        assert telemetry.REGISTRY.gauge_value("roundtable_g",
                                              engine="a") == 7

    def test_histogram_buckets_and_prom_text(self):
        telemetry.observe("roundtable_h_seconds", 0.02)
        telemetry.observe("roundtable_h_seconds", 400.0)  # > last bucket
        text = telemetry.REGISTRY.prometheus_text()
        assert "# TYPE roundtable_h_seconds histogram" in text
        assert 'roundtable_h_seconds_bucket{le="+Inf"} 2' in text
        assert "roundtable_h_seconds_count 2" in text

    def test_snapshot_compact_flattens_counters_and_gauges(self):
        telemetry.inc("roundtable_c_total", engine="e")
        telemetry.set_gauge("roundtable_g2", 1.5)
        snap = telemetry.REGISTRY.snapshot_compact()
        assert snap["roundtable_c_total{engine=e}"] == 1
        assert snap["roundtable_g2"] == 1.5

    def test_thread_safe_counting(self):
        def work():
            for _ in range(200):
                telemetry.inc("roundtable_race_total")
        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert telemetry.counter_total("roundtable_race_total") == 1600

    def test_reset_clears_everything(self):
        telemetry.inc("roundtable_r_total")
        telemetry.REGISTRY.reset()
        assert telemetry.REGISTRY.snapshot_compact() == {}


# --- flight recorder units ---


@pytest.mark.telemetry(allow_no_spans=True)
class TestFlightRecorder:
    def test_ring_is_bounded(self):
        rec = telemetry.FlightRecorder("t", capacity=16)
        for i in range(100):
            rec.record("e", i=i)
        events = rec.events()
        assert len(events) == 16
        assert events[-1]["i"] == 99  # newest kept, oldest dropped

    def test_dump_ships_ring_and_registry(self, tmp_path):
        telemetry.inc("roundtable_d_total", 3)
        telemetry.recorder().record("interesting", detail="x")
        path = telemetry.flight_dump("unit_test", extra={"why": "test"})
        assert path and Path(path).exists()
        payload = json.loads(Path(path).read_text())
        assert payload["trigger"] == "unit_test"
        assert payload["extra"] == {"why": "test"}
        assert any(e["kind"] == "interesting" for e in payload["events"])
        assert payload["metrics"]["counters"]["roundtable_d_total"] == 3
        # dumping is itself counted in the registry
        assert telemetry.counter_total("roundtable_flight_dumps_total",
                                       trigger="unit_test") == 1
        assert telemetry.last_dump_path() == path

    def test_dump_dir_env_override(self, tmp_path, monkeypatch):
        monkeypatch.setenv("ROUNDTABLE_TELEMETRY_DIR",
                           str(tmp_path / "custom"))
        path = telemetry.flight_dump("loc")
        assert path.startswith(str(tmp_path / "custom"))

    def test_default_dump_dir_is_uid_suffixed(self, monkeypatch):
        monkeypatch.delenv("ROUNDTABLE_TELEMETRY_DIR", raising=False)
        import os as _os
        assert telemetry.dump_dir().endswith(
            f"roundtable-telemetry-{_os.getuid()}")

    def test_failed_dump_not_counted(self, monkeypatch):
        """A dump whose write fails returns '' and does NOT bump the
        success counter — fleet_health must never claim postmortems
        that were never written (review finding)."""
        rec = telemetry.recorder()
        before = rec.dumps
        monkeypatch.setenv("ROUNDTABLE_TELEMETRY_DIR",
                           "/proc/definitely/not/writable")
        assert rec.dump("doomed") == ""
        assert rec.dumps == before
        assert telemetry.counter_total("roundtable_flight_dumps_total",
                                       trigger="doomed") == 0

    def test_dump_dir_pruned_to_keep_limit(self, tmp_path, monkeypatch):
        monkeypatch.setenv("ROUNDTABLE_TELEMETRY_DIR", str(tmp_path))
        monkeypatch.setattr(telemetry, "_DUMP_KEEP", 5)
        for _ in range(12):
            telemetry.flight_dump("prune")
        left = list(tmp_path.glob("flight-*.json"))
        assert len(left) == 5


# --- span tracer units ---


@pytest.mark.telemetry
class TestSpans:
    def test_nesting_shares_trace_and_chains_parents(self, tmp_path):
        sink = telemetry.session_sink(tmp_path)
        with telemetry.span("discussion", sink=sink, session="s") as d:
            with telemetry.span("round", round=1) as r:
                with telemetry.span("turn", knight="Sage") as t:
                    assert t.trace_id == d.trace_id
                    assert t.parent_id == r.span_id
                assert r.parent_id == d.span_id
        lines = [json.loads(ln) for ln in
                 (tmp_path / "telemetry" / "spans.jsonl")
                 .read_text().splitlines()]
        # children flush before parents (exit order)
        assert [ln["rung"] for ln in lines] == ["turn", "round",
                                                "discussion"]
        assert len({ln["trace_id"] for ln in lines}) == 1
        by_id = {ln["span_id"]: ln for ln in lines}
        turn = next(ln for ln in lines if ln["rung"] == "turn")
        assert by_id[turn["parent_id"]]["rung"] == "round"

    def test_children_inherit_sink_from_root(self, tmp_path):
        sink = telemetry.session_sink(tmp_path)
        with telemetry.span("discussion", sink=sink):
            with telemetry.span("turn"):
                pass
        text = (tmp_path / "telemetry" / "spans.jsonl").read_text()
        assert '"turn"' in text and '"discussion"' in text

    def test_disarmed_is_noop_singleton(self):
        telemetry.disarm()
        try:
            before = telemetry.spans_emitted()
            s = telemetry.span("turn", knight="x")
            with s:
                s.set_attr("a", 1)
            assert telemetry.spans_emitted() == before
        finally:
            telemetry.arm()  # the guard fixture expects armed
        with telemetry.span("turn"):
            pass  # re-armed: the guard's spans-emitted check passes

    def test_cross_thread_attach_parents_correctly(self, tmp_path):
        sink = telemetry.session_sink(tmp_path)
        seen = {}
        with telemetry.span("round", sink=sink) as r:
            ctx = telemetry.current_context()

            def worker():
                with telemetry.attached(ctx):
                    with telemetry.span("turn") as t:
                        seen["parent"] = t.parent_id
                        seen["trace"] = t.trace_id

            th = threading.Thread(target=worker)
            th.start()
            th.join()
        assert seen["parent"] == r.span_id
        assert seen["trace"] == r.trace_id
        # and the worker's span landed in the session sink it inherited
        text = (tmp_path / "telemetry" / "spans.jsonl").read_text()
        assert '"turn"' in text

    def test_manual_start_end_and_error_status(self):
        s = telemetry.start_span("turn", session="s")
        s.end(status="error:TimeoutError")
        spans = telemetry.recorder().span_events()
        assert spans[-1]["status"] == "error:TimeoutError"

    def test_exception_marks_span_status(self):
        with pytest.raises(ValueError):
            with telemetry.span("turn"):
                raise ValueError("boom")
        spans = telemetry.recorder().span_events()
        assert spans[-1]["status"] == "error:ValueError"

    def test_span_flood_does_not_evict_decision_events(self):
        """Spans ride a separate ring: a long armed decode's hundreds
        of span records must not push the sched/breaker/hang decision
        history out of a later dump (review finding)."""
        telemetry.recorder().record("sched_admit", session="s")
        for _ in range(2000):
            with telemetry.span("dispatch"):
                pass
        kinds = [e["kind"] for e in telemetry.recorder().events()]
        assert "sched_admit" in kinds
        path = telemetry.flight_dump("flood")
        payload = json.loads(Path(path).read_text())
        assert any(e["kind"] == "sched_admit"
                   for e in payload["events"])
        assert payload["spans"]  # spans shipped too, separately


# --- one clock: the armed span buffer (ISSUE 25) ---


@pytest.fixture
def fresh_buffer():
    """A span buffer of this test's own: a disarm/arm cycle opens one."""
    telemetry.disarm()
    telemetry.arm()
    yield
    telemetry.arm()


@pytest.mark.telemetry
class TestSpanBuffer:
    def test_a_finished_span_record_carries_t0_on_the_monotonic_clock(
            self, fresh_buffer):
        before = time.monotonic()
        with telemetry.span("turn"):
            time.sleep(0.01)
        after = time.monotonic()
        (rec,) = telemetry.spans_between(before, after)
        assert before <= rec["t0"] <= rec["t0"] + rec["dur_s"] <= after
        assert rec["dur_s"] >= 0.01
        # the wall-clock start it always had is still there
        assert abs(rec["start"] - time.time()) < 60

    def test_spans_between_returns_exactly_the_spans_started_in_the_stretch(
            self, fresh_buffer):
        with telemetry.span("turn", n=0):
            pass
        t_a = time.monotonic()
        straddler = telemetry.start_span("turn", n=1)
        with telemetry.span("dispatch", n=2):
            pass
        t_b = time.monotonic()
        with telemetry.span("dispatch", n=3):
            pass
        straddler.end()            # started inside, ended after t_b
        still_open = telemetry.start_span("turn", n=4)
        got = telemetry.spans_between(t_a, t_b)
        assert sorted(r["attrs"]["n"] for r in got) == [1, 2]
        assert telemetry.spans_between(t_b, t_b) == []
        assert telemetry.spans_dropped() == 0
        still_open.end()

    def test_the_buffer_is_bounded_and_counts_what_it_drops(
            self, monkeypatch):
        monkeypatch.setattr(telemetry, "SPAN_BUFFER_CAPACITY", 8)
        telemetry.disarm()
        telemetry.arm()            # opens a buffer of 8
        t_a = time.monotonic()
        for i in range(11):
            with telemetry.span("dispatch", i=i):
                pass
        got = telemetry.spans_between(t_a, time.monotonic())
        assert [r["attrs"]["i"] for r in got] == list(range(3, 11))
        assert telemetry.spans_dropped() == 3

    def test_disarm_leaves_the_buffer_readable_until_the_next_arm(
            self, fresh_buffer):
        t_a = time.monotonic()
        with telemetry.span("turn"):
            pass
        telemetry.disarm()
        try:
            assert len(telemetry.spans_between(
                t_a, time.monotonic())) == 1
            with telemetry.span("turn"):    # disarmed: no record
                pass
            assert len(telemetry.spans_between(
                t_a, time.monotonic())) == 1
        finally:
            telemetry.arm()        # a fresh arming: a fresh buffer
        assert telemetry.spans_between(t_a, time.monotonic()) == []
        with telemetry.span("turn"):
            pass                   # the marker guard wants a span

    def test_arming_twice_keeps_what_the_first_arming_gathered(
            self, fresh_buffer):
        t_a = time.monotonic()
        with telemetry.span("turn"):
            pass
        telemetry.arm()
        assert len(telemetry.spans_between(t_a, time.monotonic())) == 1

    def test_emit_span_backdates_its_start(self, fresh_buffer):
        t_a = time.monotonic()
        with telemetry.span("dispatch") as d:
            telemetry.emit_span("compile", 0.25, label="decode[b=4]",
                                cache_hit=True)
        recs = {r["rung"]: r for r in telemetry.spans_between(
            t_a - 1.0, time.monotonic())}
        c = recs["compile"]
        assert c["dur_s"] == 0.25 and c["parent_id"] == d.span_id
        assert c["t0"] == pytest.approx(t_a - 0.25, abs=0.05)
        assert c["attrs"] == {"label": "decode[b=4]", "cache_hit": True}

    def test_leave_fixes_the_stretch_and_end_emits_later(
            self, fresh_buffer):
        t_a = time.monotonic()
        seg = telemetry.start_span("segment")
        with seg:
            pass                   # a with-block still ends it at once
        seg = telemetry.start_span("segment")
        seg.__enter__()
        assert telemetry.current_context()["span_id"] == seg.span_id
        seg.leave()
        assert telemetry.current_context() is None
        assert len(telemetry.spans_between(t_a, time.monotonic())) == 1
        time.sleep(0.02)
        seg.set_attr("accepted", 3)
        seg.end()
        late = telemetry.spans_between(t_a, time.monotonic())[-1]
        assert late["dur_s"] < 0.02 and late["attrs"] == {"accepted": 3}
        telemetry.NULL_SPAN.leave()    # the disarmed twin: a no-op


@pytest.mark.telemetry
class TestLexicalMirror:
    """Only a span entered with `with` mirrors into the profiler: a
    held one would lie open over every idle gap (ISSUE 25)."""

    @pytest.fixture
    def mirrored(self, monkeypatch):
        names = []

        class Annotation:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                names.append(("open", self.name))
                return self

            def __exit__(self, *exc):
                names.append(("close", self.name))

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
        telemetry.set_profiling(True)
        yield names
        telemetry.set_profiling(False)

    def test_a_held_span_opens_no_trace_annotation(self, mirrored):
        turn = telemetry.start_span("turn", session="s")
        turn.end()
        assert mirrored == []
        spans = telemetry.recorder().span_events()
        assert spans[-1]["rung"] == "turn"      # its record is kept

    def test_a_lexical_span_mirrors_for_exactly_its_block(self, mirrored):
        with telemetry.span("segment"):
            assert mirrored == [("open", "rt:segment")]
        assert mirrored == [("open", "rt:segment"),
                            ("close", "rt:segment")]

    def test_no_annotation_without_a_running_profile(self, mirrored):
        telemetry.set_profiling(False)
        with telemetry.span("segment"):
            pass
        assert mirrored == []

    def test_the_request_clocks_held_span_is_not_mirrored(self, mirrored):
        from theroundtaible_tpu.utils import tracing
        trace = tracing.RequestTrace(stream="st", session="s")
        trace.stage("admission")
        record = trace.finish("ok")
        assert mirrored == [] and record["span_id"]


# --- the round-start rungs stay out of the profiler's trace (ISSUE 37) ---


@pytest.mark.telemetry
@pytest.mark.parametrize("rung,mirrored", [
    ("plan", False), ("page_copy", False), ("share", False),
    ("pack", False), ("admit", True), ("dispatch", True)])
def test_a_round_start_rung_is_a_record_and_no_annotation(
        monkeypatch, fresh_buffer, rung, mirrored):
    names = []
    monkeypatch.setattr(telemetry, "_open_annotation",
                        lambda name: names.append(name) or name)
    monkeypatch.setattr(telemetry, "_close_annotation", lambda ann: None)
    t_a = time.monotonic()
    telemetry.set_profiling(True)
    try:
        with telemetry.span("admit") as outer:
            names.clear()
            with telemetry.span(rung, pages=1) as inner:
                with telemetry.span("dispatch"):
                    pass
    finally:
        telemetry.set_profiling(False)
    assert names == ([rung] if mirrored else []) + ["dispatch"]
    recs = {r["span_id"]: r for r in
            telemetry.spans_between(t_a, time.monotonic())}
    assert recs[inner.span_id]["parent_id"] == outer.span_id
    assert recs[inner.span_id]["attrs"] == {"pages": 1}
    # children still nest under it, in its trace
    (child,) = [r for r in recs.values()
                if r["parent_id"] == inner.span_id]
    assert child["trace_id"] == outer.trace_id


# --- the loop clock (ISSUE 25) ---


PHASES = ("wait", "admit", "admit_sync", "build", "dispatch", "sync")
WITHIN = {"admit": {"sync": "admit_sync", "dispatch": "admit"}}


class TestLoopClock:
    def test_phases_telescope_to_the_wall(self):
        t0 = time.monotonic()
        clock = telemetry.LoopClock(PHASES, "wait")
        for phase in ("admit", "build", "dispatch", "sync", "build",
                      "wait"):
            time.sleep(0.002)
            clock.mark(phase)
        snap = clock.snapshot()
        wall = time.monotonic() - t0
        assert sum(snap.values()) == pytest.approx(wall, rel=0.01)
        assert snap["build"] >= 0.004 and snap["admit_sync"] == 0.0
        assert clock.phase == "wait"

    def test_a_seam_switches_and_marks_back(self):
        clock = telemetry.LoopClock(PHASES, "build", within=WITHIN)
        back = clock.switch("sync")
        assert (back, clock.phase) == ("build", "sync")
        clock.mark(back)
        assert clock.phase == "build"
        # inside admission a blocking read is admit_sync, and issuing a
        # program stays admission's own host work
        clock.mark("admit")
        back = clock.switch("sync")
        assert (back, clock.phase) == ("admit", "admit_sync")
        clock.mark(back)
        back = clock.switch("dispatch")
        assert (back, clock.phase) == ("admit", "admit")
        clock.mark(back)
        assert clock.phase == "admit"

    def test_unarmed_a_mark_creates_no_span_and_the_totals_move(self):
        telemetry.disarm()
        before = telemetry.spans_emitted()
        clock = telemetry.LoopClock(PHASES, "wait")
        time.sleep(0.002)
        clock.mark("build")
        clock.mark("wait")
        assert telemetry.spans_emitted() == before
        assert clock._open is None
        assert clock.seconds["wait"] >= 0.002

    @pytest.mark.telemetry
    def test_armed_the_stretches_lie_end_to_end_outside_the_flight_ring(
            self, fresh_buffer):
        ring_before = len(telemetry.recorder().span_events())
        t_a = time.monotonic()
        clock = telemetry.LoopClock(PHASES, "wait", engine="e")
        clock.tick = 7
        for phase in ("admit", "build", "sync", "build", "wait"):
            time.sleep(0.001)
            clock.mark(phase)
        recs = [r for r in telemetry.spans_between(t_a, time.monotonic())
                if r["rung"].startswith("loop.")]
        # armed from the first mark on: admit, build, sync, build are
        # over, the last wait is still open
        assert [r["rung"] for r in recs] == [
            "loop.admit", "loop.build", "loop.sync", "loop.build"]
        for a, b in zip(recs, recs[1:]):
            assert a["t0"] + a["dur_s"] == pytest.approx(b["t0"],
                                                         abs=2e-6)
        assert {r["trace_id"] for r in recs} == {recs[0]["trace_id"]}
        assert all(r["attrs"] == {"engine": "e", "tick": 7, "fed": 0}
                   for r in recs)
        assert len(telemetry.recorder().span_events()) == ring_before
        # disarmed mid-phase: the open stretch still ends, no new one
        telemetry.disarm()
        clock.mark("build")
        telemetry.arm()
        assert clock._open is None
        last = telemetry.spans_between(t_a, time.monotonic())
        assert last == []          # (a fresh arming, a fresh buffer)

    @pytest.mark.telemetry
    def test_while_profiling_each_stretch_is_an_rt_loop_annotation(
            self, monkeypatch, fresh_buffer):
        names = []
        monkeypatch.setattr(telemetry, "_open_annotation",
                            lambda name: names.append(name) or name)
        monkeypatch.setattr(telemetry, "_close_annotation",
                            lambda ann: names.append("/" + ann))
        telemetry.set_profiling(True)
        try:
            clock = telemetry.LoopClock(PHASES, "wait")
            clock.mark("build")
            clock.mark("sync")
        finally:
            telemetry.set_profiling(False)
        clock.mark("wait")
        assert names == ["loop.build", "/loop.build", "loop.sync",
                         "/loop.sync"]

    # --- the feed bit (ISSUE 37) ---

    # (what the loop does, the tickets it holds) -> is the device fed?
    FEEDS = [
        ("a dispatch feeds, its read drains",
         ["feed", "drain1"], [True, False]),
        ("pipelined: the read of the first leaves the second outstanding",
         ["feed", "feed", "drain1", "drain2"], [True, True, True, False]),
        ("a prologue: one read drains every chunk's ticket",
         ["feed", "feed", "feed", "drain"], [True, True, True, False]),
        ("a read of an older ticket, after a later one, changes nothing",
         ["feed", "feed", "drain2", "drain1"], [True, True, False, False]),
        ("a failed dispatch's handler drains what nobody will read",
         ["feed", "feed", "drain", "drain"], [True, True, False, False]),
        ("a drain with nothing issued stays unfed",
         ["drain", "feed"], [False, True]),
    ]

    @pytest.mark.parametrize("what,steps,fed", FEEDS,
                             ids=[f[0] for f in FEEDS])
    def test_fed_is_a_count_of_outstanding_tickets(self, what, steps, fed):
        clock = telemetry.LoopClock(PHASES, "build")
        tickets, seen = [], []
        for step in steps:
            if step == "feed":
                tickets.append(clock.feed())
            else:
                n = step[len("drain"):]
                clock.drain(tickets[int(n) - 1] if n else None)
            seen.append(clock.fed)
        assert seen == fed
        assert tickets == list(range(1, len(tickets) + 1))

    def test_fed_and_unfed_stretches_telescope_to_the_wall(self):
        """A pipelined loop: dispatch A, dispatch B, read A (still
        fed), read B. Every phase's starved seconds are at most its
        seconds, the unfed and the fed parts sum to the thread's wall,
        and what was slept unfed is what reads starved."""
        t0 = time.monotonic()
        clock = telemetry.LoopClock(PHASES, "wait", within=WITHIN)
        lap = 0.004

        def spend(phase):
            clock.mark(phase)
            time.sleep(lap)

        spend("build")                      # unfed
        spend("dispatch")                   # unfed: fed at its return
        a = clock.feed()
        spend("build")                      # fed
        spend("dispatch")                   # fed
        b = clock.feed()
        spend("sync")                       # fed
        clock.drain(a)
        spend("build")                      # fed: b is outstanding
        spend("sync")                       # fed
        clock.drain(b)
        spend("build")                      # unfed
        clock.mark("wait")
        seconds, starved = clock.snapshots()
        wall = time.monotonic() - t0
        assert tuple(seconds) == tuple(starved) == PHASES
        assert all(starved[p] <= seconds[p] for p in PHASES)
        assert sum(seconds.values()) == pytest.approx(wall, rel=0.01)
        # (the drain comes a few microseconds before the next mark)
        assert starved["sync"] < 1e-3
        assert starved["dispatch"] == pytest.approx(seconds["dispatch"] / 2,
                                                    rel=0.25)
        assert starved["build"] == pytest.approx(seconds["build"] / 2,
                                                 rel=0.25)
        unfed = sum(starved.values())
        assert 3 * lap <= unfed <= wall - 4 * lap

    def test_a_within_rename_keeps_the_bit(self):
        clock = telemetry.LoopClock(PHASES, "admit", within=WITHIN)
        clock.feed()
        back = clock.switch("sync")
        assert (clock.phase, clock.fed) == ("admit_sync", True)
        time.sleep(0.002)
        clock.mark(back)
        assert clock.seconds["admit_sync"] >= 0.002
        assert clock.starved["admit_sync"] == 0.0
        clock.drain()
        back = clock.switch("sync")
        time.sleep(0.002)
        clock.mark(back)
        assert clock.fed is False
        assert clock.starved["admit_sync"] >= 0.002

    def test_snapshots_from_another_thread_never_read_more_starved(self):
        """describe()'s read races the loop's marks: whatever lap it
        catches, no phase reads more starved than spent, and both dicts
        keep every phase."""
        import sys
        import threading

        clock = telemetry.LoopClock(PHASES, "wait")
        stop = threading.Event()
        bad = []

        def reader():
            while not stop.is_set():
                seconds, starved = clock.snapshots()
                bad.extend(p for p in PHASES if starved[p] > seconds[p])
                if tuple(seconds) != PHASES or tuple(starved) != PHASES:
                    bad.append("keys")

        readers = [threading.Thread(target=reader) for _ in range(3)]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for thread in readers:
                thread.start()
            bound = time.monotonic() + 5.0
            for i in range(20000):
                clock.mark(PHASES[i % len(PHASES)])
                if i % 3 == 0:
                    clock.feed()
                elif i % 3 == 2:
                    clock.drain()
                if time.monotonic() > bound:
                    break
        finally:
            stop.set()
            for thread in readers:
                thread.join(timeout=10.0)
            sys.setswitchinterval(interval)
        assert not any(thread.is_alive() for thread in readers)
        assert bad == []

    def test_unarmed_a_flip_creates_no_span(self):
        telemetry.disarm()
        before = telemetry.spans_emitted()
        clock = telemetry.LoopClock(PHASES, "build")
        clock.drain(clock.feed())
        assert telemetry.spans_emitted() == before
        assert clock._open is None

    @pytest.mark.telemetry
    def test_armed_a_stretch_also_ends_where_the_feed_changes(
            self, fresh_buffer):
        t_a = time.monotonic()
        clock = telemetry.LoopClock(PHASES, "wait", engine="e")
        clock.mark("build")
        ticket = clock.feed()               # build goes on, fed
        clock.feed()                        # a second handle: no flip
        clock.mark("sync")
        clock.drain(ticket)                 # one still outstanding
        clock.drain()
        clock.mark("build")
        clock.mark("wait")
        recs = [r for r in telemetry.spans_between(t_a, time.monotonic())
                if r["rung"].startswith("loop.")]
        assert [(r["rung"], r["attrs"]["fed"]) for r in recs] == [
            ("loop.build", 0), ("loop.build", 1), ("loop.sync", 1),
            ("loop.sync", 0), ("loop.build", 0)]
        for a, b in zip(recs, recs[1:]):
            assert a["t0"] + a["dur_s"] == pytest.approx(b["t0"],
                                                         abs=2e-6)

    @pytest.mark.telemetry
    def test_while_profiling_a_flip_keeps_the_annotations_name(
            self, monkeypatch, fresh_buffer):
        names = []
        monkeypatch.setattr(telemetry, "_open_annotation",
                            lambda name: names.append(name) or name)
        monkeypatch.setattr(telemetry, "_close_annotation",
                            lambda ann: names.append("/" + ann))
        telemetry.set_profiling(True)
        try:
            clock = telemetry.LoopClock(PHASES, "wait")
            clock.mark("build")
            clock.feed()
            clock.mark("sync")
        finally:
            telemetry.set_profiling(False)
        clock.mark("wait")
        assert names == ["loop.build", "/loop.build", "loop.build",
                         "/loop.build", "loop.sync", "/loop.sync"]

    def test_the_serving_seams_switch_the_threads_clock_and_back(self):
        from theroundtaible_tpu.engine.serving_loop import (host_sync,
                                                            run_dispatch)
        clock = telemetry.LoopClock(PHASES, "build", within=WITHIN)
        seen = []
        assert telemetry.loop_clock() is None
        telemetry.bind_loop_clock(clock)
        try:
            assert run_dispatch(lambda: seen.append(clock.phase) or 1,
                                None) == 1
            assert host_sync(lambda: seen.append(clock.phase) or 2) == 2
            with pytest.raises(ValueError):
                host_sync(lambda: (_ for _ in ()).throw(ValueError()))
            assert clock.phase == "build"
            clock.mark("admit")
            host_sync(lambda: seen.append(clock.phase))
            assert clock.phase == "admit"
        finally:
            telemetry.bind_loop_clock(None)
        assert seen == ["dispatch", "sync", "admit_sync"]
        # a thread with no clock (generate_batch callers) pays a lookup
        assert host_sync(lambda: 3) == 3


# --- watchdog / breaker auto-dump seams ---


@pytest.mark.chaos
class TestAutoDumps:
    def test_hang_carries_telemetry_dump_path(self):
        deadlines.arm_watchdog()
        budget = deadlines.Budget.root(0.2, rung="dispatch")
        with pytest.raises(deadlines.HangDetected) as e:
            deadlines.watched_wait(lambda: time.sleep(5.0), budget,
                                   "dispatch")
        assert "telemetry_dump:" in str(e.value)
        assert Path(e.value.telemetry_dump).exists()
        payload = json.loads(Path(e.value.telemetry_dump).read_text())
        assert payload["trigger"] == "hang"
        assert telemetry.counter_total("roundtable_hangs_total",
                                       rung="dispatch") == 1
        # the dump message must still classify as a hang
        from theroundtaible_tpu.core.errors import classify_error
        assert classify_error(e.value) == "hang"

    def test_breaker_trip_dumps_once_per_open_transition(self):
        b = CircuitBreaker(threshold=2, name="eng")
        b.record_failure(RuntimeError("x"))
        assert telemetry.counter_total(
            "roundtable_breaker_trips_total") == 0
        b.record_failure(RuntimeError("y"))  # crosses the threshold
        b.record_failure(RuntimeError("z"))  # already open: no re-trip
        assert telemetry.counter_total(
            "roundtable_breaker_trips_total", engine="eng") == 1
        assert telemetry.REGISTRY.gauge_value(
            "roundtable_breaker_open", engine="eng") == 1.0
        assert telemetry.counter_total(
            "roundtable_flight_dumps_total", trigger="breaker_trip") == 1
        b.record_success()
        assert telemetry.REGISTRY.gauge_value(
            "roundtable_breaker_open", engine="eng") == 0.0

    def test_forced_trip_dumps_too(self):
        b = CircuitBreaker(threshold=3, name="eng2")
        b.trip(RuntimeError("permanent"))
        assert telemetry.counter_total(
            "roundtable_breaker_trips_total", engine="eng2") == 1

    def test_fault_injection_counts(self):
        faults.arm("dispatch", count=2)
        with pytest.raises(faults.FaultInjected):
            faults.maybe_inject("dispatch")
        assert telemetry.counter_total(
            "roundtable_faults_injected_total", point="dispatch") == 1


# --- single-source-of-truth drift lint (CI satellite) ---


class TestSurfaceDrift:
    def test_fleet_health_keys_are_bound_to_registry_series(self):
        from theroundtaible_tpu.engine.fleet import fleet_health
        health = fleet_health()
        bound = set(telemetry.SURFACE_BINDINGS["fleet_health"])
        unbound = set(health) - bound
        assert not unbound, (
            f"fleet_health grew key(s) {sorted(unbound)} with no "
            "registry binding — declare how the unified registry sees "
            "them in telemetry.SURFACE_BINDINGS['fleet_health'] (the "
            "single-source-of-truth contract, ISSUE 5)")

    def test_scheduler_describe_keys_are_bound(self):
        from theroundtaible_tpu.engine.scheduler import scheduler_for
        cfg = _tpu_cfg(seed=301)
        engine = get_engine(cfg)
        sched = scheduler_for(engine)
        try:
            desc = sched.describe()
        finally:
            sched.close()
        bound = set(telemetry.SURFACE_BINDINGS["scheduler_describe"])
        unbound = set(desc) - bound
        assert not unbound, (
            f"SessionScheduler.describe() grew key(s) {sorted(unbound)} "
            "with no registry binding — declare them in "
            "telemetry.SURFACE_BINDINGS['scheduler_describe']")

    def test_fleet_health_telemetry_view_is_live(self):
        from theroundtaible_tpu.engine.fleet import fleet_health
        telemetry.inc("roundtable_hangs_total", rung="dispatch")
        view = fleet_health()["telemetry"]
        assert view["metrics"][
            "roundtable_hangs_total{rung=dispatch}"] == 1

    def test_engine_view_label_match_is_exact(self):
        """'knight' must not absorb 'knight2' series on a prefix match
        (review finding)."""
        from theroundtaible_tpu.engine.trace_hooks import \
            engine_telemetry_view
        telemetry.inc("roundtable_x_total", 1, engine="knight")
        telemetry.inc("roundtable_x_total", 5, engine="knight2")
        view = engine_telemetry_view("knight")
        assert view["metrics"] == {
            "roundtable_x_total{engine=knight}": 1}


# --- scheduler counters publish in lockstep ---


@pytest.mark.telemetry
@pytest.mark.scheduler(allow_serial=True)
class TestSchedulerLockstep:
    def test_describe_counters_match_registry(self):
        from theroundtaible_tpu.engine.scheduler import scheduler_for
        cfg = _tpu_cfg(seed=302)
        engine = get_engine(cfg)
        sched = scheduler_for(engine)
        try:
            out, stats = sched.submit(
                "sess-a", [("Sage", "one small question")],
                max_new_tokens=4, timeout_s=120.0)
            assert len(out) == 1
            desc = sched.describe()
            name = engine.cfg.name
            for key, metric in (
                    ("admitted", "roundtable_sched_admitted_total"),
                    ("completed", "roundtable_sched_completed_total"),
                    ("segments", "roundtable_sched_segments_total")):
                assert desc[key] == telemetry.counter_total(
                    metric, engine=name), key
            assert desc["admitted"] == 1
            assert stats.sched is not None
        finally:
            sched.close()


# --- end-to-end acceptance ---


@pytest.mark.telemetry
@pytest.mark.chaos
class TestEndToEnd:
    def test_discussion_spans_match_budget_tree_and_hang_dumps(
            self, project_root):
        """ISSUE 5 acceptance: with telemetry armed (marker guard), a
        2-knight run_discussion under an injected `hang` fault (the
        PR-2 chaos path) completes degraded, emits a per-session
        spans.jsonl whose nesting matches the Budget-tree rungs
        discussion→round→turn→prefill|decode→segment→dispatch, writes
        the registry snapshot next to it, and the hang ships a
        flight-recorder dump."""
        cfg = _tpu_cfg(seed=303)
        adapter = TpuLlmAdapter("tpu-llm", cfg, timeout_ms=600_000)
        # Warm both program shapes so the only slow wait is the fault.
        adapter.execute_round([KnightTurn("Sage", "warm"),
                               KnightTurn("Oracle", "warm too")])
        adapter.execute_for("Sage", "warm the single-row path")
        deadlines.configure_rungs({"dispatch": 2.0})
        faults.arm("hang", count=1, delay_s=10.0)
        config = _discussion_config(cfg)
        with pytest.warns(UserWarning, match="retrying 2 knight"):
            result = run_discussion(
                "telemetry acceptance topic", config,
                {"tpu-llm": adapter}, str(project_root))
        assert result.rounds == 1
        assert len(result.all_rounds) == 2     # both knights spoke

        tdir = Path(result.session_path) / "telemetry"
        spans = [json.loads(ln) for ln in
                 (tdir / "spans.jsonl").read_text().splitlines()]
        by_id = {s["span_id"]: s for s in spans}
        rungs = {s["rung"] for s in spans}
        assert {"discussion", "round", "turn", "prefill", "decode",
                "segment", "dispatch"} <= rungs

        def parent_rung(s):
            p = by_id.get(s.get("parent_id"))
            return p["rung"] if p else None

        # Budget-tree nesting, rung by rung (spans whose parents were
        # cut by the ring/sink boundary — none here — would show None).
        for s in spans:
            if s["rung"] == "round":
                assert parent_rung(s) == "discussion"
            elif s["rung"] == "turn":
                assert parent_rung(s) == "round"
            elif s["rung"] in ("prefill", "decode"):
                assert parent_rung(s) == "turn"
            elif s["rung"] == "segment":
                assert parent_rung(s) == "decode"
            elif s["rung"] == "dispatch":
                assert parent_rung(s) in ("prefill", "decode",
                                          "segment", "turn")
        # one trace: every span shares the discussion's trace id
        disc = next(s for s in spans if s["rung"] == "discussion")
        assert all(s["trace_id"] == disc["trace_id"] for s in spans)

        # the hang shipped its postmortem + counted in the registry
        assert telemetry.counter_total("roundtable_hangs_total") >= 1
        assert telemetry.counter_total("roundtable_flight_dumps_total",
                                       trigger="hang") >= 1
        dump = Path(telemetry.last_dump_path())
        assert dump.exists()
        # the serial-retry ladder escalation dumped too
        assert telemetry.counter_total(
            "roundtable_degradations_total", rung="serial_retry") >= 1

        # metrics.prom snapshot written next to the spans
        prom = (tdir / "metrics.prom").read_text()
        assert "roundtable_turns_total" in prom
        assert "roundtable_decode_tokens_total" in prom

    def test_status_telemetry_renders_session_view(self, project_root,
                                                   capsys):
        """`roundtable status --telemetry` renders the files the
        armed discussion produced."""
        cfg = _tpu_cfg(seed=304)
        adapter = TpuLlmAdapter("tpu-llm", cfg, timeout_ms=600_000)
        config = _discussion_config(cfg)
        run_discussion("status telemetry topic", config,
                       {"tpu-llm": adapter}, str(project_root))
        from theroundtaible_tpu.commands.status import status_command
        rc = status_command(project_root=str(project_root),
                            telemetry_view=True)
        out = capsys.readouterr().out
        assert rc == 0
        assert "Registry snapshot" in out
        assert "roundtable_turns_total" in out
        assert "Spans" in out


# --- maybe_profile satellite ---


@pytest.mark.telemetry
class TestMaybeProfile:
    def test_profile_opens_root_span_sharing_trace_id(self, tmp_path,
                                                      monkeypatch):
        from theroundtaible_tpu.utils.metrics import maybe_profile
        monkeypatch.setenv("ROUNDTABLE_PROFILE",
                           str(tmp_path / "trace"))
        sink = telemetry.session_sink(tmp_path)
        with maybe_profile(tmp_path):
            with telemetry.span("discussion", sink=sink) as d:
                disc_trace = d.trace_id
        spans = [json.loads(ln) for ln in
                 (tmp_path / "telemetry" / "spans.jsonl")
                 .read_text().splitlines()]
        prof = next(s for s in spans if s["rung"] == "profile")
        # one trace id across the device profile root and the JSONL tree
        assert prof["trace_id"] == disc_trace

    def test_degrade_warning_goes_through_ui(self, tmp_path,
                                             monkeypatch, capsys):
        """A broken profiler start degrades via ui.warn (stderr,
        styled), not a bare print on stdout."""
        from theroundtaible_tpu.utils.metrics import maybe_profile
        monkeypatch.setenv("ROUNDTABLE_PROFILE", str(tmp_path / "t"))
        import jax as _jax
        monkeypatch.setattr(
            _jax.profiler, "start_trace",
            lambda *a, **k: (_ for _ in ()).throw(
                RuntimeError("no profiler here")))
        with maybe_profile(tmp_path):
            pass
        captured = capsys.readouterr()
        assert "tracing unavailable" in captured.err
        assert "tracing unavailable" not in captured.out
