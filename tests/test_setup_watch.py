"""The set-up table of engine/compile_watch.py and the collector's hook
(ISSUE 54): synthetic monitoring events through the module's own
listeners — what JAX sends, in the order it sends it (a timed section's
opening as a scalar, its extent as a time span when it ends) — one real
`jax.jit`, and the spans while armed. Host-only: no engine is built.
"""
import gc
import threading
import time

import pytest

from theroundtaible_tpu.commands import status
from theroundtaible_tpu.engine import compile_watch as cw
from theroundtaible_tpu.utils import telemetry

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
SAVED = "/jax/compilation_cache/compile_time_saved_sec"
SETUP_KEYS = {"closed", "wall_s", "stages", "phases", "staged",
              "programs", "bodies_traced", "bodies_reused", "cache_hits",
              "cache_misses", "saved_s", "misses", "twice", "slowest"}


@pytest.fixture(autouse=True)
def table(tmp_path, monkeypatch):
    """A fresh, open table and a quiet thread."""
    monkeypatch.setenv("ROUNDTABLE_TELEMETRY_DIR", str(tmp_path))
    monkeypatch.delenv(cw.STRICT_ENV, raising=False)
    cw.install()
    cw.reset_steady_state()
    fresh = cw._Setup()
    fresh.t0 = time.monotonic()
    monkeypatch.setattr(cw, "_setup", fresh)
    cw._tls.row, cw._tls.depth, cw._tls.retrieved = None, 0, False
    yield fresh
    cw.reset_steady_state()


class Clock:
    """Wall-clock instants for a thread's events, in order."""

    def __init__(self):
        self.now = time.time() - 30.0

    def span(self, event, dur, fun_name, inside=()):
        """One trace or lower interval of `dur` seconds; `inside` are
        (event, dur, fun_name) heard first, from within it."""
        start = self.now
        cw._on_scalar(event, start, fun_name=fun_name)      # it opens
        at = start + 0.001
        for ev, d, name in inside:
            cw._on_scalar(ev, at, fun_name=name)
            cw._on_time_span(ev, at, at + d, fun_name=name)
            at += d + 0.001
        self.now = max(start + dur, at)
        cw._on_time_span(event, start, self.now, fun_name=fun_name)
        self.now += 0.001

    def program(self, name, trace=0.2, lower=0.3, compile_s=1.0,
                hit=False, traced=True):
        if traced:
            self.span(TRACE, trace, name)
        self.span(LOWER, lower, f"jit({name})")
        if hit:
            cw._on_duration(SAVED, 9.0)
            cw._on_duration(RETRIEVAL, compile_s)
            cw._on_duration(COMPILE, compile_s + 0.01,
                            fun_name=f"jit({name})")
        else:
            cw._on_duration(COMPILE, compile_s, fun_name=f"jit({name})")
        self.now += compile_s


def test_a_nested_trace_counts_once_and_the_lowering_takes_back_its_own():
    clock = Clock()
    with cw.label("ragged[t=512]", engine="m", shape=512):
        clock.span(TRACE, 1.0, "step", inside=[
            (TRACE, 0.2, "sin"), (TRACE, 0.3, "inner")])
        clock.span(LOWER, 2.0, "jit(step)", inside=[(TRACE, 0.5, "less")])
        cw._on_duration(COMPILE, 4.0, fun_name="jit(step)")
    report = cw.setup_report()
    assert report["stages"] == pytest.approx(
        {"trace": 1.0, "lower": 2.0, "retrieve": 0.0, "compile": 4.0})
    assert report["programs"] == 1 and report["cache_misses"] == 1
    (row,) = report["by_program"]
    assert (row["label"], row["fun_name"], row["shape"]) == (
        "ragged[t=512]", "jit(step)", 512)
    assert (row["trace_s"], row["lower_s"], row["compile_s"]) == \
        pytest.approx((1.0, 2.0, 4.0))
    assert row["cache_hit"] is False and "retrieve_s" not in row
    assert row["thread"] == threading.current_thread().name
    # t0 is the first interval's start, on time.monotonic()
    assert row["t0"] == pytest.approx(time.monotonic() - 30.0, abs=1.0)


def test_an_interval_whose_opening_was_not_heard_counts_as_outermost():
    """The hooks went in while a trace was under way."""
    now = time.time()
    cw._on_time_span(TRACE, now - 2.0, now - 1.0, fun_name="step")
    cw._on_time_span(LOWER, now - 1.0, now - 0.5, fun_name="jit(step)")
    report = cw.setup_report()
    assert report["stages"]["trace"] == pytest.approx(1.0)
    assert report["stages"]["lower"] == pytest.approx(0.5)
    assert report["programs"] == 1
    (row,) = report["by_program"]           # lowered, never compiled
    assert row["cache_hit"] is None and "compile_s" not in row


def test_a_hit_is_one_row_with_retrieve_s_and_no_compile_s():
    c0 = cw.compiles_seen()
    with cw.label("decode[b=4]", engine="m", batch=4):
        Clock().program("decode", compile_s=0.4, hit=True)
    report = cw.setup_report()
    (row,) = report["by_program"]
    assert row["cache_hit"] is True and row["batch"] == 4
    assert row["retrieve_s"] == pytest.approx(0.4)
    assert "compile_s" not in row
    assert (report["cache_hits"], report["cache_misses"]) == (1, 0)
    assert report["stages"]["retrieve"] == pytest.approx(0.4)
    assert report["stages"]["compile"] == 0.0
    assert report["saved_s"] == pytest.approx(9.0) and not report["misses"]
    # the enclosing compile event was not counted again
    assert cw.compiles_seen() - c0 == 1
    assert cw.history()[-1]["fun_name"] == "jit(decode)"


def test_one_fun_name_lowered_twice_is_two_rows_and_a_twice_entry():
    """What PR 50 needed to see: the same trace, lowered once for an
    uncommitted argument and once for a committed one."""
    clock = Clock()
    with cw.label("prefill[b=1,bucket=128]", engine="m"):
        clock.program("prefill_step")
    with cw.label("prefill[b=1,bucket=128]", engine="m"):
        clock.program("prefill_step", trace=0.0)
    with cw.label("decode[b=1]", engine="m"):
        clock.program("decode_loop")
    report = cw.setup_report()
    assert [r["fun_name"] for r in report["by_program"]] == [
        "jit(prefill_step)", "jit(prefill_step)", "jit(decode_loop)"]
    assert report["twice"] == {"jit(prefill_step)": 2}
    assert report["programs"] == 3
    assert report["misses"] == {"prefill[b=1,bucket=128]": 2,
                                "decode[b=1]": 1}
    assert cw.summary()["setup"]["twice"] == {"jit(prefill_step)": 2}


@pytest.mark.parametrize("reopened", [False, True])
def test_the_close_keeps_later_events_out_and_reopen_lets_them_in(
        reopened):
    clock = Clock()
    with cw.label("decode[b=2]", engine="m"):
        clock.program("decode_loop")
    cw.warmup_complete("m")
    closed = cw.setup_report()
    assert closed["closed"] and closed["closed_by"] == "m"
    if reopened:
        cw.reopen_warmup("m")
        assert not cw.setup_report()["closed"]
    before, steady = len(cw.history()), cw.steady_state_compiles()
    with cw.label("decode[b=3]", engine="m"):
        clock.program("decode_loop")
    report = cw.setup_report()
    # history() hears it either way; the sentinel where steady
    assert cw.history()[-1]["label"] == "decode[b=3]"
    assert len(cw.history()) == min(before + 1, cw._HISTORY_CAP)
    if reopened:
        assert report["programs"] == 2 and len(report["by_program"]) == 2
        assert cw.steady_state_compiles() == steady
        assert not cw.history()[-1]["steady_state"]
    else:
        assert cw.history()[-1]["steady_state"]
        assert cw.steady_state_compiles() == steady + 1
        for key in ("programs", "stages", "by_program", "wall_s",
                    "cache_misses"):
            assert report[key] == closed[key]


def test_an_engine_in_steady_state_stays_out_of_an_open_table():
    """Several engines in one process: the second one's build opens the
    table again; the first one's mid-serve compile is not its set-up."""
    cw.warmup_complete("first")
    with cw.phase("init"):
        pass
    assert not cw.setup_report()["closed"]
    clock = Clock()
    with cw.label("decode[b=1]", engine="first"):
        clock.program("decode_loop")
    with cw.label("decode[b=1]", engine="second"):
        clock.program("decode_loop")
    report = cw.setup_report()
    assert report["programs"] == 1 and report["twice"] == {}
    assert cw.steady_state_compiles() == 1


def test_the_table_stays_within_its_rows_and_still_counts():
    clock = Clock()
    for i in range(cw._SETUP_ROWS_CAP + 20):
        with cw.label(f"p[{i}]", engine="m"):
            clock.program(f"f{i}", trace=0.01, lower=0.01, compile_s=0.01)
    report = cw.setup_report()
    assert len(report["by_program"]) == cw._SETUP_ROWS_CAP == 128
    assert report["rows_dropped"] == 20
    assert report["programs"] == report["cache_misses"] == 148
    assert report["stages"]["compile"] == pytest.approx(1.48)
    assert len(report["misses"]) == cw._SETUP_MISSES_CAP == 32
    assert len(cw.summary()["setup"]["slowest"]) == 8


def test_summary_setup_has_its_keys_and_they_are_bound():
    clock = Clock()
    with cw.label("a", engine="m"):
        clock.program("fa", compile_s=3.0)
    with cw.label("b", engine="m"):
        clock.program("fb", compile_s=0.2, hit=True)
    with cw.label("c", engine="m"):
        clock.program("fc", compile_s=5.0)
    cw.warmup_complete("m")
    setup = cw.summary()["setup"]
    assert set(setup) == SETUP_KEYS == set(
        telemetry.SURFACE_BINDINGS["engine_setup"])
    assert set(setup["stages"]) == set(cw.STAGES)
    assert set(setup["phases"]) == set(cw.PHASES)
    assert setup["misses"] == {"a": 1, "c": 1}
    assert [r["label"] for r in setup["slowest"]] == ["c", "a", "b"]
    assert setup["closed"] and setup["wall_s"] > 0.0
    assert (setup["programs"], setup["cache_hits"],
            setup["cache_misses"]) == (3, 1, 2)
    assert set(cw.gc_report()) == set(
        telemetry.SURFACE_BINDINGS["engine_gc"])


def test_two_threads_do_not_mix_their_rows():
    """Each thread's trace, lowering and compile, interleaved."""
    turn = [threading.Event() for _ in range(2)]
    clocks = [Clock(), Clock()]

    def bring_up(i):
        other = turn[1 - i]
        steps = [
            lambda: clocks[i].span(TRACE, 0.1 * (i + 1), f"f{i}"),
            lambda: clocks[i].span(LOWER, 0.2 * (i + 1), f"jit(f{i})"),
            lambda: cw._on_duration(COMPILE, 1.0 * (i + 1),
                                    fun_name=f"jit(f{i})"),
        ]
        with cw.label(f"prog[{i}]", engine="m"):
            for step in steps:
                assert turn[i].wait(10.0)
                turn[i].clear()
                step()
                other.set()

    threads = [threading.Thread(target=bring_up, args=(i,),
                                name=f"bringer-{i}") for i in range(2)]
    for t in threads:
        t.start()
    turn[0].set()
    for t in threads:
        t.join(20.0)
    rows = {r["thread"]: r for r in cw.setup_report()["by_program"]}
    assert set(rows) == {"bringer-0", "bringer-1"}
    for i in range(2):
        r = rows[f"bringer-{i}"]
        assert (r["label"], r["fun_name"]) == (f"prog[{i}]", f"jit(f{i})")
        assert (r["trace_s"], r["lower_s"], r["compile_s"]) == \
            pytest.approx((0.1 * (i + 1), 0.2 * (i + 1), 1.0 * (i + 1)))


def test_the_phases_tile_the_wall_and_the_outermost_owns():
    t0 = time.monotonic()
    with cw.phase("init"):
        time.sleep(0.02)
    with cw.phase("warm_programs"):
        with cw.phase("pools"):            # inside another: silent
            time.sleep(0.02)
    cw.phase("warm_traffic").begin()       # left open: the close ends it
    time.sleep(0.03)
    running = cw.setup_report()
    assert not running["closed"] and running["phases"]["warm_traffic"] > 0
    cw.warmup_complete("m")
    report = cw.setup_report()
    wall = time.monotonic() - t0
    assert report["phases"]["pools"] == 0.0
    # (a sleep is a floor; a busy host adds to it)
    assert 0.02 <= report["phases"]["init"] < 1.0
    assert 0.02 <= report["phases"]["warm_programs"] < 1.0
    assert 0.03 <= report["phases"]["warm_traffic"] < 1.0
    assert sum(report["phases"].values()) <= report["wall_s"] <= wall + 0.01
    assert sum(report["phases"].values()) >= 0.8 * report["wall_s"]
    time.sleep(0.01)
    assert cw.setup_report()["wall_s"] == report["wall_s"]
    snap = telemetry.REGISTRY.snapshot_compact()
    assert snap["roundtable_setup_seconds_total{stage=warm_traffic}"] > 0


def test_stage_seconds_are_booked_to_the_phase_that_is_open():
    clock = Clock()
    with cw.phase("quantize"):
        clock.program("true_divide", trace=0.1, lower=0.2, compile_s=0.3,
                      hit=True)
    with cw.phase("warm_programs"):
        with cw.label("ragged[t=64]", engine="m"):
            clock.span(TRACE, 1.0, "step", inside=[(TRACE, 0.4, "sin")])
            clock.span(LOWER, 2.0, "jit(step)")
            cw._on_duration(COMPILE, 4.0, fun_name="jit(step)")
    clock.program("late_eager", trace=0.0, lower=0.5, compile_s=0.5)
    report = cw.setup_report()
    assert report["staged"] == pytest.approx({
        "init": 0.0, "quantize": 0.6, "pools": 0.0, "warm_programs": 7.0,
        "warm_traffic": 0.0})
    # what no phase was open for is in `stages` alone
    assert sum(report["stages"].values()) == pytest.approx(8.6, abs=0.01)


def test_a_real_jit_yields_a_row_with_all_three_stages():
    import jax
    import jax.numpy as jnp

    salt = time.time_ns() % 100_003

    def fresh_small_function(x):
        return jnp.tanh(x * salt).sum() + salt

    x = jnp.ones((salt % 7 + 3,))       # (an eager op is a program too)
    with cw.label("unit[real]", engine="m"):
        jax.jit(fresh_small_function)(x)
    rows = [r for r in cw.setup_report()["by_program"]
            if r["label"] == "unit[real]"]
    assert [r["fun_name"] for r in rows] == ["jit(fresh_small_function)"]
    (row,) = rows
    last = "retrieve_s" if row["cache_hit"] else "compile_s"
    assert row["trace_s"] > 0 and row["lower_s"] > 0 and row[last] > 0
    assert cw.history()[-1]["fun_name"] == "jit(fresh_small_function)"
    stages = cw.setup_report()["stages"]
    assert stages["trace"] >= row["trace_s"] - 1e-3
    assert stages["lower"] >= row["lower_s"] - 1e-3


@pytest.mark.parametrize("armed", [True, False])
def test_trace_lower_and_gc_spans_are_on_the_timeline_only_while_armed(
        armed, monkeypatch):
    monkeypatch.setattr(cw, "GC_SPAN_FLOOR_S", 0.0)
    was = telemetry.ACTIVE
    telemetry.disarm()
    telemetry.arm()                         # a fresh buffer
    if not armed:
        telemetry.disarm()
    try:
        t_a = time.monotonic()
        with telemetry.span("dispatch") as stalled:
            with cw.label("ragged[t=256]", engine="m"):
                clock = Clock()
                clock.now = time.time() - 0.5
                clock.span(TRACE, 0.1, "step",
                           inside=[(TRACE, 0.02, "sin")])
                clock.span(LOWER, 0.2, "jit(step)")
                cw._on_duration(COMPILE, 0.05, fun_name="jit(step)")
            gc.collect()
            cw.gc_report()
        t_b = time.monotonic()
    finally:
        telemetry.disarm()
        if was:
            telemetry.arm()
    spans = [r for r in telemetry.spans_between(t_a - 1.0, t_b)
             if r["rung"] in ("trace", "lower", "compile", "gc")]
    if not armed:
        assert spans == []
        return
    by = {r["rung"]: r for r in spans}
    assert sorted(r["rung"] for r in spans if r["rung"] != "gc") == [
        "compile", "lower", "trace"]           # the inner trace is not one
    assert by["trace"]["attrs"] == {"label": "ragged[t=256]",
                                    "fun_name": "step"}
    assert by["lower"]["attrs"]["fun_name"] == "jit(step)"
    assert (by["trace"]["dur_s"], by["lower"]["dur_s"]) == \
        pytest.approx((0.1, 0.2), abs=1e-3)
    # on time.monotonic(): the trace began half a second before now
    assert by["trace"]["t0"] == pytest.approx(t_a - 0.5, abs=0.4)
    assert by["trace"]["t0"] < by["lower"]["t0"] < t_b
    for r in spans:
        assert r["parent_id"] == stalled.span_id
    assert by["gc"]["attrs"]["generation"] == 2
    assert by["gc"]["attrs"]["thread"] == threading.current_thread().name
    assert t_a <= by["gc"]["t0"] <= t_b


def test_the_collectors_pauses_are_summed_by_generation():
    before = cw.gc_report()
    series0 = telemetry.REGISTRY.snapshot_compact().get(
        "roundtable_gc_collections_total{generation=2}", 0.0)
    gc.collect()
    gc.collect(0)
    after = cw.gc_report()
    assert after["pauses"]["2"] == before["pauses"]["2"] + 1
    assert after["pauses"]["0"] >= before["pauses"]["0"] + 1
    assert after["seconds"]["2"] > before["seconds"]["2"]
    assert after["longest_s"]["2"] >= after["seconds"]["2"] / \
        after["pauses"]["2"]
    snap = telemetry.REGISTRY.snapshot_compact()
    assert snap["roundtable_gc_collections_total{generation=2}"] == \
        series0 + 1
    assert snap["roundtable_gc_pause_seconds_total{generation=2}"] > 0


def test_a_pause_the_buffer_was_busy_for_waits_for_the_next_flush(
        monkeypatch):
    monkeypatch.setattr(cw, "GC_SPAN_FLOOR_S", 0.0)
    was = telemetry.ACTIVE
    telemetry.disarm()
    telemetry.arm()
    try:
        t_a = time.monotonic()
        with telemetry._spans_lock:     # the frame the collector stopped
            gc.collect()
            assert len(cw._gc_late) >= 1
        assert not [r for r in telemetry.spans_between(t_a, t_a + 60)
                    if r["rung"] == "gc"]
        cw.gc_report()
        assert not cw._gc_late
        assert [r for r in telemetry.spans_between(t_a, t_a + 60)
                if r["rung"] == "gc"]
    finally:
        telemetry.disarm()
        if was:
            telemetry.arm()


def test_status_prints_the_split(capsys):
    clock = Clock()
    with cw.phase("warm_programs"):
        with cw.label("ragged[t=512]", engine="m"):
            clock.program("ragged_step", compile_s=2.0)
            clock.program("ragged_step", compile_s=0.3, hit=True)
    cw.warmup_complete("m")
    status.print_setup_split(cw.summary()["setup"])
    out = capsys.readouterr().out
    for word in ("programs=2", "cache_hits=1", "cache_misses=1", "trace",
                 "lower", "retrieve", "warm_programs", "warm_traffic",
                 "compiled fresh: ragged[t=512] x1",
                 "lowered more than once: jit(ragged_step) x2"):
        assert word in out, word
    # nothing heard, nothing printed
    status.print_setup_split(dict(
        cw.summary()["setup"], stages=dict.fromkeys(cw.STAGES, 0.0),
        phases=dict.fromkeys(cw.PHASES, 0.0)))
    assert capsys.readouterr().out == ""
