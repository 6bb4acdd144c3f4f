"""The readers of the program's own spans (the scheduler's loop clock,
`admit` and `compile` spans) and of the idle gaps' names: each on a
span list made by hand, on the small recorded trace, and with nothing
to read — no slice (a CPU run), a program without the span buffer (a
commit before it), a buffer that overflowed."""
import importlib.util
import json
import os

import pytest

import bench_paths
from harness import loopspans, manifest as mf, tracered
from theroundtaible_tpu.utils import telemetry

HERE = os.path.dirname(os.path.abspath(__file__))
SLICE = {"start": 10.0, "end": 16.0}
SPAN_READERS = ("sched.loop_host_share", "sched.loop_wait_share",
                "sched.admit_host_ms", "compile.stall_ms_per_s")
NEW = SPAN_READERS + ("device.idle_unnamed_share",)


def reader(name):
    path = os.path.join(bench_paths.BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "r_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def loop(phase, t0, end, clock="c1"):
    return {"rung": "loop." + phase, "t0": t0, "dur_s": end - t0,
            "trace_id": clock, "attrs": {"tick": 1}}


# One loop thread, end to end from 9.5 to 16.4 s; the slice is 10-16 s,
# so the first and the last stretch count for their part inside:
# sync 0.5 | build 0.5 | wait 0.3 | accept 1.2 | admit 0.1 |
# admit_sync 0.3 | admit 0.1 | dispatch 0.2 | sync 2.6 | retire 0.2
SPANS = [
    loop("sync", 9.5, 10.5), loop("build", 10.5, 11.0),
    loop("wait", 11.0, 11.3), loop("accept", 11.3, 12.5),
    loop("admit", 12.5, 12.6), loop("admit_sync", 12.6, 12.9),
    loop("admit", 12.9, 13.0), loop("dispatch", 13.0, 13.2),
    loop("sync", 13.2, 15.8), loop("retire", 15.8, 16.4),
    {"rung": "admit", "t0": 9.9, "dur_s": 9.0, "trace_id": "r0",
     "attrs": {"sync_s": 0.0}},                  # began before the slice
    {"rung": "admit", "t0": 12.5, "dur_s": 0.40, "trace_id": "r1",
     "attrs": {"sync_s": 0.30}},
    {"rung": "admit", "t0": 13.5, "dur_s": 0.05, "trace_id": "r2",
     "attrs": {"sync_s": 0.0}},
    {"rung": "admit", "t0": 14.5, "dur_s": 0.20, "trace_id": "r3",
     "attrs": {"sync_s": 0.05}},
    {"rung": "compile", "t0": 12.55, "dur_s": 0.03, "trace_id": "r1",
     "attrs": {"label": "unlabeled", "cache_hit": True}},
    {"rung": "compile", "t0": 14.55, "dur_s": 0.03, "trace_id": "r3",
     "attrs": {"label": "unlabeled", "cache_hit": True}},
    {"rung": "compile", "t0": 16.1, "dur_s": 0.5, "trace_id": "r4",
     "attrs": {"label": "decode[b=16,paged]", "cache_hit": False}},
    {"rung": "segment", "t0": 13.0, "dur_s": 2.8, "trace_id": "s",
     "attrs": {"kind": "plain"}},
]


@pytest.fixture
def buffered(monkeypatch):
    """The program's span buffer, holding SPANS."""
    monkeypatch.setattr(
        telemetry, "spans_between",
        lambda a, b: [r for r in SPANS if a <= r["t0"] < b])
    monkeypatch.setattr(telemetry, "spans_dropped", lambda: 0)


def ctx(**over):
    return dict({"slice": dict(SLICE), "trace": {}}, **over)


EXPECTED = {
    # build 0.5 + accept 1.2 + admit 0.2 + dispatch 0.2 + retire 0.2
    "sched.loop_host_share": 100 * 2.3 / 6.0,
    "sched.loop_wait_share": 100 * 0.3 / 6.0,
    "sched.admit_host_ms": 100.0,       # median of 100, 50 and 150
    "compile.stall_ms_per_s": 10.0,     # 2 x 30 ms in 6 s
}


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_span_reader_on_a_hand_made_span_list(buffered, metric):
    assert reader(metric)(ctx()) == pytest.approx(EXPECTED[metric])


def test_the_loops_stretches_clipped_to_the_slice_sum_to_it(buffered):
    seconds = loopspans.loop_seconds(ctx())
    assert sum(seconds.values()) == pytest.approx(6.0)
    assert seconds == pytest.approx({
        "sync": 3.1, "build": 0.5, "wait": 0.3, "accept": 1.2,
        "admit": 0.2, "admit_sync": 0.3, "dispatch": 0.2,
        "retire": 0.2})


def test_several_clocked_loops_are_averaged(monkeypatch, buffered):
    twice = SPANS + [dict(r, trace_id="c2") for r in SPANS
                     if r["rung"].startswith("loop.")]
    monkeypatch.setattr(
        telemetry, "spans_between",
        lambda a, b: [r for r in twice if a <= r["t0"] < b])
    assert sum(loopspans.loop_seconds(ctx()).values()) == \
        pytest.approx(6.0)


def test_no_compile_in_the_slice_reads_zero_and_no_admission_nothing(
        monkeypatch, buffered):
    loops_only = [r for r in SPANS if r["rung"].startswith("loop.")]
    monkeypatch.setattr(telemetry, "spans_between",
                        lambda a, b: list(loops_only))
    assert reader("compile.stall_ms_per_s")(ctx()) == 0.0
    assert reader("sched.admit_host_ms")(ctx()) is None


@pytest.mark.parametrize("metric", SPAN_READERS)
def test_a_span_reader_with_no_slice_returns_nothing(buffered, metric):
    assert reader(metric)(ctx(slice=None)) is None


@pytest.mark.parametrize("metric", SPAN_READERS)
def test_a_span_reader_on_a_program_without_the_buffer_returns_nothing(
        monkeypatch, metric):
    """What the parent commit is to this benchmark: no spans_between."""
    monkeypatch.delattr(telemetry, "spans_between")
    assert reader(metric)(ctx()) is None


@pytest.mark.parametrize("metric", SPAN_READERS)
def test_a_span_reader_returns_nothing_from_a_buffer_that_overflowed(
        monkeypatch, buffered, metric):
    monkeypatch.setattr(telemetry, "spans_dropped", lambda: 3)
    assert reader(metric)(ctx()) is None


def test_the_readers_read_the_real_buffer_on_the_windows_clock():
    """End to end on the program's own tracer: a clocked loop's
    stretches and a compile span, armed around a slice."""
    import time

    was = telemetry.ACTIVE
    telemetry.disarm()
    telemetry.arm()
    try:
        clock = telemetry.LoopClock(("wait", "build", "sync"), "wait")
        clock.mark("wait")
        t_a = time.monotonic()
        time.sleep(0.02)
        clock.mark("build")
        time.sleep(0.03)
        clock.mark("sync")
        telemetry.emit_span("compile", 0.01, label="x", cache_hit=True)
        time.sleep(0.02)
        clock.mark("wait")
        t_b = time.monotonic()
    finally:
        telemetry.disarm()
        if was:
            telemetry.arm()
    c = ctx(slice={"start": t_a, "end": t_b})
    seconds = loopspans.loop_seconds(c)
    assert sum(seconds.values()) == pytest.approx(t_b - t_a, rel=0.01)
    assert reader("sched.loop_host_share")(c) == pytest.approx(
        100 * seconds["build"] / (t_b - t_a))
    assert reader("sched.loop_wait_share")(c) == pytest.approx(
        100 * seconds["wait"] / (t_b - t_a))
    assert reader("compile.stall_ms_per_s")(c) == pytest.approx(
        10.0 / (t_b - t_a))


# --- the idle gaps' names ---


def recorded():
    with open(os.path.join(HERE, "trace_small.json"),
              encoding="utf-8") as f:
        return json.load(f)


def test_unnamed_share_on_the_small_recorded_trace():
    # 2.34 ms idle of 5; rt:turn (a held span) holds 1.83 ms of it,
    # rt:segment 0.5, and 10 us of turn-around is no gap.
    reduced = tracered.reduce(recorded())
    got = reader("device.idle_unnamed_share")(ctx(trace=reduced))
    assert got == pytest.approx(100 * 1.83 / 2.34)


def test_unnamed_share_falls_when_the_gaps_get_a_lexical_name():
    """The same trace as the program writes it now: the held spans
    (rt:turn, rt:request) are out of the profile, the loop clock's
    stretches are in. A gap goes to the span that covers its middle;
    a stretch the reduction does not know ranks above `none`."""
    trace = recorded()
    trace["host"] = [e for e in trace["host"]
                     if e[0] not in ("rt:turn", "rt:request")]
    trace["host"] += [["rt:loop.accept", 2_100_000, 900_000],
                      ["rt:loop.wait", 4_000_000, 400_000]]
    reduced = tracered.reduce(trace)
    # 2.12-3.0 ms lies in loop.accept; the middle of 4.0-4.95 ms lies
    # past loop.wait's end, under nothing.
    assert dict(map(tuple, reduced["idle_gaps"])) == pytest.approx({
        "rt:segment": 5e-4, "rt:loop.accept": 8.8e-4, "none": 9.5e-4})
    got = reader("device.idle_unnamed_share")(ctx(trace=reduced))
    assert got == pytest.approx(100 * 0.95 / 2.34)


def test_unnamed_share_with_no_trace_returns_nothing():
    assert reader("device.idle_unnamed_share")(ctx(trace={})) is None
    assert reader("device.idle_unnamed_share")(ctx(trace=None)) is None
    busy = {"window_s": 1.0, "busy_s": 1.0, "idle_gaps": [["none", 0.0]]}
    assert reader("device.idle_unnamed_share")(ctx(trace=busy)) is None


# --- the manifest ---


def test_the_five_metrics_are_appended_to_the_manifest_with_readers():
    manifest = mf.load(os.path.join(bench_paths.REPO, "BENCHMARK.json"))
    assert mf.problems(manifest, bench_paths.REPO) == []
    tail = manifest["per_layer"][-5:]
    assert tuple(m["name"] for m in tail) == NEW
    by = {m["name"]: m for m in tail}
    cell = ["mistral-7b-int8.roundtable"]
    assert by["sched.loop_host_share"] == {
        "name": "sched.loop_host_share", "unit": "%", "better": "lower",
        "source": "program_span", "layer": "scheduler",
        "moves": "tokens_per_s", "workloads": cell}
    assert by["sched.admit_host_ms"]["moves"] == "ttft_p50_ms"
    assert by["compile.stall_ms_per_s"]["layer"] == "compile watch"
    assert by["device.idle_unnamed_share"]["source"] == "device_trace"
    for name in NEW:
        assert callable(reader(name))
        assert os.path.isfile(mf.reader_file(manifest, bench_paths.REPO,
                                             name))
