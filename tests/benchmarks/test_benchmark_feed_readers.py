"""The readers of the loop clock's feed bit and of the round-start
spans (ISSUE 37): each on a span list made by hand, whose answer can be
checked by hand; with nothing to read — no slice (a CPU run), a program
from before the feed bit (the parent commit), a buffer that overflowed;
and on the program's own tracer. Their manifest entries wait in
layer_metrics/feed_entries.json (BENCHMARK.json has no place for them
yet: PERF.md, Open questions), so the manifest is grown here as
`run.py --manifest` takes it on the chip."""
import importlib.util
import json
import os
import time

import pytest

import bench_paths
from harness import feedspans, loopspans, manifest as mf
from theroundtaible_tpu.utils import telemetry

SLICE = {"start": 10.0, "end": 16.0}
FEED_READERS = ("sched.starved_share", "device.idle_fed_share",
                "sched.page_copy_ms_per_join", "sched.share_ms")
CELLS = ["mistral-7b-int8.roundtable", "nemotron-3-nano-ep2.roundtable",
         "a.x-k1-ep16.roundtable", "laguna-xs.2-d5.roundtable"]


def reader(name):
    path = os.path.join(bench_paths.BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "r_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def loop(phase, t0, end, fed, clock="c1"):
    return {"rung": "loop." + phase, "t0": t0, "dur_s": end - t0,
            "trace_id": clock, "attrs": {"tick": 1, "fed": fed}}


def span(rung, t0, dur, **attrs):
    return {"rung": rung, "t0": t0, "dur_s": dur, "trace_id": "r",
            "attrs": attrs}


# One loop thread, end to end from 9.5 to 16.4 s; the slice is 10-16 s.
# Unfed inside the slice: build 0.5 | wait 0.3 | accept 0.2 | admit 0.1 |
# dispatch 0.2 | retire 0.2 (of 0.6, the rest lies beyond the slice)
# -> 1.5 s, 1.2 s outside `wait`. Fed: sync 0.5 (of 1.0) | accept 1.0 |
# admit_sync 0.3 | admit 0.1 | sync 2.6 -> 4.5 s.
SPANS = [
    loop("sync", 9.5, 10.5, 1), loop("build", 10.5, 11.0, 0),
    loop("wait", 11.0, 11.3, 0), loop("accept", 11.3, 12.3, 1),
    loop("accept", 12.3, 12.5, 0), loop("admit", 12.5, 12.6, 0),
    loop("admit_sync", 12.6, 12.9, 1), loop("admit", 12.9, 13.0, 1),
    loop("dispatch", 13.0, 13.2, 0), loop("sync", 13.2, 15.8, 1),
    loop("retire", 15.8, 16.4, 0),
    span("admit", 9.9, 0.3, sync_s=0.0),        # began before the slice
    span("page_copy", 9.95, 0.05, pages=1, cause="alias"),   # and its copy
    span("admit", 12.5, 0.5, sync_s=0.3),
    span("plan", 12.5, 0.1, prompt_tokens=900, matched_tokens=800,
         pages_allocated=2),
    span("page_copy", 12.52, 0.006, pages=1, cause="alias"),
    span("page_copy", 12.55, 0.004, pages=2, cause="cow"),
    span("admit", 14.0, 0.02, sync_s=0.0),
    span("share", 14.1, 0.020, followers=2, pages_aliased=6, copies=2),
    span("page_copy", 14.105, 0.005, pages=2, cause="share"),
    span("share", 14.3, 0.030, followers=1, pages_aliased=3, copies=1),
    span("share", 14.5, 0.022, followers=1, pages_aliased=3, copies=0),
    span("pack", 14.2, 0.004, kind="ragged", rows=15, tokens=700),
    span("segment", 13.0, 2.8, kind="plain"),
]


@pytest.fixture
def buffered(monkeypatch):
    """The program's span buffer, holding SPANS."""
    monkeypatch.setattr(
        telemetry, "spans_between",
        lambda a, b: [r for r in SPANS if a <= r["t0"] < b])
    monkeypatch.setattr(telemetry, "spans_dropped", lambda: 0)


def ctx(**over):
    # 1.8 of the slice's 6 s idle on the device's own clock
    return dict({"slice": dict(SLICE), "trace": {"idle_share": 0.30}},
                **over)


EXPECTED = {
    "sched.starved_share": 100 * 1.2 / 6.0,
    # idle 30 % less starved 20 % less wait 5 %
    "device.idle_fed_share": 30.0 - 20.0 - 5.0,
    # (6 + 4 + 5) ms over the slice's two joins
    "sched.page_copy_ms_per_join": 7.5,
    "sched.share_ms": 22.0,             # median of 20, 30 and 22
}


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_feed_reader_on_a_hand_made_span_list(buffered, metric):
    assert reader(metric)(ctx()) == pytest.approx(EXPECTED[metric])


def test_the_unfed_and_the_fed_seconds_sum_to_the_slice(buffered):
    starved = feedspans.starved_seconds(ctx())
    assert starved == pytest.approx({
        "build": 0.5, "wait": 0.3, "accept": 0.2, "admit": 0.1,
        "dispatch": 0.2, "retire": 0.2})
    seconds = loopspans.loop_seconds(ctx())
    assert all(starved[p] <= seconds[p] + 1e-9 for p in starved)
    fed = sum(seconds.values()) - sum(starved.values())
    assert (sum(starved.values()), fed) == pytest.approx((1.5, 4.5))
    # starved + wait + idle while fed is the trace's idle share, by
    # construction
    assert (reader("sched.starved_share")(ctx())
            + 100 * seconds["wait"] / 6.0
            + reader("device.idle_fed_share")(ctx())) == pytest.approx(30.0)


def test_several_clocked_loops_are_averaged(monkeypatch, buffered):
    twice = SPANS + [dict(r, trace_id="c2") for r in SPANS
                     if r["rung"].startswith("loop.")]
    monkeypatch.setattr(
        telemetry, "spans_between",
        lambda a, b: [r for r in twice if a <= r["t0"] < b])
    assert reader("sched.starved_share")(ctx()) == pytest.approx(20.0)


def test_a_slice_with_no_copy_reads_zero_and_with_no_share_nothing(
        monkeypatch, buffered):
    quiet = [r for r in SPANS if r["rung"] not in ("page_copy", "share")]
    monkeypatch.setattr(
        telemetry, "spans_between",
        lambda a, b: [r for r in quiet if a <= r["t0"] < b])
    assert reader("sched.page_copy_ms_per_join")(ctx()) == 0.0
    assert reader("sched.share_ms")(ctx()) is None


def test_idle_fed_share_invents_no_device_number_without_a_trace(buffered):
    assert reader("device.idle_fed_share")(ctx(trace={})) is None
    assert reader("device.idle_fed_share")(ctx(trace=None)) is None
    assert reader("sched.starved_share")(ctx(trace={})) == \
        pytest.approx(20.0)


@pytest.mark.parametrize("metric", FEED_READERS)
def test_a_feed_reader_with_no_slice_returns_nothing(buffered, metric):
    assert reader(metric)(ctx(slice=None)) is None


@pytest.mark.parametrize("metric", FEED_READERS)
def test_a_feed_reader_on_the_parents_spans_returns_nothing(
        monkeypatch, metric):
    """What the parent commit writes: `loop.*` records without `fed`,
    `admit` spans, none of the round-start rungs. Its silence is not
    "nothing starved" or "no page copied"."""
    parents = [dict(r, attrs={k: v for k, v in r["attrs"].items()
                              if k != "fed"})
               for r in SPANS
               if r["rung"] not in feedspans.ROUND_START_RUNGS]
    monkeypatch.setattr(
        telemetry, "spans_between",
        lambda a, b: [r for r in parents if a <= r["t0"] < b])
    monkeypatch.setattr(telemetry, "spans_dropped", lambda: 0)
    assert reader(metric)(ctx()) is None


@pytest.mark.parametrize("metric", FEED_READERS)
def test_a_feed_reader_on_a_program_without_the_buffer_returns_nothing(
        monkeypatch, metric):
    monkeypatch.delattr(telemetry, "spans_between")
    assert reader(metric)(ctx()) is None


@pytest.mark.parametrize("metric", FEED_READERS)
def test_a_feed_reader_returns_nothing_from_a_buffer_that_overflowed(
        monkeypatch, buffered, metric):
    monkeypatch.setattr(telemetry, "spans_dropped", lambda: 3)
    assert reader(metric)(ctx()) is None


def test_the_readers_read_the_real_clock_on_the_windows_clock():
    """End to end on the program's own tracer: a clocked loop that
    dispatches, reads and packs around a slice."""
    was = telemetry.ACTIVE
    telemetry.disarm()
    telemetry.arm()
    try:
        clock = telemetry.LoopClock(("wait", "build", "sync"), "wait")
        clock.mark("wait")
        t_a = time.monotonic()
        time.sleep(0.02)                    # wait, unfed
        clock.mark("build")
        time.sleep(0.03)                    # build, unfed
        ticket = clock.feed()
        time.sleep(0.01)                    # build, fed
        clock.mark("sync")
        time.sleep(0.02)                    # sync, fed
        clock.mark("build")                 # (the seam marks back,
        clock.drain(ticket)                 # then the loop drains)
        with telemetry.span("admit"):
            with telemetry.span("share", followers=1):
                time.sleep(0.01)            # build, unfed
        clock.mark("wait")
        t_b = time.monotonic()
    finally:
        telemetry.disarm()
        if was:
            telemetry.arm()
    c = ctx(slice={"start": t_a, "end": t_b})
    starved = feedspans.starved_seconds(c)
    assert set(starved) == {"wait", "build"}
    assert starved["build"] == pytest.approx(0.04, abs=0.01)
    assert reader("sched.starved_share")(c) == pytest.approx(
        100 * starved["build"] / (t_b - t_a))
    assert reader("sched.share_ms")(c) == pytest.approx(10.0, abs=5.0)
    assert reader("sched.page_copy_ms_per_join")(c) == 0.0


def test_the_four_entries_wait_beside_the_readers_and_fit_the_manifest():
    with open(os.path.join(bench_paths.BENCH, "layer_metrics",
                           "feed_entries.json"), encoding="utf-8") as f:
        entries = json.load(f)["per_layer"]
    assert tuple(e["name"] for e in entries) == FEED_READERS
    assert all(e["better"] == "lower" for e in entries)
    # a model with recurrent state declines the leader pass: Nemotron's
    # cell has no `share` span, so that reader is not listed for it
    for e in entries:
        assert e["workloads"] == [
            c for c in CELLS if e["name"] != "sched.share_ms"
            or not c.startswith("nemotron")]
    base = mf.load(os.path.join(bench_paths.REPO, "BENCHMARK.json"))
    grown = json.loads(json.dumps(base))
    grown["per_layer"] += entries
    assert mf.problems(grown, bench_paths.REPO) == []
    moves = {m["name"] for m in base["end_to_end"]}
    layers = {m["layer"] for m in base["per_layer"]}
    for e in entries:
        assert e["moves"] in moves and e["layer"] in layers
        assert os.path.exists(mf.reader_file(grown, bench_paths.REPO,
                                             e["name"]))
        for cell in e["workloads"]:
            assert e["name"] in {
                m["name"] for m in mf.cell(grown, cell)["per_layer"]}
    # BENCHMARK.json itself is as it was: the entries wait
    assert not {m["name"] for m in base["per_layer"]} & set(FEED_READERS)
