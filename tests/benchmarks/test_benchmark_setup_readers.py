"""The readers of the program's set-up table and of the collector's
pauses (ISSUE 54): each on a report made by hand, whose answer can be
checked by hand; with nothing to read — a program from before the table
(the parent commit), a table that never closed; and on the program's own
table. Their manifest entries wait in layer_metrics/setup_entries.json
(BENCHMARK.json has no place for them yet: PERF.md, Open questions), so
the manifest is grown here as `run.py --manifest` takes it on the chip."""
import importlib.util
import json
import os
import time

import pytest

import bench_paths
from harness import manifest as mf
from theroundtaible_tpu.engine import compile_watch
from theroundtaible_tpu.utils import telemetry

SLICE = {"start": 10.0, "end": 16.0}
SETUP_READERS = ("setup.programs", "setup.cache_misses", "setup.lower_s",
                 "setup.compile_s", "setup.unstaged_s")
GC_READER = "host.gc_pause_ms_per_s"
READERS = SETUP_READERS + (GC_READER,)

# A warm start whose cache held all but two of its programs: 70 s from
# the hooks going in to the close, 25.5 s of them in a stage.
REPORT = {
    "closed": True, "closed_by": "m", "wall_s": 70.0,
    "stages": {"trace": 6.0, "lower": 12.0, "retrieve": 3.5,
               "compile": 4.0},
    "phases": {"init": 8.0, "quantize": 2.0, "pools": 0.5,
               "warm_programs": 20.0, "warm_traffic": 39.0},
    # the build's eager operations are programs too: 2.5 s of the
    # stages were heard inside `init` and `quantize`
    "staged": {"init": 1.0, "quantize": 1.5, "pools": 0.0,
               "warm_programs": 14.0, "warm_traffic": 9.0},
    "programs": 45, "cache_hits": 43, "cache_misses": 2, "saved_s": 310.0,
    "misses": {"ragged[t=512]": 2}, "twice": {}, "by_program": [],
    "rows_dropped": 0,
}
EXPECTED = {
    "setup.programs": 45.0,
    "setup.cache_misses": 2.0,
    "setup.lower_s": 18.0,              # trace + lower
    "setup.compile_s": 7.5,             # retrieve + compile
    # 70 less init, quantize and pools' 10.5, less the 23 s of stages
    # heard outside them
    "setup.unstaged_s": 36.5,
    # (4 + 2 + 9) ms of pauses that began in the slice's 6 s
    "host.gc_pause_ms_per_s": 2.5,
}
SPANS = [
    {"rung": "gc", "t0": 9.9, "dur_s": 0.003, "attrs": {"generation": 2}},
    {"rung": "gc", "t0": 10.5, "dur_s": 0.004, "attrs": {"generation": 1}},
    {"rung": "segment", "t0": 11.0, "dur_s": 2.0, "attrs": {}},
    {"rung": "gc", "t0": 12.0, "dur_s": 0.002, "attrs": {"generation": 0}},
    {"rung": "gc", "t0": 15.9, "dur_s": 0.009, "attrs": {"generation": 2}},
    {"rung": "gc", "t0": 16.1, "dur_s": 0.050, "attrs": {"generation": 2}},
]


def reader(name):
    path = os.path.join(bench_paths.BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "r_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@pytest.fixture
def program(monkeypatch):
    """The program, holding REPORT and SPANS."""
    monkeypatch.setattr(compile_watch, "setup_report",
                        lambda: json.loads(json.dumps(REPORT)))
    monkeypatch.setattr(
        telemetry, "spans_between",
        lambda a, b: [r for r in SPANS if a <= r["t0"] < b])
    monkeypatch.setattr(telemetry, "spans_dropped", lambda: 0)


def ctx(**over):
    return dict({"slice": dict(SLICE), "trace": {}}, **over)


@pytest.mark.parametrize("metric", READERS)
def test_reader_on_a_hand_made_report(program, metric):
    assert reader(metric)(ctx()) == pytest.approx(EXPECTED[metric])


@pytest.mark.parametrize("metric", SETUP_READERS)
def test_a_setup_reader_needs_no_slice(program, metric):
    """The table is closed before the window opens: an untraced run, or
    one on the CPU, reads what a traced one reads."""
    assert reader(metric)(ctx(slice=None)) == pytest.approx(
        EXPECTED[metric])


@pytest.mark.parametrize("metric", READERS)
def test_a_reader_on_a_program_without_the_table_returns_nothing(
        monkeypatch, program, metric):
    """The parent commit: no `setup_report`, no `gc_report`, no `gc`
    span. Its silence is not "no program was lowered" or "the collector
    never ran"."""
    monkeypatch.delattr(compile_watch, "setup_report")
    monkeypatch.delattr(compile_watch, "gc_report")
    assert reader(metric)(ctx()) is None


@pytest.mark.parametrize("metric", SETUP_READERS)
def test_a_setup_reader_on_a_table_that_never_closed_returns_nothing(
        monkeypatch, metric):
    monkeypatch.setattr(compile_watch, "setup_report",
                        lambda: dict(REPORT, closed=False, closed_by=None))
    assert reader(metric)(ctx()) is None


@pytest.mark.parametrize("why", ["no slice", "no buffer", "overflowed"])
def test_the_gc_reader_with_nothing_to_read_returns_nothing(
        monkeypatch, program, why):
    c = ctx()
    if why == "no slice":
        c = ctx(slice=None)
    elif why == "no buffer":
        monkeypatch.delattr(telemetry, "spans_between")
    else:
        monkeypatch.setattr(telemetry, "spans_dropped", lambda: 2)
    assert reader(GC_READER)(c) is None


def test_a_slice_with_no_pause_reads_zero(monkeypatch, program):
    monkeypatch.setattr(telemetry, "spans_between", lambda a, b: [
        r for r in SPANS if r["rung"] != "gc" and a <= r["t0"] < b])
    assert reader(GC_READER)(ctx()) == 0.0


def test_the_readers_read_the_programs_own_table(monkeypatch):
    """End to end on compile_watch itself: a table with one program
    heard through the listeners, closed, and a pause while armed."""
    compile_watch.install()
    fresh = compile_watch._Setup()
    fresh.t0 = time.monotonic() - 5.0
    monkeypatch.setattr(compile_watch, "_setup", fresh)
    monkeypatch.setattr(compile_watch, "GC_SPAN_FLOOR_S", 0.0)
    compile_watch.reset_steady_state()
    assert reader("setup.programs")(ctx()) is None      # still open
    now = time.time()
    with compile_watch.label("decode[b=2]", engine="unit-setup"):
        compile_watch._on_time_span(
            "/jax/core/compile/jaxpr_trace_duration", now - 3.0,
            now - 2.5, fun_name="decode_loop")
        compile_watch._on_time_span(
            "/jax/core/compile/jaxpr_to_mlir_module_duration", now - 2.5,
            now - 1.5, fun_name="jit(decode_loop)")
        compile_watch._on_duration(
            "/jax/core/compile/backend_compile_duration", 1.5,
            fun_name="jit(decode_loop)")
    was = telemetry.ACTIVE
    telemetry.disarm()
    telemetry.arm()
    try:
        t_a = time.monotonic()
        import gc
        gc.collect()
        compile_watch.gc_report()
        t_b = time.monotonic()
    finally:
        telemetry.disarm()
        if was:
            telemetry.arm()
    compile_watch.warmup_complete("unit-setup")
    try:
        c = ctx(slice={"start": t_a, "end": t_b})
        assert reader("setup.programs")(c) == 1.0
        assert reader("setup.cache_misses")(c) == 1.0
        assert reader("setup.lower_s")(c) == pytest.approx(1.5)
        assert reader("setup.compile_s")(c) == pytest.approx(1.5)
        # no phase was marked: the wall (5 s and this test's own time,
        # a full collection among it) less the stages' 3 s
        wall = compile_watch.setup_report()["wall_s"]
        assert wall >= 5.0
        assert reader("setup.unstaged_s")(c) == pytest.approx(
            wall - 3.0, abs=1e-3)
        assert reader(GC_READER)(c) > 0.0
    finally:
        compile_watch.reset_steady_state()


def test_the_six_entries_wait_beside_the_readers_and_fit_the_manifest():
    with open(os.path.join(bench_paths.BENCH, "layer_metrics",
                           "setup_entries.json"), encoding="utf-8") as f:
        entries = json.load(f)["per_layer"]
    assert tuple(e["name"] for e in entries) == READERS
    assert all(e["better"] == "lower" and "workloads" not in e
               for e in entries)
    assert {e["moves"] for e in entries[:5]} == {"setup_s"}
    assert {e["layer"] for e in entries[:5]} == {"compile watch"}
    assert (entries[5]["moves"], entries[5]["layer"]) == (
        "ttft_p90_ms", "scheduler")
    base = mf.load(os.path.join(bench_paths.REPO, "BENCHMARK.json"))
    # the first per-layer metrics that move the set-up
    assert not [m for m in base["per_layer"] if m["moves"] == "setup_s"]
    grown = json.loads(json.dumps(base))
    grown["per_layer"] += entries
    assert mf.problems(grown, bench_paths.REPO) == []
    moves = {m["name"] for m in base["end_to_end"]}
    layers = {m["layer"] for m in base["per_layer"]}
    for e in entries:
        assert e["moves"] in moves and e["layer"] in layers
        assert os.path.exists(mf.reader_file(grown, bench_paths.REPO,
                                             e["name"]))
        # no `workloads` list: every cell reports it
        for cell in (w["name"] for w in base["workloads"]):
            assert e["name"] in {
                m["name"] for m in mf.cell(grown, cell)["per_layer"]}
    # BENCHMARK.json itself is as it was: the entries wait
    assert not {m["name"] for m in base["per_layer"]} & set(READERS)
