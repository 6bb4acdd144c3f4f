"""The join buffer's top shape holds a round's leaders (ISSUE 57).

What is held here: the budget rule by `num_slots` and by whether the
model has `layer_kinds` (`state` below), its floor and its two
overrides; the shape grid of a budget and the shape a want picks; on
the tiny engine tests/test_dispatch_pack.py plays its discussions on,
that the warm-up issues exactly the grid's shapes and that what the
join dispatches carried is counted once — on
the `segment` spans, in the registry's two series by shape and on
describe()["ragged_fill"]; and the reader of those spans,
`sched.join_fill_share`, on a span list made by hand, on the parent's
spans, and through its waiting manifest entry.
"""

import copy
import importlib.util
import json
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
for p in (HERE, os.path.join(HERE, "benchmarks")):
    if p not in sys.path:
        sys.path.insert(0, p)

from theroundtaible_tpu.engine.serving_loop import (  # noqa: E402
    RAGGED_BLOCK_Q, RAGGED_TOKENS_ENV, ragged_pick_shape,
    ragged_shape_grid, ragged_token_budget)

# --- the rule ---------------------------------------------------------


@pytest.mark.parametrize("num_slots,state,asked,env,budget", [
    # a plain decoder: 96 tokens a slot over the floor
    (4, False, 0, None, 1024), (8, False, 0, None, 1024),
    (10, False, 0, None, 1024), (11, False, 0, None, 1056),
    (16, False, 0, None, 1536), (32, False, 0, None, 3072),
    # with state: the floor, and every row's block beside a chunk's room
    (4, True, 0, None, 1024), (8, True, 0, None, 1024),
    (16, True, 0, None, 1024), (32, True, 0, None, 1024),
    (128, True, 0, None, 1088),
    # the key and the environment, rounded up to a block, whatever the
    # engine is; the environment over the key
    (16, True, 1536, None, 1536), (16, False, 1024, None, 1024),
    (16, False, 1001, None, 1008), (4, True, 100, None, 104),
    (16, False, 0, "2048", 2048), (16, True, 1536, "700", 704),
    (16, False, 0, "0", 1536), (16, True, 1536, "", 1536),
])
def test_the_budget_is_a_rule_of_slots_and_state(monkeypatch, num_slots,
                                                 state, asked, env,
                                                 budget):
    monkeypatch.delenv(RAGGED_TOKENS_ENV, raising=False)
    if env is not None:
        monkeypatch.setenv(RAGGED_TOKENS_ENV, env)
    got = ragged_token_budget(num_slots, asked, hybrid=state)
    assert got == budget
    assert got % RAGGED_BLOCK_Q == 0
    # every resident row's decode block leaves a chunk its room
    assert asked or env or got >= RAGGED_BLOCK_Q * (num_slots + 1)


@pytest.mark.parametrize("budget,grid", [
    (1536, (64, 256, 1024, 1536)), (1024, (64, 256, 1024)),
    (3072, (64, 256, 1024, 3072)), (1056, (64, 256, 1024, 1056)),
    (704, (64, 256, 704)), (256, (64, 256)), (104, (64, 104)),
    (64, (64,)), (40, (40,)),
])
def test_a_grid_is_sorted_deduplicated_and_capped(budget, grid):
    assert ragged_shape_grid(budget) == grid
    assert all(s <= budget for s in grid)
    assert grid[-1] == budget and list(grid) == sorted(set(grid))


GRIDS = [(64, 256, 1024), (64, 256, 1024, 1536)]


@pytest.mark.parametrize("grid,want,shape", [
    (grid, want, shape) for grid in GRIDS
    for shape in grid for want in (shape - 7, shape)
] + [
    (grid, lo + 1, hi) for grid in GRIDS
    for lo, hi in zip(grid, grid[1:])
] + [(grid, 1, 64) for grid in GRIDS]
  + [(grid, grid[-1] + 8, grid[-1]) for grid in GRIDS])
def test_a_want_picks_the_smallest_shape_that_holds_it(grid, want, shape):
    assert ragged_pick_shape(grid, want) == shape
    assert ragged_pick_shape(grid, want, carry=120) == shape or \
        want > grid[-1]


@pytest.mark.parametrize("grid,want,carry,shape", [
    # the remainder and the rows' blocks fit the 256 shape: 1536 + 256
    # computes less than 1024 + 1024
    (GRIDS[1], 1608, 120, 1536), (GRIDS[1], 1672, 120, 1536),
    # ... they do not: 1024 + 1024 computes less than 1536 + 1024
    (GRIDS[1], 1680, 120, 1024), (GRIDS[1], 1944, 120, 1024),
    (GRIDS[1], 2128, 120, 1024),
    # a tie (2560 both ways): the smaller, first tokens sooner
    (GRIDS[1], 2600, 0, 1024),
    # a remainder past the top shape as well (it counts as one top
    # shape): three dispatches either way, the smaller first
    (GRIDS[1], 3000, 120, 1024), (GRIDS[1], 6000, 120, 1024),
    # one shape from the floor up, and a grid under the floor: the last
    (GRIDS[0], 1304, 120, 1024), (GRIDS[0], 5000, 0, 1024),
    ((64, 256, 704), 900, 64, 704),
])
def test_a_want_past_the_top_shape_picks_the_cheapest_pair(grid, want,
                                                           carry, shape):
    assert ragged_pick_shape(grid, want, carry=carry) == shape


# --- the tiny engine --------------------------------------------------


def test_the_warm_up_issues_the_grid_and_the_joins_are_counted_once():
    from discussion_play import KNIGHTS, SEED, tokens_of
    from theroundtaible_tpu.engine.engine import InferenceEngine
    from theroundtaible_tpu.engine.models.registry import get_model_config
    from theroundtaible_tpu.engine.scheduler import SessionScheduler
    from theroundtaible_tpu.utils import telemetry
    # (one device: the ragged kernel serves, so a join is deferred)
    eng = InferenceEngine(
        get_model_config("tiny-gemma", max_seq_len=512), num_slots=8,
        kv_layout="paged", seed=SEED, mesh_shape={"data": 1, "model": 1})
    eng.joins_ragged_alone, eng.ragged_defer_min = True, 0
    assert eng.ragged_path == "pallas_ragged"
    assert eng.ragged_tokens == 1024                # 8 slots: the floor
    assert eng.ragged_shapes == ragged_shape_grid(eng.ragged_tokens)
    dispatch, warmed = eng._ragged_dispatch, []

    def spy(batch):
        warmed.append(len(batch["tokens"]))
        return dispatch(batch)

    eng._ragged_dispatch = spy
    eng._warm_ragged()
    assert sorted(set(warmed)) == list(eng.ragged_shapes)
    # ... and of a plain decoder's grid `from_config` warms the join
    # program alone, in the engine's own mode: each shape twice
    whole = len(warmed)
    del warmed[:]
    eng._warm_ragged(whole=False)
    eng._ragged_dispatch = dispatch
    assert warmed == [s for s in eng.ragged_shapes for _ in range(2)]
    assert whole >= len(warmed)

    series = ("roundtable_ragged_buffer_tokens_total",
              "roundtable_ragged_real_tokens_total")

    def totals(**labels):
        return [telemetry.REGISTRY.counter_total(
            s, engine=eng.cfg.name, **labels) for s in series]

    shapes = [str(s) for s in eng.ragged_shapes]
    before = {s: totals(shape=s) for s in shapes}
    telemetry.arm()
    try:
        t_a = time.monotonic()
        sched = SessionScheduler(eng)
        flush, firsts, streamed = sched._flush_streams, [], []

        def flushed():
            firsts.append(flush())
            return firsts[-1]

        sched._flush_streams = flushed
        try:
            transcript = [1] + tokens_of(81, 60)
            sched.wait(sched.submit_async(
                "s", [(k, transcript + [10 + i, 21, 30])
                      for i, k in enumerate(KNIGHTS)],
                max_new_tokens=3, on_commit=streamed.append))
            described = sched.describe()
        finally:
            sched.close()
        t_b = time.monotonic()
        spans = telemetry.spans_between(t_a, t_b)
        # (the reader reads those very spans over a slice)
        read = reader()({"slice": {"start": t_a, "end": t_b}})
    finally:
        telemetry.disarm()
    # the one flush that carried the rows' first tokens says so (the
    # loop then yields to the streams' thread: scheduler._STREAM_YIELD_S)
    assert firsts.count(True) == 1 and firsts[0] is True
    assert [e["row"] for e in streamed if e["type"] == "tokens"][:3] == \
        [0, 1, 2]
    joins = [s["attrs"] for s in spans if s["rung"] == "segment"
             and s["attrs"]["kind"] == "ragged"]
    assert joins
    for a in joins:
        assert a["label"] == f"ragged[t={a['shape']}]"
        assert a["shape"] in eng.ragged_shapes
        assert a["real_tokens"] == a["prefill_tokens"] + a["decode_tokens"]
        assert a["real_tokens"] <= a["want"]
        assert a["shape"] == ragged_pick_shape(
            eng.ragged_shapes, min(a["want"], eng.ragged_tokens))
    fill = described["ragged_fill"]
    assert set(fill) <= set(shapes)
    for s in shapes:
        mine = [a for a in joins if str(a["shape"]) == s]
        moved = [a - b for a, b in zip(totals(shape=s), before[s])]
        assert moved == [sum(a["shape"] for a in mine),
                         sum(a["real_tokens"] for a in mine)]
        assert fill.get(s, {"dispatches": 0, "buffer_tokens": 0,
                            "real_tokens": 0}) == {
            "dispatches": len(mine), "buffer_tokens": moved[0],
            "real_tokens": moved[1]}
    assert sum(v["dispatches"] for v in fill.values()) == \
        described["ragged_segments"]
    assert "ragged_fill" in telemetry.SURFACE_BINDINGS["scheduler_describe"]
    assert read == pytest.approx(
        100.0 * sum(a["real_tokens"] for a in joins)
        / sum(a["shape"] for a in joins))


# --- the reader -------------------------------------------------------

NAME = "sched.join_fill_share"
BENCH = os.path.join(os.path.dirname(HERE), "benchmarks")


def reader():
    spec = importlib.util.spec_from_file_location(
        "r_join_fill", os.path.join(BENCH, "layer_metrics", NAME + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def seg(t0, kind, **attrs):
    return {"rung": "segment", "t0": t0, "dur_s": 0.04, "trace_id": "r",
            "attrs": dict(attrs, kind=kind)}


# A round in two dispatches and a lone tail; a verify and a plain
# segment that are not joins; a join before the slice.
SPANS = [
    seg(9.9, "ragged", shape=1024, real_tokens=1000, want=1000),
    seg(10.1, "ragged", shape=1536, real_tokens=1290, want=1296),
    seg(10.2, "ragged", shape=256, real_tokens=230, want=240),
    seg(10.3, "spec", shape=256, real_tokens=9),
    seg(10.4, "plain"),
    seg(11.0, "ragged", shape=64, real_tokens=30, want=32),
    {"rung": "pack", "t0": 10.05, "dur_s": 0.002, "trace_id": "r",
     "attrs": {"kind": "ragged", "rows": 9, "tokens": 1290}},
]


@pytest.fixture
def buffered(monkeypatch):
    from theroundtaible_tpu.utils import telemetry
    held = {"spans": SPANS}
    monkeypatch.setattr(
        telemetry, "spans_between",
        lambda a, b: [r for r in held["spans"] if a <= r["t0"] < b])
    monkeypatch.setattr(telemetry, "spans_dropped", lambda: 0)
    return held


def test_the_reader_on_a_hand_made_span_list(buffered):
    ctx = {"slice": {"start": 10.0, "end": 16.0}}
    assert reader()(ctx) == pytest.approx(
        100.0 * (1290 + 230 + 30) / (1536 + 256 + 64))


@pytest.mark.parametrize("case", ["parent", "no-join", "overflowed",
                                  "no-buffer"])
def test_the_reader_with_nothing_to_read_returns_nothing(
        monkeypatch, buffered, case):
    """The parent's spans carry neither attribute; a slice may hold no
    join; a buffer that overflowed and a program without one give no
    spans. None of them is a fill of 0."""
    from theroundtaible_tpu.utils import telemetry
    if case == "parent":
        buffered["spans"] = [
            dict(r, attrs={k: v for k, v in r["attrs"].items()
                           if k not in ("shape", "real_tokens", "want")})
            for r in SPANS]
    elif case == "no-join":
        buffered["spans"] = [r for r in SPANS
                             if r["attrs"]["kind"] != "ragged"]
    elif case == "overflowed":
        monkeypatch.setattr(telemetry, "spans_dropped", lambda: 2)
    else:
        monkeypatch.delattr(telemetry, "spans_between")
    assert reader()({"slice": {"start": 10.0, "end": 16.0}}) is None


def test_the_waiting_entry_fits_the_manifest_and_names_every_cell():
    import bench_paths
    from harness import manifest as mf
    base = mf.load(os.path.join(bench_paths.REPO, "BENCHMARK.json"))
    with open(os.path.join(BENCH, "layer_metrics", "join_entries.json"),
              encoding="utf-8") as f:
        entries = json.load(f)["per_layer"]
    assert [e["name"] for e in entries] == [NAME]
    grown = copy.deepcopy(base)
    grown["per_layer"].extend(entries)
    assert mf.problems(grown, bench_paths.REPO) == []
    assert entries[0]["workloads"] == [w["name"] for w in base["workloads"]]
    assert entries[0]["moves"] == "ttft_p90_ms"
    assert os.path.isfile(mf.reader_file(grown, bench_paths.REPO, NAME))
