"""One buffer a dispatch, and the key chain on the device (ISSUE 53).

What is held here: the layout helper packs and cuts apart every field
kind at every shape the programs are compiled for; a scheduled
discussion at a fixed seed yields the tokens the tree BEFORE the change
yielded (tests/fixtures/dispatch_pack_tokens.json, recorded from commit
c5d0f0f by `python tests/discussion_play.py --record <file>` run in
that tree: the module uses nothing the two trees do not share), sampled
and greedy, on a plain decoder and on every hybrid family the tests
serve; the keys a program hands back are the host's own split chain, step
for step; such a run costs one host buffer and one launch a program; a
dispatch that fails leaves the key where it was.
"""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "dispatch_pack_tokens.json")
from discussion_play import (MODELS, MODES, ROUNDS, SEED,  # noqa: E402
                             build, play, tokens_of)

# --- the layout helper ------------------------------------------------


def fill(layout, rng):
    from theroundtaible_tpu.engine import dispatch_pack
    values = {}
    for f in layout.fields:
        if f.kind == dispatch_pack.FLOAT:
            v = rng.standard_normal(f.shape).astype(np.float32)
            if v.size:
                v.reshape(-1)[0] = np.float32(-0.0)   # bits, not values
        elif f.kind == dispatch_pack.BOOL:
            v = rng.integers(0, 2, f.shape).astype(bool)
        else:
            v = rng.integers(-2 ** 31, 2 ** 31, f.shape).astype(np.int32)
        values[f.name] = v
    return values


def layouts():
    from theroundtaible_tpu.engine import dispatch_pack
    from theroundtaible_tpu.engine.serving_loop import (
        PREFILL_BUCKETS, RAGGED_BLOCK_Q, ragged_shape_grid)
    cases = []
    for b in (1, 2, 4, 8):
        for kw in ({}, {"rows": True}, {"lora": True}):
            cases.append((f"decode-b{b}-" + "-".join(kw or ["plain"]),
                          dispatch_pack.decode_layout(b, 32, **kw)))
        cases.append((f"sampler-b{b}", dispatch_pack.sampler_layout(b)))
        for kw in ({}, {"hybrid": True}, {"lora": True}):
            cases.append((
                f"prefill-b{b}-" + "-".join(kw or ["plain"]),
                dispatch_pack.prefill_layout(b, PREFILL_BUCKETS[b % 3],
                                             32, **kw)))
    for t in ragged_shape_grid(1536):
        for kw in ({}, {"hybrid": True}, {"lora": True},
                   {"score_width": 5}, {"score_width": 5, "copy_slots": 8,
                                        "lora": True}):
            cases.append((
                f"ragged-t{t}-" + "-".join(kw or ["plain"]),
                dispatch_pack.ragged_layout(t, t // RAGGED_BLOCK_Q, 9, 32,
                                            **kw)))
    return cases


@pytest.mark.parametrize("name,layout", layouts(),
                         ids=[n for n, _l in layouts()])
def test_layout_round_trips_every_field(name, layout):
    """Host values -> one int32 vector -> the same values again, bit for
    bit and under their own dtypes: cut apart on the host, and cut
    apart by a program that takes the vector as its one argument."""
    import jax

    values = fill(layout, np.random.default_rng(len(name)))
    buf = layout.pack(values)
    assert buf.dtype == np.int32 and buf.shape == (layout.size,)
    assert layout.size == sum(v.size for v in values.values())
    host = layout.unpack(buf)
    device = jax.jit(layout.unpack)(buf)
    assert list(host) == [f.name for f in layout.fields]
    assert sorted(device) == sorted(host)   # (jit sorts a dict's keys)
    for f in layout.fields:
        for got in (host[f.name], np.asarray(device[f.name])):
            assert got.dtype == values[f.name].dtype, f.name
            assert got.shape == f.shape, f.name
            assert got.tobytes() == values[f.name].tobytes(), f.name


def test_a_layout_is_its_shapes_and_refuses_what_does_not_fit():
    from theroundtaible_tpu.engine import dispatch_pack
    a = dispatch_pack.decode_layout(4, 32, rows=True)
    assert a is dispatch_pack.decode_layout(4, 32, rows=True)
    assert a == dispatch_pack.Layout(
        [(f.name, f.shape, f.kind) for f in a.fields])
    assert hash(a) == hash(dispatch_pack.Layout(
        [(f.name, f.shape, f.kind) for f in a.fields]))
    assert a != dispatch_pack.decode_layout(4, 32)
    assert "rows" in a and "rows" not in dispatch_pack.decode_layout(4, 32)
    values = fill(a, np.random.default_rng(0))
    with pytest.raises(KeyError):
        a.pack({k: v for k, v in values.items() if k != "rows"})
    with pytest.raises(ValueError, match="rows"):
        a.pack(dict(values, rows=np.zeros((3,), np.int32)))
    with pytest.raises(ValueError, match="buffer"):
        a.unpack(np.zeros((a.size + 1,), np.int32))
    with pytest.raises(ValueError, match="once"):
        dispatch_pack.Layout([("x", (1,), "int32"), ("x", (2,), "int32")])
    with pytest.raises(ValueError, match="kind"):
        dispatch_pack.Layout([("x", (1,), "int8")])


# --- a scheduled discussion -------------------------------------------


KEYED = ("_first_token", "_decode_loop_paged", "_ragged_step",
         "_decode_loop_hybrid", "_ragged_step_hybrid")


def record_keys(eng):
    """Spy on every program that takes the engine's pair of keys: -> a
    list, in order of issue, of (the pair it was given, whether it draws,
    the pair it handed back)."""
    seen = []

    def spy(name, program):
        def call(*args, **kw):
            out = program(*args, **kw)
            given = next(a for a in args[1:] if getattr(
                a, "dtype", None) == np.uint32 and a.shape == (2, 2))
            draws = not (name == "_first_token" and kw["greedy"])
            seen.append((np.asarray(given), draws, np.asarray(out[-1])))
            return out
        return call

    for name in KEYED:
        if getattr(eng, name, None) is not None:
            setattr(eng, name, spy(name, getattr(eng, name)))
    if eng._decode_loop_paged_gather is not None:
        eng._decode_loop_paged_gather = spy(
            "_decode_loop_paged", eng._decode_loop_paged_gather)
    return seen


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE, encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def played():
    """Every (model, mode) played once, with what the run left behind:
    -> {(model, mode): (tokens, keys seen, describe()["dispatch"],
    the key the engine started from, the key it ended on)}."""
    import jax
    runs = {}

    def run(model, mode):
        if (model, mode) not in runs:
            eng, knights, cue = build(model)
            # (from_config warms the ragged program: the chain stands
            # as many links from the seed as programs were issued)
            start = np.asarray(eng._keys)
            keys = jax.random.split(jax.random.PRNGKey(SEED + 1))
            for _ in range(eng.describe()["dispatch"]["programs"]):
                keys = jax.random.split(keys[0])
            assert start.tolist() == np.asarray(keys).tolist()
            keys = record_keys(eng)
            tokens = play(eng, knights, cue, mode)
            runs[model, mode] = (tokens, keys, eng.describe()["dispatch"],
                                 start, np.asarray(eng._keys))
        return runs[model, mode]
    return run


CASES = [pytest.param(model, mode, id=f"{model}-{mode}")
         for model in MODELS for mode in MODES]


@pytest.mark.parametrize("model,mode", CASES)
def test_a_discussion_yields_the_tokens_of_the_tree_before(
        recorded, played, model, mode):
    tokens = played(model, mode)[0]
    assert all(len(a) >= 1 for answers in tokens for a in answers)
    assert tokens == recorded[f"{model}/{mode}"]


@pytest.mark.parametrize("model,mode", CASES)
def test_the_key_a_program_returns_is_the_hosts_split_chain(
        played, model, mode):
    """The host's chain before ISSUE 53 was `key, sub = split(key)` a
    dispatch (`_next_key`), `sub` the program's. The engine holds that
    pair (`_keys` = split(key): row 0 the chain, row 1 the next
    program's); every program that samples is given it and hands back
    `jax.random.split(row 0)`, in the order of issue — so each program
    draws from the `sub` the host's chain gives it, step for step; the
    prologue's sampler leaves a greedy batch's pair alone. The chain is
    not read back to be checked against: the engine ends on its last
    link."""
    import jax
    _tokens, seen, _d, start, end = played(model, mode)
    assert len(seen) >= 2 * ROUNDS
    # the host's own chain, from the key the pair was split from: the
    # engine's first pair is split(PRNGKey(seed + 1)) (checked in
    # `played`), and `key` below runs as `_next_key`'s did
    keys = jax.numpy.asarray(start)
    for given, draws, back in seen:
        assert given.tolist() == np.asarray(keys).tolist()
        if draws:
            key, sub = keys[0], keys[1]     # what _next_key() left / gave
            assert given[1].tolist() == np.asarray(sub).tolist()
            keys = jax.random.split(key)
        assert back.tolist() == np.asarray(keys).tolist()
    assert end.tolist() == np.asarray(keys).tolist()


@pytest.mark.parametrize("model,mode", CASES)
def test_a_program_costs_one_host_buffer_and_one_launch(played, model,
                                                        mode):
    _tokens, keys, dispatch, _s, _e = played(model, mode)
    assert set(dispatch) == {"programs", "host_buffers", "launches"}
    # (the prefill chunks of a prologue take no key: at least as many)
    assert dispatch["programs"] >= len(keys)
    assert dispatch["host_buffers"] / dispatch["programs"] == 1.0
    assert dispatch["launches"] / dispatch["programs"] == 1.0


def test_the_dispatch_spans_carry_what_they_sent_and_issued():
    """Armed, every `dispatch` span that issued a step program carries
    `host_buffers` and `launches` (1 and 1 on the packed paths), the
    registry's series move with describe()["dispatch"], and the surface
    is bound."""
    import time

    from theroundtaible_tpu.utils import telemetry
    eng, knights, cue = build("gemma-ragged")
    sampler, prologues = eng._first_token, []

    def counted(*args, **kw):
        prologues.append(1)      # (issued beside the chunks' dispatches,
        return sampler(*args, **kw)     # under no span of its own)

    eng._first_token = counted
    series = ("roundtable_dispatch_programs_total",
              "roundtable_dispatch_host_buffers_total",
              "roundtable_dispatch_launches_total")
    before = [telemetry.REGISTRY.counter_total(s, engine=eng.cfg.name)
              for s in series]
    telemetry.arm()
    try:
        t_a = time.monotonic()
        play(eng, knights, cue, "sampled", rounds=1)
        spans = [s for s in telemetry.spans_between(t_a, time.monotonic())
                 if s.get("rung") == "dispatch"
                 and s.get("attrs", s).get("op") != "host_sync"]
    finally:
        telemetry.disarm()
    issued = [s.get("attrs", s) for s in spans]
    assert issued and all(
        a["host_buffers"] == 1 and a["launches"] == 1 for a in issued)
    d = eng.describe()["dispatch"]
    assert set(d) == set(telemetry.SURFACE_BINDINGS["engine_dispatch"])
    after = [telemetry.REGISTRY.counter_total(s, engine=eng.cfg.name)
             for s in series]
    assert [a - b for a, b in zip(after, before)] == [
        d["programs"], d["host_buffers"], d["launches"]]
    assert d["programs"] == len(issued) + len(prologues)


def test_an_unpacked_path_shows_in_the_quotients():
    """The multi-chunk prefill's merge of the kept logits is a mask sent
    and a program issued beside the chunks' own: generate_batch over a
    prompt of two chunks reads more than one buffer and one launch a
    program."""
    from theroundtaible_tpu.engine.engine import InferenceEngine
    from theroundtaible_tpu.engine.models.registry import get_model_config
    from theroundtaible_tpu.engine.serving_loop import MAX_PREFILL_CHUNK
    eng = InferenceEngine(
        get_model_config("tiny-gemma", max_seq_len=2 * MAX_PREFILL_CHUNK),
        num_slots=2, kv_layout="paged", seed=SEED)
    eng.generate_batch(
        [("a", [1] + tokens_of(5, MAX_PREFILL_CHUNK + 20))],
        max_new_tokens=2)
    d = eng.describe()["dispatch"]
    # two chunks, the sampler and one decode segment; and the merge
    assert d["programs"] == 4
    assert d["host_buffers"] == d["launches"] == 5


@pytest.mark.parametrize("model", ["gemma", "jamba"])
def test_a_failed_dispatch_leaves_the_key_and_its_retry_draws_the_same(
        model):
    """A dispatch made to fail by `faults` (the dispatch-stage point,
    and a program that raises once it is called) leaves the engine
    holding the key it held; issued again it draws what a twin engine
    that never failed draws."""
    from theroundtaible_tpu.engine import faults
    from theroundtaible_tpu.engine.serving_loop import (
        RaggedSeq, build_ragged_batch, run_dispatch)

    def batch_of(eng):
        name = "__warmup_0"
        eng.kv.ensure_capacity(name, 32, write_from=0, pinned=(name,))
        table = eng.kv.table_for([name])[0]
        b = build_ragged_batch(
            [RaggedSeq([1] + tokens_of(9, 23), 0, table, temperature=1.3)],
            t_budget=eng.ragged_shapes[0], s_max=eng.kv.num_slots + 1,
            pages_per_seq=eng.kv.pages_per_seq,
            scratch_page=eng.kv.scratch_page(0),
            pad_id=eng.tokenizer.pad_id, page_size=eng.kv.page_size)
        b["seq_names"] = [name]
        return b

    twin, _k, _c = build(model)
    want = np.asarray(twin._ragged_dispatch(batch_of(twin)))
    eng, _k, _c = build(model)
    batch, held = batch_of(eng), eng._keys
    try:
        faults.arm("dispatch", count=1)
        with pytest.raises(faults.FaultInjected):
            run_dispatch(lambda: eng._ragged_dispatch(batch), None)
    finally:
        faults.disarm()
    assert eng._keys is held

    name = "_ragged_step_hybrid" if eng.hybrid is not None \
        else "_ragged_step"
    program, errors = getattr(eng, name), [RuntimeError("injected: once")]

    def failing(*args, **kw):
        if errors:
            raise errors.pop()
        return program(*args, **kw)

    setattr(eng, name, failing)
    got = run_dispatch(lambda: eng._ragged_dispatch(batch), eng.retry)
    assert not errors and eng._keys is not held
    assert np.asarray(got).tolist() == want.tolist()
    assert np.asarray(eng._keys).tolist() == np.asarray(
        twin._keys).tolist()
    # (the two that failed issued nothing and count for nothing)
    assert eng.describe()["dispatch"] == twin.describe()["dispatch"]
