"""A layer's body is a function `jax.jit` has seen (ISSUE 55;
models/common.layer_body, engine/paged_forward.py): for one tiny preset
of each family — dense, hybrid with Mamba-2 and experts, latent, window
and full attention, retention, short convolution — the three step
programs' forwards (a prefill chunk, a decode step, a ragged dispatch)

- give the logits they gave with every layer traced where it stands
  (`__wrapped__`, the parent's program), bit for bit;
- trace a body once a signature and reuse it for every further layer of
  that signature, as the compile watch's set-up table counts it
  (`bodies_traced`, `bodies_reused` of the program's row);
- hold one `jit` equation a layer in their jaxpr, and still donate their
  pools and states.

And two layers that differ in a static field are two traces. Host-only
and small: float32 weights made from a seed, the kernels interpreted, no
engine built.
"""
import dataclasses
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from theroundtaible_tpu.engine import compile_watch as cw  # noqa: E402
from theroundtaible_tpu.engine import paged_forward as pf  # noqa: E402
from theroundtaible_tpu.engine.models import common, hybrid  # noqa: E402
from theroundtaible_tpu.engine.models.registry import (  # noqa: E402
    get_model_config)
from theroundtaible_tpu.engine.serving_loop import (  # noqa: E402
    RaggedSeq, build_ragged_batch)

FAMILIES = {"dense": "tiny-mistral", "hybrid": "tiny-nemotron-h",
            "latent": "tiny-axk1", "window_full": "tiny-mellum",
            "retention": "tiny-brumby", "shortconv": "tiny-lfm2"}
PROGRAMS = ("prefill", "decode", "ragged")
BODIES = ("_paged_block", "_ragged_block", "_paged_hybrid_layer",
          "_ragged_hybrid_layer")
PAGE, PAGES, PER_SEQ = 16, 24, 4
SLOTS, SNAPS = 3, 4


def _model(family, dtype=jnp.float32):
    cfg = get_model_config(FAMILIES[family])
    return cfg, common.init_params(cfg, jax.random.PRNGKey(7), dtype)


def _pools(cfg, dtype=jnp.float32):
    shape = ((PAGES, PAGE, cfg.page_width) if cfg.latent
             else (PAGES, PAGE, cfg.page_heads, cfg.page_width))
    return [tuple(jnp.zeros(shape, dtype)
                  for _ in range(1 if cfg.latent else 2))
            for _ in cfg.attention_layers]


def _tables(rows):
    return jnp.arange(rows * PER_SEQ, dtype=jnp.int32).reshape(rows, PER_SEQ)


def _program(family, program, rows=2, chunk=16, dtype=jnp.float32):
    """(fn(params, pools, state) -> (logits, pools, state), the three
    arguments, how many layers go through a body). `rows` and `chunk`
    size the dispatch, so a case can ask for shapes no other has traced."""
    cfg, params = _model(family, dtype)
    pools = _pools(cfg, dtype)
    layers = sum(n for kinds, n in cfg.layer_runs
                 if kinds[0] != hybrid.MAMBA1) \
        if cfg.layer_kinds else cfg.num_layers
    tokens = jax.random.randint(jax.random.PRNGKey(1), (rows, chunk), 3,
                                cfg.vocab_size)
    if program != "ragged":
        t = chunk if program == "prefill" else 1
        start = 0 if program == "prefill" else chunk
        positions = start + jnp.broadcast_to(jnp.arange(t)[None], (rows, t))
        valid = jnp.full((rows,), start + t, jnp.int32)
        table = _tables(rows)
        if cfg.layer_kinds is None:
            def fn(params, pools, state):
                logits, pools = pf.forward_paged(
                    params, cfg, tokens[:, :t], positions, pools, table,
                    valid)
                return logits, pools, state
            return fn, (params, pools, None), layers
        # (row parts in batch-row order; a slot part whole, its last row
        # the scratch row)
        state = hybrid.zero_state(
            cfg, rows + 1 if cfg.retention_layers else rows, dtype)
        kw = ({"lengths": valid} if program == "prefill"
              else {"active": jnp.ones((rows,), bool)})

        def fn(params, pools, state):
            logits, pools, state, _cap, _counts = pf.forward_paged_hybrid(
                params, cfg, tokens[:, :t], positions, pools, table, valid,
                state, page_size=PAGE, rows=jnp.arange(rows), **kw)
            return logits, pools, state
        return fn, (params, pools, state), layers
    # A ragged dispatch: one sequence joins with a chunk, one decodes.
    table = np.asarray(_tables(rows))
    seqs = [RaggedSeq([int(x) for x in tokens[0]], 0, table[0]),
            RaggedSeq([5], chunk, table[1])]
    b = build_ragged_batch(seqs, t_budget=2 * chunk, s_max=SLOTS,
                           pages_per_seq=PER_SEQ, scratch_page=PAGES - 1,
                           pad_id=0, page_size=PAGE)
    flat = [jnp.asarray(b[k]) for k in ("tokens", "positions")]
    walk = [jnp.asarray(b[k]) for k in (
        "tables", "seq_of_block", "block_qstart", "query_offsets",
        "kv_valid", "token_pages", "token_offs", "token_seq", "last_rows")]
    if cfg.layer_kinds is None:
        def fn(params, pools, state):
            logits, pools = pf.forward_ragged(params, cfg, *flat, pools,
                                              *walk)
            return logits, pools, state
        return fn, (params, pools, None), layers
    state = hybrid.zero_state(cfg, SLOTS + 1, dtype)
    snaps = {p: v for p, v in
             hybrid.zero_state(cfg, SNAPS, dtype).items()
             if p in hybrid.SLOT_PARTS}
    seq_slot = jnp.asarray([0, 1] + [SLOTS] * (SLOTS - 2), jnp.int32)
    zero = jnp.zeros((SLOTS,), jnp.int32)

    def fn(params, pools, state):
        logits, pools, state, _cap, _counts = pf.forward_ragged_hybrid(
            params, cfg, *flat, pools, *walk, state, seq_slot, zero,
            page_size=PAGE, snaps=snaps, snap_idx=zero)
        return logits, pools, state
    return fn, (params, pools, state), layers


def _inline(monkeypatch):
    """Every layer traced where it stands: the parent's programs."""
    for name in BODIES:
        monkeypatch.setattr(pf, name, getattr(pf, name).__wrapped__)
    monkeypatch.setattr(common, "_cached_block",
                        common._cached_block.__wrapped__)


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("family", list(FAMILIES))
def test_logits_equal_the_layers_traced_where_they_stand(
        family, program, monkeypatch):
    fn, args, _layers = _program(family, program)
    got = jax.jit(fn)(*args)
    _inline(monkeypatch)
    want = jax.jit(fn)(*args)
    assert np.isfinite(np.asarray(got[0])).all()
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("family", list(FAMILIES))
def test_bfloat16_logits_round_no_further_than_the_layers_in_place(
        family, monkeypatch):
    """The serving dtype, a ragged dispatch (a join and a decode row): a
    compiler rounds a bfloat16 sum where it fused it, and a program of
    bodies is fused otherwise than one whose layers stand in place, so
    the two differ — by no more than either differs from the same
    weights computed in float32, and with the same pick wherever the
    pick is not a tie of that size."""
    fn, args, _layers = _program(family, "ragged", dtype=jnp.bfloat16)
    have = np.asarray(jax.jit(fn)(*args)[0], np.float32)
    _inline(monkeypatch)
    want = np.asarray(jax.jit(fn)(*args)[0], np.float32)
    exact = np.asarray(jax.jit(fn)(*jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), args))[0])
    assert np.isfinite(have).all()
    rounding = np.abs(want - exact).max()
    assert 0 < rounding < 0.05 * np.abs(exact).max() + 0.05
    assert np.abs(have - exact).max() <= 2 * rounding
    assert np.abs(have - want).max() <= 2 * rounding
    best = np.sort(exact, axis=-1)
    clear = best[..., -1] - best[..., -2] > 2 * rounding
    assert (have.argmax(-1) == exact.argmax(-1))[clear].all()


@pytest.fixture
def table(monkeypatch):
    """A fresh, open set-up table and a quiet thread."""
    cw.install()
    fresh = cw._Setup()
    fresh.t0 = time.monotonic()
    monkeypatch.setattr(cw, "_setup", fresh)
    cw._tls.row, cw._tls.depth, cw._tls.bodies = None, 0, (0, 0)
    return fresh


def _signatures(cfg):
    """The distinct (kind, what is static about it) among the layers
    that go through a body."""
    if cfg.layer_kinds is None:
        return 1
    seen, ai = set(), 0
    for kinds, _n in cfg.layer_runs:
        kind = kinds[0]
        if kind == hybrid.MAMBA1:
            continue
        if kind == hybrid.ATTENTION:
            seen.add((kind, cfg.attention_layer(ai)))
            ai += 1
        else:
            seen.add((kind, cfg))
    return len(seen)


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_program_traces_a_body_once_a_signature(family, program, table):
    """Read from the set-up table, as a run's `warmup` line has it: the
    row of the lowered program and the table's totals. (Shapes of this
    case's own: JAX's trace cache is the process's.)"""
    fn, args, layers = _program(family, program, rows=3, chunk=24)
    cfg, _params = _model(family)
    with cw.label(f"{program}[{family}]", engine="bodies"):
        jax.jit(fn).lower(*args)
    report = cw.setup_report()
    row = [r for r in report["by_program"]
           if r["label"] == f"{program}[{family}]"][-1]
    want = _signatures(cfg)
    assert (row["bodies_traced"], row["bodies_reused"]) \
        == (want, layers - want)
    assert layers - want > 0          # two layers of one signature
    assert (report["bodies_traced"], report["bodies_reused"]) \
        == (want, layers - want)
    summary = cw.summary()
    assert summary["bodies_traced"] >= want
    assert summary["bodies_reused"] >= layers - want
    # ... and a second program of the same shapes traces none of them.
    with cw.label(f"{program}[{family}] again", engine="bodies"):
        jax.jit(lambda *a: fn(*a)).lower(*args)
    again = [r for r in cw.setup_report()["by_program"]
             if r["label"] == f"{program}[{family}] again"][-1]
    assert (again["bodies_traced"], again["bodies_reused"]) == (0, layers)


@pytest.mark.parametrize("rows,field,value", [
    (5, "sliding_window", 8), (7, "num_heads", 4), (9, "rope_theta", 1e4)])
def test_two_layers_that_differ_in_a_static_field_are_two_traces(
        rows, field, value, table):
    """Mellum's window layers and its full one are two signatures; make
    every attention layer the same and they are one; change one static
    field of ONE layer and that layer is a trace of its own — read from
    the arguments, no model's name asked. (Each program at a batch of
    its own: a body traced for one program serves the next of the same
    shapes.)"""
    cfg = get_model_config("tiny-mellum")
    views = cfg.attention_views
    assert len(set(views)) == 2 < len(views)
    same = dataclasses.replace(
        cfg, attn_layers=(cfg.attn_layers[0],) * len(cfg.attn_layers))
    odd = dataclasses.replace(same, attn_layers=(
        dataclasses.replace(same.attn_layers[0], **{field: value}),
    ) + same.attn_layers[1:])
    traced = {}
    for name, c, b in (("same", same, rows), ("odd", odd, rows + 1)):
        params = jax.eval_shape(
            lambda k, c=c: common.init_params(c, k, jnp.float32),
            jax.random.PRNGKey(0))
        state = jax.eval_shape(
            lambda c=c, b=b: hybrid.zero_state(c, b, jnp.float32))
        pools = jax.eval_shape(lambda c=c: _pools(c))
        i32 = jax.ShapeDtypeStruct((b, 1), jnp.int32)

        def step(params, pools, state, tokens, positions, c=c, b=b):
            return pf.forward_paged_hybrid(
                params, c, tokens, positions, pools, _tables(b),
                jnp.full((b,), 9), state, active=jnp.ones((b,), bool),
                page_size=PAGE, rows=jnp.arange(b))

        with cw.label(name, engine="bodies"):
            jax.jit(step).lower(params, pools, state, i32, i32)
        traced[name] = [r for r in cw.setup_report()["by_program"]
                        if r["label"] == name][-1]["bodies_traced"]
    assert traced["same"] == 2          # the attention layers, the experts
    assert traced["odd"] == 3


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_decode_jaxpr_holds_one_jit_a_layer_and_donates_its_pools(
        family):
    fn, args, layers = _program(family, "decode")
    names = {"_paged_block", "_paged_hybrid_layer"}
    jaxpr = jax.make_jaxpr(fn)(*args)
    calls = [e for e in jaxpr.eqns if e.primitive.name == "jit"
             and e.params["name"] in names]
    assert len(calls) == layers
    # one jaxpr a signature: the later layers' equations hold the first's
    cfg, _params = _model(family)
    assert len({id(e.params["jaxpr"]) for e in calls}) == _signatures(cfg)
    # the pools and the states go in donated and come out in place
    donated = jax.tree_util.tree_leaves(args[1:])
    text = jax.jit(fn, donate_argnums=(1, 2)).lower(*args).as_text()
    head = text[text.index("func.func public @main"):]
    head = head[:head.index("{\n")]
    assert head.count("tf.aliasing_output") == len(donated)


def test_a_switch_flipped_between_two_calls_is_another_trace(monkeypatch):
    """ROUNDTABLE_INT4_MM flipped between two calls of the same shapes,
    mesh and sink: the body is traced again and its products take the
    other path — a lever read at trace time is part of a body's key
    (`common._switches`), so an A/B never compares a path with itself."""
    from theroundtaible_tpu.engine.quant import quantize_params
    cfg = common.ModelConfig(
        name="switch-test", vocab_size=512, num_layers=2, embed_dim=256,
        num_heads=4, num_kv_heads=2, head_dim=128, mlp_dim=512,
        max_seq_len=64, tie_embeddings=True)
    qp = quantize_params(
        common.init_params(cfg, jax.random.PRNGKey(0), jnp.float32), cfg,
        act_dtype=jnp.float32, bits=4)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (3, 8), 0, 512)
    positions = jnp.broadcast_to(jnp.arange(8)[None], (3, 8))
    valid = jnp.full((3,), 8, jnp.int32)
    mesh1 = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("one",))
    sink: dict = {}
    traced = []
    block = common.transformer_block
    monkeypatch.setattr(common, "transformer_block",
                        lambda *a, **k: traced.append(1) or block(*a, **k))

    def paths():
        with common.spmd_mesh(mesh1, sink):
            logits, _ = common.forward(qp, cfg, tokens, positions, None,
                                       None, valid)
        got = {e["path"] for e in sink.values()}
        sink.clear()
        return np.asarray(logits), got

    monkeypatch.setenv("ROUNDTABLE_INT4_MM", "1")
    on, on_paths = paths()
    assert on_paths == {common.PATH_KERNEL} and len(traced) == 1
    monkeypatch.setenv("ROUNDTABLE_INT4_MM", "0")
    off, off_paths = paths()
    assert off_paths == {common.PATH_XLA} and len(traced) == 2
    np.testing.assert_allclose(on, off, rtol=1e-4, atol=1e-4)
    assert not np.array_equal(on, off)     # two paths, two roundings
    # ... and back: the first trace is found again.
    monkeypatch.setenv("ROUNDTABLE_INT4_MM", "1")
    again, _head = paths()
    assert len(traced) == 2
    np.testing.assert_array_equal(again, on)


@pytest.mark.parametrize("switch", [
    "ROUNDTABLE_INT4_MM", "ROUNDTABLE_LORA_MM", "attention", "grouped",
    "int4mm", "lora", "retention"])
def test_every_trace_time_switch_is_in_a_bodys_key(switch, monkeypatch):
    """The two levers of the environment and each kernel module's
    `_interpret` (a compile test patches it): a change of any is another
    static key, read when the body is called."""
    import importlib
    before = common._announced()[0]
    assert before == common._announced()[0]
    assert hash(before) == hash(common._announced()[0])
    if switch.isupper():
        monkeypatch.setenv(switch, "1")      # (off the chip: default off)
    else:
        mod = importlib.import_module(
            f"theroundtaible_tpu.engine.pallas.{switch}")
        now = mod._interpret()
        monkeypatch.setattr(mod, "_interpret", lambda: not now)
    assert common._announced()[0] != before
