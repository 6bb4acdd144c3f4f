"""The Mamba-1 mixer (models/mamba1.py) and its scan kernel
(pallas/mamba1.py) on the CPU, a layer at a time: the step, [B, T] and
flat-buffer forms against the token-at-a-time recurrence of the plain
reference (benchmarks/configs/jamba_reference.py: `mamba_layer`) on
seeded weights — chunk and page boundaries, the conv tail across a
boundary, a run restarting from its slot's row, dt = 0 the identity, a
capture at a token — and the Pallas kernel in interpret mode against the
`jax.numpy` scan it replaces on the chip. TOL: float32 sums of the same
products in another order move a mixer's output by 1e-6 here; 2e-5 is an
order above that and two below what the bfloat16-state control of
tests/test_jamba_serving.py moves it by."""
import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
from configs import jamba_reference as ref  # noqa: E402

from theroundtaible_tpu.engine import fleet  # noqa: E402
from theroundtaible_tpu.engine.models import hybrid, mamba1  # noqa: E402
from theroundtaible_tpu.engine.models.common import init_params  # noqa: E402
from theroundtaible_tpu.engine.models.registry import (  # noqa: E402
    get_model_config, list_models)
from theroundtaible_tpu.engine.pallas import mamba1 as kernel  # noqa: E402

TOL = 2e-5
CFG = get_model_config("tiny-jamba")
N, G, W, K1 = mamba1.dims(CFG)
SIZES = (CFG.mamba1_dim, N, CFG.conv_kernel, CFG.dt_rank)
ROWS = 4                       # state rows: three slots and the scratch


@pytest.fixture(scope="module")
def layer():
    """Layer 2 of the first run, its small norms and D off one so that
    leaving one out would show."""
    params = init_params(CFG, jax.random.PRNGKey(5), jnp.float32)
    one = jax.tree_util.tree_map(lambda a: a[2],
                                 params["layers"][0][hybrid.MAMBA1])
    key = jax.random.PRNGKey(6)
    for i, name in enumerate(("dt_norm", "b_norm", "c_norm", "D", "norm")):
        one[name] = one[name] * (1.0 + 0.3 * jax.random.normal(
            jax.random.fold_in(key, i), one[name].shape))
    one["conv_b"] = 0.1 * jax.random.normal(key, one["conv_b"].shape)
    return one


def stream(seed, t):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (t, CFG.embed_dim), jnp.float32)


def want(layer, x):
    """The reference's mixer output for the whole of x [T, E]."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.mamba_layer(
            layer, x, eps=CFG.norm_eps, norms=True, read=ref.as_float32,
            sizes=SIZES) - x)


def normed(layer, x):
    return hybrid.layer_norm_in(x, layer, CFG)


def zero():
    return (jnp.zeros((ROWS, 1, N, G, W)), jnp.zeros((ROWS, 1, K1, G, W)))


def prefill(layer, x, ssm, conv, row, take=None, **kw):
    """x [T, E] through `mamba1_prefill` as one row from state row `row`
    (the first `take` tokens valid)."""
    t = x.shape[0]
    with jax.default_matmul_precision("highest"):
        return mamba1.mamba1_prefill(
            normed(layer, x)[None], layer, CFG, ssm, conv, 0,
            jnp.asarray([row]), jnp.asarray([t if take is None else take]),
            **kw)


def test_the_whole_row_is_the_reference(layer):
    x = stream(1, 45)                       # 45: not a multiple of a block
    out, ssm, conv, _ = prefill(layer, x, *zero(), 1)
    assert np.abs(np.asarray(out[0]) - want(layer, x)).max() < TOL
    assert float(jnp.abs(ssm[0]).max()) == 0.0        # row 0 untouched
    assert float(jnp.abs(ssm[1]).max()) > 0.0


@pytest.mark.parametrize("cut", [1, 3, 16, 29])
def test_two_chunks_carry_the_state_and_the_conv_tail(layer, cut):
    """A chunk's end anywhere: under the conv's reach (1, 3), on a page
    boundary (16), inside a block (29)."""
    x = stream(2, 40)
    a, ssm, conv, _ = prefill(layer, x[:cut], *zero(), 2)
    b, _s, _c, _ = prefill(layer, x[cut:], ssm, conv, 2)
    got = np.concatenate([np.asarray(a[0]), np.asarray(b[0])])
    assert np.abs(got - want(layer, x)).max() < TOL


def test_steps_a_token_at_a_time_are_the_reference(layer):
    x = stream(3, 24)
    ssm, conv = zero()
    rows = jnp.asarray([2, 0])              # batch order is not slot order
    got = []
    with jax.default_matmul_precision("highest"):
        for t in range(24):
            h = normed(layer, jnp.stack([x[t], x[t] * 0.5]))[:, None]
            out, ssm, conv = mamba1.mamba1_step(
                h, layer, CFG, ssm, conv, 0, rows,
                jnp.asarray([True, False]))
            got.append(np.asarray(out[0, 0]))
    assert np.abs(np.stack(got) - want(layer, x)).max() < TOL
    assert float(jnp.abs(ssm[0]).max()) == 0.0    # the inactive row's
    assert float(jnp.abs(conv[0]).max()) == 0.0
    assert float(jnp.abs(ssm[3]).max()) == 0.0    # a slot not in the batch


def test_a_pad_is_the_identity_and_a_capture_is_the_state_there(layer):
    x = stream(4, 32)
    snaps = (jnp.zeros((3, 1, N, G, W)), jnp.zeros((3, 1, K1, G, W)))
    _o, ssm, conv, held = prefill(
        layer, x, *zero(), 1, take=21, snaps=snaps,
        cap_len=jnp.asarray([16]), snap_idx=jnp.asarray([2]))
    _o, s21, c21, _ = prefill(layer, x[:21], *zero(), 1)
    _o, s16, c16, _ = prefill(layer, x[:16], *zero(), 1)
    assert np.array_equal(np.asarray(ssm[1]), np.asarray(s21[1]))
    assert np.array_equal(np.asarray(conv[1]), np.asarray(c21[1]))
    assert np.abs(np.asarray(held[0][2]) - np.asarray(s16[1])).max() < 1e-6
    assert np.array_equal(np.asarray(held[1][2]), np.asarray(c16[1]))
    assert float(jnp.abs(held[0][:2]).max()) == 0.0


def test_the_flat_buffer_restarts_each_run_from_its_slots_row(layer):
    """Three runs in one buffer: a sequence continuing from 19 tokens
    (its state on row 2: the conv reaches back into the slot's tail), a
    new one from zero on row 0 with a snapshot after 16 tokens, and a
    decode row (one token, seven pads) on row 1."""
    from theroundtaible_tpu.engine.serving_loop import (RaggedSeq,
                                                        build_ragged_batch)
    xa, xb, xc = stream(5, 40), stream(6, 20), stream(7, 12)
    ssm, conv = zero()
    _o, ssm, conv, _ = prefill(layer, xa[:19], ssm, conv, 2)
    _o, ssm, conv, _ = prefill(layer, xc[:11], ssm, conv, 1)
    table = np.zeros((8,), np.int32)
    batch = build_ragged_batch(
        [RaggedSeq([5] * 21, 19, table), RaggedSeq([5] * 20, 0, table),
         RaggedSeq([5], 11, table)],
        t_budget=64, s_max=ROWS, pages_per_seq=8, scratch_page=0, pad_id=0,
        page_size=16)
    b = {k: jnp.asarray(v) for k, v in batch.items()
         if isinstance(v, np.ndarray)}
    slots = jnp.asarray([2, 0, 1, 3])
    rg = hybrid.ragged_meta(
        b["positions"], b["token_seq"], b["query_offsets"], b["kv_valid"],
        b["last_rows"], b["seq_of_block"], b["block_qstart"], slots,
        jnp.asarray([0, 16, 0, 0]), 8)
    flat = jnp.zeros((64, CFG.embed_dim))
    starts = np.asarray(rg["seq_start"])
    flat = flat.at[starts[0]:starts[0] + 21].set(xa[19:])
    flat = flat.at[starts[1]:starts[1] + 20].set(xb)
    flat = flat.at[starts[2]].set(xc[11])
    snaps = (jnp.zeros((2, 1, N, G, W)), jnp.zeros((2, 1, K1, G, W)))
    with jax.default_matmul_precision("highest"):
        out, ssm, conv, held = mamba1.mamba1_ragged(
            normed(layer, flat)[None], layer, CFG, ssm, conv, 0, rg, snaps,
            jnp.asarray([1, 0, 1, 1]))
    out = np.asarray(out[0])
    for x, lo, n, at in ((xa, 19, 21, starts[0]), (xb, 0, 20, starts[1]),
                         (xc, 11, 1, starts[2])):
        assert np.abs(out[at:at + n] - want(layer, x)[lo:lo + n]).max() \
            < TOL
    _o, s16, c16, _ = prefill(layer, xb[:16], *zero(), 0)
    assert np.abs(np.asarray(held[0][0]) - np.asarray(s16[0])).max() < 1e-6
    assert np.array_equal(np.asarray(held[1][0]), np.asarray(c16[0]))
    _o, s40, _c, _ = prefill(layer, xa, *zero(), 2)
    assert np.abs(np.asarray(ssm[2]) - np.asarray(s40[2])).max() < 1e-6


def _scan_case(seed, t, block, layers=2):
    rng = np.random.default_rng(seed)
    nb = t // block
    dt = rng.uniform(0, 0.1, (t, G, W)).astype(np.float32)
    dt[block + 1:2 * block] = 0.0                     # pads
    args = (dt, rng.normal(size=(t, G, W)).astype(np.float32),
            rng.normal(size=(t, 2 * N)).astype(np.float32),
            -rng.uniform(1, 16, (N, G, W)).astype(np.float32),
            rng.normal(size=(ROWS, layers, N, G, W)).astype(np.float32))
    slots = np.repeat([2, 0, 3], [nb - nb // 2 - 1, nb // 2, 1])[:nb]
    seqs = np.repeat([0, 1, 2], [nb - nb // 2 - 1, nb // 2, 1])[:nb]
    caps = np.full((nb,), -1)
    caps[0], caps[nb - 2] = block - 1, block // 2
    return args, (jnp.int32(1), slots, caps, seqs)


@pytest.mark.parametrize("t,block", [(64, 8), (32, 1), (136, 8)])
def test_the_kernel_interpreted_is_the_scan_it_replaces(t, block):
    """Blocks of 8 (a join) and of one token (a decode step's pass over
    the slots); 136 tokens: the SMEM tile of B and C is shared by four
    blocks and padded at the end."""
    args, meta = _scan_case(t, t, block)
    y, state, held = kernel.mamba1_scan(*args, *meta, block=block,
                                        n_seqs=3, interpret=True)
    y2, state2, held2 = mamba1.scan_blocks(*args, *meta, block=block,
                                           n_seqs=3)
    assert np.abs(np.asarray(y) - np.asarray(y2)).max() < 1e-5
    assert np.abs(np.asarray(state) - np.asarray(state2)).max() < 1e-5
    assert np.array_equal(np.asarray(state[:, 0]), args[4][:, 0])
    for seq in {int(meta[3][0]), int(meta[3][-2])}:   # rows with a capture
        assert np.abs(np.asarray(held[seq])
                      - np.asarray(held2[seq])).max() < 1e-5


def test_the_kernel_declines_by_one_rule():
    assert kernel.decline_reason(5120, 16).startswith("not on a TPU")
    assert kernel.fold(5120) == (40, 128) and kernel.fold(64) == (1, 64)


def test_runs_are_derived_from_the_kinds_and_only_the_new_kind_scans():
    full = get_model_config("jamba2-3b")
    assert full.scan_runs == (7, 13, 6) and CFG.scan_runs == (7, 6)
    assert sum(n * len(k) for k, n in full.layer_runs) == 56
    assert [k for k, _n in full.layer_runs] == [
        ("mamba1", "mlp"), ("attention",), ("mlp",), ("mamba1", "mlp"),
        ("attention",), ("mlp",), ("mamba1", "mlp")]
    for name in list_models():
        cfg = get_model_config(name)
        if cfg.layer_kinds is not None and not cfg.mamba1_layers:
            assert cfg.layer_runs == tuple(((k,), 1)
                                           for k in cfg.layer_kinds), name
    assert mamba1.bytes_per_state(full) == 327_680 + 61_440
    assert hybrid.state_bytes_per_sequence(full) == 10_117_120


def test_the_count_agrees_with_the_stacked_tree_leaf_for_leaf():
    shapes = jax.eval_shape(
        lambda k: init_params(get_model_config("jamba2-3b"), k,
                              jnp.bfloat16), jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(shapes)
    assert sum(int(np.prod(a.shape)) for a in leaves) == 3_029_337_472 \
        == fleet.estimate_param_count(get_model_config("jamba2-3b"))
    run = shapes["layers"][3]                    # layers 8-20: 13 blocks
    assert run["mamba1"]["in_proj"].shape == (13, 2560, 10240)
    assert run["mamba1"]["A_log"].shape == (13, 16, 5120)
    assert run["mlp"]["down_proj"].shape == (13, 8192, 2560)
    assert "lm_head" not in shapes               # tied: the embedding
    tiny = init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    assert sum(a.size for a in jax.tree_util.tree_leaves(tiny)) \
        == fleet.estimate_param_count(CFG)
    kinds = [k for k, _l in hybrid.layers_unrolled(CFG, tiny)]
    assert tuple(kinds) == CFG.layer_kinds
