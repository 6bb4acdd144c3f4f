"""The gated short convolution (models/shortconv.py) in its three forms
— one token a row, [B, T] rows each from its own tail, the scheduler's
flat buffer — against the layer's equations in float64 and against each
other across a block of the flat buffer (8 rows) and a page boundary (a
snapshot's tail), on the CPU at tiny-lfm2's widths. TOL: float32 sums in
another order (the reading 6e-7); with the tails in bfloat16 the same
comparison reads 8e-3."""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from theroundtaible_tpu.engine.models import hybrid, shortconv  # noqa: E402
from theroundtaible_tpu.engine.models.registry import (  # noqa: E402
    get_model_config)

CFG = get_model_config("tiny-lfm2")
E, K1, ROWS, TOL = CFG.embed_dim, CFG.conv_kernel - 1, 4, 1e-5


@pytest.fixture(scope="module")
def layer():
    return hybrid.init_layer(CFG, hybrid.SHORTCONV, jax.random.PRNGKey(7),
                             jnp.float32)


def stream(seed, n):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, E), jnp.float32)


def want(layer, h):
    """The equations, float64, a token at a time."""
    w_in, w_out, w = (np.asarray(layer[k], np.float64)
                      for k in ("in_proj", "out_proj", "conv_w"))
    bcu = np.asarray(h, np.float64) @ w_in
    g = bcu[:, :E] * bcu[:, 2 * E:]
    out = np.zeros_like(g)
    for t in range(len(g)):
        conv = sum(w[j] * g[t - K1 + j] for j in range(K1 + 1)
                   if t - K1 + j >= 0)
        out[t] = (bcu[t, E:2 * E] * conv) @ w_out
    return out, g


def zero(rows=ROWS):
    return shortconv.zero_state(CFG, rows, jnp.float32)[shortconv.PART][0]


def test_the_layer_is_two_gates_around_three_taps_and_keeps_two_rows(layer):
    assert sorted(layer) == ["conv_w", "in_proj", "norm", "out_proj"]
    assert layer["in_proj"].shape == (E, 3 * E)
    assert layer["conv_w"].shape == (3, E) and K1 == 2
    assert zero().shape == (ROWS, 2, E) and zero().dtype == jnp.float32
    assert shortconv.bytes_per_state(CFG, jnp.float32) == 2 * E * 4
    assert shortconv.bytes_per_state(CFG) == 2 * E * 2        # bfloat16
    assert shortconv.PART in hybrid.ROW_PARTS
    assert hybrid.state_bytes_per_sequence(CFG, jnp.float32) == 5 * 2 * E * 4
    # Mamba-2's conv is the same sum with a bias ahead and SiLU behind.
    rows = [stream(20 + i, 3) for i in range(3)]
    w = stream(30, 3)
    assert np.allclose(shortconv.taps_sum(rows, w, jnp.ones((E,))),
                       1.0 + sum(w[k] * rows[k] for k in range(3)))


def test_rows_from_their_own_tails_are_the_equations(layer):
    """[B, T] rows from zero tails: the outputs and the last two rows of
    g; a second chunk from the first one's tail continues the sequence;
    a snapshot is the tail after `cap_len` tokens."""
    xa, xb = stream(1, 37), stream(2, 37)
    with jax.default_matmul_precision("highest"):
        out, tail, cap = shortconv.shortconv_prefill(
            jnp.stack([xa, xb]), layer, CFG, zero(2),
            jnp.asarray([37, 21]), jnp.asarray([32, 16]))
        more, tail2 = shortconv.shortconv_prefill(
            jnp.stack([xb[21:], xa[:16]]), layer, CFG,
            jnp.stack([tail[1], jnp.zeros((2, E))]), jnp.asarray([16, 16]))
    wa, ga = want(layer, xa)
    wb, gb = want(layer, xb)
    assert np.abs(np.asarray(out[0]) - wa).max() < TOL
    assert np.abs(np.asarray(out[1, :21]) - wb[:21]).max() < TOL
    assert np.abs(np.asarray(tail[0]) - ga[35:37]).max() < TOL
    assert np.abs(np.asarray(tail[1]) - gb[19:21]).max() < TOL
    assert np.abs(np.asarray(cap[0]) - ga[30:32]).max() < TOL
    assert np.abs(np.asarray(cap[1]) - gb[14:16]).max() < TOL
    assert np.abs(np.asarray(more[0]) - wb[21:]).max() < TOL
    assert np.abs(np.asarray(tail2[0]) - gb[35:37]).max() < TOL
    # a run of one token keeps one row of the old tail
    with jax.default_matmul_precision("highest"):
        _o, t1 = shortconv.shortconv_prefill(
            xa[16:17][None], layer, CFG, jnp.asarray(ga[14:16])[None]
            .astype(jnp.float32), jnp.asarray([1]))
    assert np.abs(np.asarray(t1[0]) - ga[15:17]).max() < TOL


def test_a_token_a_row_continues_a_chunk_and_leaves_idle_rows_alone(layer):
    x = stream(3, 24)
    w, g = want(layer, x)
    with jax.default_matmul_precision("highest"):
        _o, tail = shortconv.shortconv_prefill(
            x[:16][None], layer, CFG, zero(1), jnp.asarray([16]))
        tails = jnp.concatenate([tail, tail])
        for t in range(16, 24):
            out, tails = shortconv.shortconv_step(
                jnp.stack([x[t], x[t]])[:, None], layer, CFG, tails,
                jnp.asarray([True, False]))
            assert np.abs(np.asarray(out[0, 0]) - w[t]).max() < TOL
    assert np.abs(np.asarray(tails[0]) - g[22:24]).max() < TOL
    assert np.array_equal(np.asarray(tails[1]), np.asarray(tail[0]))


def test_the_flat_buffer_restarts_each_run_from_its_slots_tail(layer):
    """Three runs in one buffer: a sequence continuing from 19 tokens
    (its tail on row 2: the taps of its first two rows reach into it,
    and its 21 rows cross two blocks of eight), a new one from zero on
    row 0 with a snapshot after 16 tokens (a page boundary), and a
    decode row (one token, seven pads) on row 1 that keeps one row of
    its old tail."""
    from theroundtaible_tpu.engine.serving_loop import (RaggedSeq,
                                                        build_ragged_batch)
    xa, xb, xc = stream(5, 40), stream(6, 20), stream(7, 12)
    with jax.default_matmul_precision("highest"):
        _o, tails = shortconv.shortconv_prefill(
            jnp.stack([jnp.zeros((19, E)),
                       jnp.concatenate([xc[:11], jnp.zeros((8, E))]),
                       xa[:19], jnp.zeros((19, E))]), layer, CFG, zero(),
            jnp.asarray([0, 11, 19, 0]))
    table = np.zeros((8,), np.int32)
    batch = build_ragged_batch(
        [RaggedSeq([5] * 21, 19, table), RaggedSeq([5] * 20, 0, table),
         RaggedSeq([5], 11, table)],
        t_budget=64, s_max=ROWS, pages_per_seq=8, scratch_page=0, pad_id=0,
        page_size=16)
    b = {k: jnp.asarray(v) for k, v in batch.items()
         if isinstance(v, np.ndarray)}
    rg = hybrid.ragged_meta(
        b["positions"], b["token_seq"], b["query_offsets"], b["kv_valid"],
        b["last_rows"], b["seq_of_block"], b["block_qstart"],
        jnp.asarray([2, 0, 1, 3]), jnp.asarray([0, 16, 0, 0]), 8)
    starts = np.asarray(rg["seq_start"])
    flat = jnp.zeros((64, E))
    flat = flat.at[starts[0]:starts[0] + 21].set(xa[19:])
    flat = flat.at[starts[1]:starts[1] + 20].set(xb)
    flat = flat.at[starts[2]].set(xc[11])
    with jax.default_matmul_precision("highest"):
        out, tails, cap = shortconv.shortconv_ragged(
            flat[None], layer, CFG, tails, rg)
    out = np.asarray(out[0])
    for x, lo, n, at in ((xa, 19, 21, starts[0]), (xb, 0, 20, starts[1]),
                         (xc, 11, 1, starts[2])):
        assert np.abs(out[at:at + n] - want(layer, x)[0][lo:lo + n]).max() \
            < TOL
    ga, gb, gc = (want(layer, x)[1] for x in (xa, xb, xc))
    assert np.abs(np.asarray(tails[2]) - ga[38:40]).max() < TOL
    assert np.abs(np.asarray(tails[0]) - gb[18:20]).max() < TOL
    assert np.abs(np.asarray(tails[1]) - gc[10:12]).max() < TOL
    assert np.abs(np.asarray(cap[1]) - gb[14:16]).max() < TOL


def test_a_tail_in_bfloat16_is_rounded_once_and_alike_in_every_form(layer):
    """The published dtype: handed a bfloat16 tail, a form rounds g to
    bfloat16 where it is made, so the tail a chunk leaves, the tail steps
    leave and the rows a longer chunk read in between are the same bits
    — and the outputs leave the float32 tolerance (the control)."""
    x = stream(9, 20)
    z = jnp.zeros((1, 2, E), jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        whole, tail = shortconv.shortconv_prefill(
            x[None], layer, CFG, z, jnp.asarray([20]), )
        first, mid = shortconv.shortconv_prefill(
            x[:17][None], layer, CFG, z, jnp.asarray([17]))
        for t in range(17, 20):
            out, mid = shortconv.shortconv_step(
                x[t][None, None], layer, CFG, mid, jnp.asarray([True]))
            assert np.array_equal(np.asarray(out[0, 0]),
                                  np.asarray(whole[0, t]))
    assert tail.dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(tail, np.float32),
                          np.asarray(mid, np.float32))
    assert np.abs(np.asarray(whole[0]) - want(layer, x)[0]).max() > 50 * TOL
