"""Pallas attention kernels vs the dense reference path.

Runs in interpret mode on the CPU backend (conftest pins jax to cpu); the
same kernels compile for TPU in serving (engine._resolve_attn "auto").
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theroundtaible_tpu.engine.pallas.attention import (
    NEG_INF, flash_prefill_attention, ragged_decode_attention, supported)


def dense_ref(q, k, v, offsets, valid, window=None, softcap=None):
    """The models/common.py dense path, inlined for comparison."""
    B, T, H, D = q.shape
    S, K = k.shape[1], k.shape[2]
    ka = jnp.repeat(k, H // K, axis=2)
    va = jnp.repeat(v, H // K, axis=2)
    logits = jnp.einsum("bthd,bshd->bhts", q, ka).astype(jnp.float32)
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    qpos = offsets[:, None] + jnp.arange(T)[None, :]
    kv = jnp.arange(S)[None, None, :]
    mask = (kv <= qpos[:, :, None]) & (kv < valid[:, None, None])
    if window:
        mask = mask & (kv > qpos[:, :, None] - window)
    logits = jnp.where(mask[:, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, -1)
    return jnp.einsum("bhts,bshd->bthd", probs, va)


def make_inputs(B=3, T=192, H=8, K=2, D=32, S=1024, seed=0):
    """Default shapes exercise the MULTI-block machinery: T=192 → three
    64-wide q blocks, S=1024 → two 512-wide kv blocks, so online-softmax
    accumulation (alpha rescaling) and the kv index-map clamps run."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, K, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, K, D)), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("window,softcap", [
    (None, None), (48, None), (None, 30.0), (700, 30.0)])
def test_prefill_matches_dense(window, softcap):
    q, k, v = make_inputs()
    # ragged rows: different offsets (delta prefill) and lengths, with one
    # row's valid range crossing the kv-block boundary at 512
    offsets = jnp.asarray([0, 10, 600], jnp.int32)
    lengths = np.asarray([192, 40, 192])
    valid = offsets + jnp.asarray(lengths, jnp.int32)
    out = flash_prefill_attention(q, k, v, offsets, valid,
                                  sliding_window=window, softcap=softcap,
                                  interpret=True)
    ref = dense_ref(q, k, v, offsets, valid, window, softcap)
    assert out.shape == q.shape
    # compare only each row's REAL query positions — padded tail rows are
    # fully masked under small windows and never read by the engine
    for b, n in enumerate(lengths):
        np.testing.assert_allclose(np.asarray(out)[b, :n],
                                   np.asarray(ref)[b, :n],
                                   atol=5e-5, rtol=5e-5)


def test_prefill_mha_no_gqa():
    q, k, v = make_inputs(H=4, K=4)
    offsets = jnp.zeros((3,), jnp.int32)
    valid = jnp.full((3,), 192, jnp.int32)
    out = flash_prefill_attention(q, k, v, offsets, valid, interpret=True)
    ref = dense_ref(q, k, v, offsets, valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("window,softcap", [
    (None, None), (48, None), (None, 30.0), (700, None)])
def test_decode_matches_dense(window, softcap):
    _, k, v = make_inputs()
    rng = np.random.default_rng(1)
    qd = jnp.asarray(rng.normal(size=(3, 1, 8, 32)), jnp.float32)
    # rows below, at, and beyond the 512 kv-block boundary
    valid = jnp.asarray([1, 512, 1024], jnp.int32)
    out = ragged_decode_attention(qd, k, v, valid, sliding_window=window,
                                  softcap=softcap, interpret=True)
    ref = dense_ref(qd, k, v, valid - 1, valid, window, softcap)
    assert out.shape == qd.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-5, rtol=5e-5)


def test_decode_single_query_group():
    """MHA (group=1) exercises the sublane-1 decode block."""
    _, k, v = make_inputs(H=2, K=2)
    rng = np.random.default_rng(2)
    qd = jnp.asarray(rng.normal(size=(3, 1, 2, 32)), jnp.float32)
    valid = jnp.asarray([5, 600, 1000], jnp.int32)
    out = ragged_decode_attention(qd, k, v, valid, interpret=True)
    ref = dense_ref(qd, k, v, valid - 1, valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("window,softcap", [
    (None, None), (48, None), (None, 30.0), (700, None)])
def test_paged_decode_matches_dense(window, softcap):
    """paged_decode_attention off a SHUFFLED page pool must match the
    dense reference on the position-aligned view — the kv index map must
    follow the table, not the position."""
    from theroundtaible_tpu.engine.pallas.attention import (
        paged_decode_attention)
    B, S, K, D, ps = 3, 1024, 2, 32, 64
    n_pages = S // ps
    rng = np.random.default_rng(3)
    qd = jnp.asarray(rng.normal(size=(B, 1, 8, D)), jnp.float32)
    kv_view = jnp.asarray(rng.normal(size=(B, S, K, D)), jnp.float32)
    vv_view = jnp.asarray(rng.normal(size=(B, S, K, D)), jnp.float32)
    # Scatter each row's view into a pool at shuffled page ids (page 0
    # reserved scratch, like the real allocator).
    pool_pages = 1 + B * n_pages
    perm = rng.permutation(B * n_pages) + 1
    table = jnp.asarray(perm.reshape(B, n_pages), jnp.int32)
    k_pool = jnp.zeros((pool_pages, ps, K, D), jnp.float32)
    v_pool = jnp.zeros((pool_pages, ps, K, D), jnp.float32)
    k_pool = k_pool.at[table.reshape(-1)].set(
        kv_view.reshape(B * n_pages, ps, K, D))
    v_pool = v_pool.at[table.reshape(-1)].set(
        vv_view.reshape(B * n_pages, ps, K, D))
    valid = jnp.asarray([1, 512, 1024], jnp.int32)
    out = paged_decode_attention(qd, k_pool, v_pool, table, valid,
                                 sliding_window=window, softcap=softcap,
                                 interpret=True)
    ref = dense_ref(qd, kv_view, vv_view, valid - 1, valid, window,
                    softcap)
    assert out.shape == qd.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("window,softcap", [
    (None, None), (48, None), (None, 30.0), (700, 30.0)])
def test_paged_prefill_matches_dense(window, softcap):
    """paged_prefill_attention off a SHUFFLED page pool must match the
    dense reference — ragged rows with delta-prefill offsets, so the
    table-following index map, causal clamps and window bounds all
    run."""
    from theroundtaible_tpu.engine.pallas.attention import (
        paged_prefill_attention)
    B, T, H, K, D, S, ps = 3, 192, 8, 2, 32, 1024, 64
    n_pages = S // ps
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    kv_view = jnp.asarray(rng.normal(size=(B, S, K, D)), jnp.float32)
    vv_view = jnp.asarray(rng.normal(size=(B, S, K, D)), jnp.float32)
    perm = rng.permutation(B * n_pages) + 1
    table = jnp.asarray(perm.reshape(B, n_pages), jnp.int32)
    pool_pages = 1 + B * n_pages
    k_pool = jnp.zeros((pool_pages, ps, K, D), jnp.float32) \
        .at[table.reshape(-1)].set(kv_view.reshape(B * n_pages, ps, K, D))
    v_pool = jnp.zeros((pool_pages, ps, K, D), jnp.float32) \
        .at[table.reshape(-1)].set(vv_view.reshape(B * n_pages, ps, K, D))
    offsets = jnp.asarray([0, 10, 600], jnp.int32)
    lengths = np.asarray([192, 40, 192])
    valid = offsets + jnp.asarray(lengths, jnp.int32)
    out = paged_prefill_attention(q, k_pool, v_pool, table, offsets,
                                  valid, sliding_window=window,
                                  softcap=softcap, interpret=True)
    ref = dense_ref(q, kv_view, vv_view, offsets, valid, window, softcap)
    assert out.shape == q.shape
    for b, n in enumerate(lengths):
        np.testing.assert_allclose(np.asarray(out)[b, :n],
                                   np.asarray(ref)[b, :n],
                                   atol=5e-5, rtol=5e-5)


def test_paged_decode_never_reads_beyond_frontier():
    """Pages past a row's frontier hold garbage (NaN) in the pool; the
    clamped index map + mask must keep them out of the result."""
    from theroundtaible_tpu.engine.pallas.attention import (
        paged_decode_attention)
    B, S, K, D, ps = 2, 512, 1, 32, 64
    n_pages = S // ps
    rng = np.random.default_rng(4)
    qd = jnp.asarray(rng.normal(size=(B, 1, 4, D)), jnp.float32)
    view = jnp.asarray(rng.normal(size=(B, S, K, D)), jnp.float32)
    valid = jnp.asarray([70, 300], jnp.int32)
    table = jnp.arange(1, 1 + B * n_pages, dtype=jnp.int32) \
        .reshape(B, n_pages)
    k_pool = jnp.full((1 + B * n_pages, ps, K, D), jnp.nan, jnp.float32)
    v_pool = jnp.full((1 + B * n_pages, ps, K, D), jnp.nan, jnp.float32)
    k_pool = k_pool.at[table.reshape(-1)].set(
        view.reshape(B * n_pages, ps, K, D))
    v_pool = v_pool.at[table.reshape(-1)].set(
        view.reshape(B * n_pages, ps, K, D))
    # poison every page at-or-past each row's frontier page boundary
    for b in range(B):
        first_bad = (int(valid[b]) - 1) // ps + 1
        for j in range(first_bad, n_pages):
            k_pool = k_pool.at[table[b, j]].set(jnp.nan)
            v_pool = v_pool.at[table[b, j]].set(jnp.nan)
    out = paged_decode_attention(qd, k_pool, v_pool, table, valid,
                                 interpret=True)
    assert np.isfinite(np.asarray(out)).all()


def gather_softmax_ref(q, k_pool, v_pool, table, valid, window=None,
                       softcap=None):
    """Plain gather-and-softmax over each row's own pages, in numpy:
    nothing of the kernels' block structure, online softmax or masking
    is shared with it."""
    q, k_pool, v_pool = (np.asarray(x, np.float64)
                         for x in (q, k_pool, v_pool))
    B, _, H, D = q.shape
    ps, K = k_pool.shape[1], k_pool.shape[2]
    out = np.zeros((B, 1, H, D))
    for b in range(B):
        n = int(valid[b])
        if n == 0:
            continue                       # an idle row yields zeros
        pages = np.asarray(table[b, :-(-n // ps)])
        k = k_pool[pages].reshape(-1, K, D)[:n]
        v = v_pool[pages].reshape(-1, K, D)[:n]
        first = max(0, n - window) if window else 0
        for h in range(H):
            s = k[first:, h // (H // K)] @ q[b, 0, h]
            if softcap:
                s = softcap * np.tanh(s / softcap)
            p = np.exp(s - s.max())
            out[b, 0, h] = (p / p.sum()) @ v[first:, h // (H // K)]
    return out


WALK_MODES = {"plain": {}, "window": {"sliding_window": 40},
              "softcap": {"softcap": 30.0}, "int8": {}}


@pytest.mark.parametrize("mode", list(WALK_MODES))
@pytest.mark.parametrize("heads", [(32, 8), (32, 2), (8, 1), (6, 3)])
def test_paged_decode_walk_matches_gather_and_softmax(heads, mode,
                                                      monkeypatch):
    """The decode walk against a plain reference at the benchmark's
    head layouts (Mistral-7B's 32/8, Nemotron's 32/2), MQA and three kv
    heads — pools the walk takes token-major and, where the heads of a
    token do not fill whole tiles (one or three heads, two of int8),
    head-major. Four
    pages a trip in a table twelve wide; rows of length 0 (idle), 1,
    exactly one page, one past a page, seven pages (not a multiple of
    four) and the table's full width. The pool is shuffled, and behind
    every table entry past a row's frontier lies a poisoned page: the
    walk must never read one into the result."""
    from theroundtaible_tpu.engine import kv_quant as kvq
    from theroundtaible_tpu.engine.pallas import attention as pattn
    (H, K), D, ps, W, N = heads, 32, 16, 12, 4
    lens = np.asarray([0, 1, ps, ps + 1, 6 * ps + 5, W * ps])
    B = len(lens)
    rng = np.random.default_rng(28)
    q = rng.normal(size=(B, 1, H, D)).astype(np.float32)
    k_pool, v_pool = (rng.normal(size=(1 + B * W, ps, K, D))
                      .astype(np.float32) for _ in range(2))
    table = (rng.permutation(B * W) + 1).reshape(B, W).astype(np.int32)
    dead = np.concatenate([table[b, -(-int(n) // ps):]
                           for b, n in enumerate(lens)])
    quant = {}
    if mode == "int8":
        spec = kvq.KVQuantSpec(bits=8)
        (k_pool, ks), (v_pool, vs) = (
            tuple(np.array(a) for a in kvq.quantize_cells(
                jnp.asarray(x), spec)) for x in (k_pool, v_pool))
        ref_pools = [np.asarray(kvq.dequantize_cells(
            jnp.asarray(x), jnp.asarray(sc), spec, jnp.float32))
            for x, sc in ((k_pool, ks), (v_pool, vs))]
        ks[dead] = vs[dead] = np.nan       # int8 cells hold no NaN
        quant = {"k_scale": jnp.asarray(ks), "v_scale": jnp.asarray(vs)}
        shape = dict(itemsize=1, scale_groups=ks.shape[-1])
    else:
        ref_pools = [k_pool.copy(), v_pool.copy()]
        k_pool[dead] = v_pool[dead] = np.nan
        shape = dict(itemsize=4, scale_groups=0)
    monkeypatch.setattr(
        pattn, "_WALK_TRIP_BYTES",
        N * pattn._walk_page_bytes(ps, K, D, **shape))
    assert pattn._walk_pages(ps, D, K, H // K, D, **shape) == N
    out = pattn.paged_decode_attention(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(table), jnp.asarray(lens, jnp.int32), interpret=True,
        **WALK_MODES[mode], **quant)
    ref = gather_softmax_ref(
        q, *ref_pools, table, lens,
        WALK_MODES[mode].get("sliding_window"),
        WALK_MODES[mode].get("softcap"))
    assert out.shape == q.shape
    assert not np.asarray(out[0]).any()
    np.testing.assert_allclose(np.asarray(out), ref, atol=5e-5, rtol=5e-5)


def test_paged_decode_gate_reckons_what_vmem_stores(monkeypatch):
    """The walk's plan counts what VMEM holds beside the page buffers:
    the q and out blocks of a row block (so the plan holds for every
    batch size: a larger batch is more grid steps, not a larger block),
    the scores, and for a quantized pool the dequantized rows, the
    scale rows as stored (K*G rows of ps lanes) and the one-hot that
    turns them into columns. An int8 pool of 512-token pages fits by
    its buffers alone and not with what the product needs, so it
    declines to the gather view instead of failing Mosaic."""
    from theroundtaible_tpu.engine.pallas import attention as pattn
    ps, d, kh, group = 512, 128, 8, 1
    int8 = dict(itemsize=1, scale_groups=1)
    page = pattn._walk_page_bytes(ps, kh, d, **int8)
    assert page == 2 * (ps * kh * d + 8 * ps * 4)  # payload + scale rows
    assert 2 * page <= pattn._VMEM_BUDGET           # two slots alone fit
    assert pattn._walk_vmem_est(1, ps, d, kh, group, d, **int8) \
        > pattn._VMEM_BUDGET
    assert not pattn.paged_decode_supported(ps, d, kh, group, **int8)
    assert pattn.paged_decode_decline_reason(
        ps, d, kh, group, **int8).startswith("vmem:")
    # q and out: sixteen rows of 32 heads, two blocks, two buffers each
    est = pattn._walk_vmem_est(2, 128, 128, 8, 4, 128, 2, 0)
    assert est - 2 * 2 * pattn._walk_page_bytes(128, 8, 128, 2, 0) \
        >= 2 * 2 * pattn._WALK_ROW_BLOCK * 32 * 128 * 2
    # the same pages in bf16 fit, one a trip; the benchmark's pools take
    # two (Mistral-7B) and eight (Nemotron's two kv heads) a trip
    assert pattn._walk_pages(ps, d, kh, group) == 1
    assert pattn._walk_pages(128, 128, 8, 4) == 2
    assert pattn._walk_pages(128, 128, 2, 16) == 8
    assert pattn._walk_pages(128, 128, 8, 4, **int8) == 2
    # on the chip: every head count is served (token-major where XLA
    # stores the pool so, head-major where not); a head width or a
    # scale page that does not fill lane rows is not
    monkeypatch.setattr(pattn, "_interpret", lambda: False)
    for k, g in ((8, 4), (1, 8), (2, 16), (16, 2), (3, 4), (6, 4)):
        assert pattn.paged_decode_supported(128, 128, k, g)
    assert [k for k in range(1, 17) if pattn._token_major(k, 2)] \
        == [2, 4, 8, 16]
    assert not pattn._token_major(2, 1) and pattn._token_major(4, 1)
    assert pattn.paged_decode_supported(128, 128, 8, 4, **int8)
    assert pattn.paged_decode_supported(128, 128, 2, 4, **int8)
    # (64-wide heads in pairs are stored two a lane row and served:
    # ISSUE 52, lane_pack; an odd count or a quantized pool is not)
    assert pattn.paged_decode_decline_reason(128, 64, 8, 4) is None
    assert pattn.paged_decode_decline_reason(128, 64, 3, 4) \
        == "head_dim:64"
    assert pattn.paged_decode_decline_reason(128, 64, 8, 4, **int8) \
        == "head_dim:64"
    assert pattn.paged_decode_decline_reason(64, 128, 8, 4, **int8) \
        == "scale_page:64"
    assert pattn.paged_decode_supported(64, 128, 8, 4)


def test_supported_shapes():
    assert supported(64, 512, 16)          # interpret mode: any D
    assert supported(1, 2048, 128)
    assert not supported(63, 512, 16)      # T has no block divisor
    assert not supported(64, 100, 16)      # S has no block divisor


def test_engine_forward_flash_matches_dense():
    """Full forward pass: flash vs dense logits on a tiny model."""
    import dataclasses

    from theroundtaible_tpu.engine.models.common import forward, init_params
    from theroundtaible_tpu.engine.models.registry import get_model_config

    cfg = get_model_config("tiny-mistral", max_seq_len=128)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    tokens = jnp.asarray([[1, 5, 9, 8] * 8], jnp.int32)     # T=32
    positions = jnp.arange(32)[None, :]
    valid = jnp.asarray([32], jnp.int32)

    cfg_flash = dataclasses.replace(cfg, attn_impl="flash")
    logits_d, _ = forward(params, cfg, tokens, positions, None, None, valid)
    logits_f, _ = forward(params, cfg_flash, tokens, positions, None, None,
                          valid)
    # activations are bf16 inside forward, so the two summation orders can
    # differ by O(bf16 eps) per logit
    np.testing.assert_allclose(np.asarray(logits_d), np.asarray(logits_f),
                               atol=5e-2, rtol=5e-2)


class TestFlashSpmd:
    """flash under a multi-device mesh via shard_map (VERDICT r1 #4)."""

    def _mesh(self, model=2, data=1):
        from theroundtaible_tpu.engine.sharding import build_mesh
        return build_mesh({"data": data, "model": model},
                          jax.devices()[:data * model])

    def test_spmd_prefill_matches_dense(self):
        from theroundtaible_tpu.engine.pallas.attention import (
            flash_attention_spmd)
        q, k, v = make_inputs()  # H=8, K=2 → divisible by model=2
        offsets = jnp.asarray([0, 10, 600], jnp.int32)
        valid = offsets + jnp.asarray([192, 40, 192], jnp.int32)
        out = flash_attention_spmd(self._mesh(), q, k, v, offsets, valid,
                                   interpret=True)
        assert out is not None
        ref = dense_ref(q, k, v, offsets, valid)
        for b, n in enumerate([192, 40, 192]):
            np.testing.assert_allclose(np.asarray(out)[b, :n],
                                       np.asarray(ref)[b, :n],
                                       atol=5e-5, rtol=5e-5)

    def test_spmd_decode_matches_dense(self):
        from theroundtaible_tpu.engine.pallas.attention import (
            flash_attention_spmd)
        _, k, v = make_inputs()
        rng = np.random.default_rng(3)
        qd = jnp.asarray(rng.normal(size=(3, 1, 8, 32)), jnp.float32)
        valid = jnp.asarray([1, 512, 1024], jnp.int32)
        out = flash_attention_spmd(self._mesh(), qd, k, v, valid - 1, valid,
                                   interpret=True)
        assert out is not None
        ref = dense_ref(qd, k, v, valid - 1, valid)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=5e-5, rtol=5e-5)

    def test_spmd_batch_on_data_axis(self):
        from theroundtaible_tpu.engine.pallas.attention import (
            flash_attention_spmd)
        q, k, v = make_inputs(B=4)
        offsets = jnp.zeros((4,), jnp.int32)
        valid = jnp.full((4,), 192, jnp.int32)
        out = flash_attention_spmd(self._mesh(model=2, data=2), q, k, v,
                                   offsets, valid, interpret=True)
        assert out is not None
        ref = dense_ref(q, k, v, offsets, valid)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=5e-5, rtol=5e-5)

    def test_spmd_mqa_replicated_kv(self):
        """MQA (kh=1, the gemma-2b shape): q heads shard, kv replicates."""
        from theroundtaible_tpu.engine.pallas.attention import (
            flash_attention_spmd)
        q, k, v = make_inputs(H=8, K=1)
        offsets = jnp.asarray([0, 10, 600], jnp.int32)
        valid = offsets + jnp.asarray([192, 40, 192], jnp.int32)
        out = flash_attention_spmd(self._mesh(model=4), q, k, v, offsets,
                                   valid, interpret=True)
        assert out is not None
        ref = dense_ref(q, k, v, offsets, valid)
        for b, n in enumerate([192, 40, 192]):
            np.testing.assert_allclose(np.asarray(out)[b, :n],
                                       np.asarray(ref)[b, :n],
                                       atol=5e-5, rtol=5e-5)

    def test_engine_flash_tp_mqa(self):
        """End-to-end MQA engine under 4-way TP with flash: greedy parity
        with the dense engine (the gemma-2b-on-v5e-8 head layout)."""
        import dataclasses

        from theroundtaible_tpu.engine.engine import InferenceEngine
        from theroundtaible_tpu.engine.models.registry import get_model_config
        from theroundtaible_tpu.engine.sampling import SamplingParams

        cfg = dataclasses.replace(get_model_config("tiny-gemma"),
                                  num_kv_heads=1, max_seq_len=256)

        def build(attn):
            return InferenceEngine(
                cfg, mesh_shape={"data": 1, "model": 4}, num_slots=2,
                attn=attn,
                sampling=SamplingParams(temperature=0.0, max_new_tokens=8))

        flash_eng, dense_eng = build("flash"), build("dense")
        assert flash_eng.cfg.attn_impl == "flash"
        o_f = flash_eng.generate("a question", slot_name="a",
                                 max_new_tokens=8)
        o_d = dense_eng.generate("a question", slot_name="a",
                                 max_new_tokens=8)
        assert o_f == o_d

    def test_spmd_refuses_indivisible_heads(self):
        from theroundtaible_tpu.engine.pallas.attention import (
            flash_attention_spmd)
        q, k, v = make_inputs()  # K=2 does not divide model=8
        offsets = jnp.zeros((3,), jnp.int32)
        valid = jnp.full((3,), 192, jnp.int32)
        assert flash_attention_spmd(self._mesh(model=8), q, k, v,
                                    offsets, valid, interpret=True) is None

    def test_engine_flash_tp_matches_dense_tp(self):
        """Greedy parity: flash vs dense engines on the same 2-way TP mesh,
        including the slot-reuse (delta prefill) second turn."""
        from theroundtaible_tpu.engine.engine import InferenceEngine
        from theroundtaible_tpu.engine.models.registry import get_model_config
        from theroundtaible_tpu.engine.sampling import SamplingParams

        def build(attn):
            return InferenceEngine(
                get_model_config("tiny-llama", max_seq_len=256),
                mesh_shape={"data": 1, "model": 2}, num_slots=2, attn=attn,
                sampling=SamplingParams(temperature=0.0, max_new_tokens=8))

        flash_eng, dense_eng = build("flash"), build("dense")
        assert flash_eng.cfg.attn_impl == "flash"
        prompts = ["the knights debate caching",
                   "the knights debate caching, round two with more detail"]
        outs = []
        for eng in (flash_eng, dense_eng):
            o1 = eng.generate(prompts[0], slot_name="a", max_new_tokens=8)
            o2 = eng.generate(prompts[1], slot_name="a", max_new_tokens=8)
            assert eng.last_stats.reused_tokens > 0
            outs.append((o1, o2))
        assert outs[0] == outs[1]

    def test_engine_flash_raises_on_indivisible_mesh(self):
        from theroundtaible_tpu.engine.engine import InferenceEngine
        from theroundtaible_tpu.engine.models.registry import get_model_config

        with pytest.raises(ValueError, match="divisible"):
            InferenceEngine(
                get_model_config("tiny-llama", max_seq_len=256),
                mesh_shape={"data": 1, "model": 8}, num_slots=2,
                attn="flash")


def test_engine_generate_with_flash():
    """End-to-end generate through the engine with attn='flash'."""
    from theroundtaible_tpu.engine.engine import InferenceEngine
    from theroundtaible_tpu.engine.models.registry import get_model_config
    from theroundtaible_tpu.engine.sampling import SamplingParams

    cfg = get_model_config("tiny-gemma")
    # single-device mesh: the plain (non-shard_map) kernel path
    eng = InferenceEngine(cfg, num_slots=2, attn="flash",
                          mesh_shape={"data": 1, "model": 1},
                          sampling=SamplingParams(temperature=0.0,
                                                  max_new_tokens=8))
    assert eng.cfg.attn_impl == "flash"
    out = eng.generate("hello knights", slot_name="a", max_new_tokens=8)
    assert isinstance(out, str)
    # slot reuse path (delta prefill at offset > 0) under flash
    out2 = eng.generate("hello knights, round two", slot_name="a",
                        max_new_tokens=8)
    assert isinstance(out2, str)
    assert eng.last_stats.reused_tokens > 0
