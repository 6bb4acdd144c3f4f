"""Chipless Mosaic validation of the attention kernels' TPU lowering.

Mosaic compiles Pallas kernels in jaxlib at LOWERING time, so
`jit(f).trace(...).lower(lowering_platforms=("tpu",))` on the CPU test
box surfaces TPU block-shape/op-support violations without a chip —
closing VERDICT r4 weak #6 ("every line of round-4 device code has only
ever executed in interpret mode"): the spmd wrappers below (including
nested-shard_map manualization and the pool-direct replica-grouped
paged path) now cannot regress their TPU lowering silently even though
the test environment has one real chip at most. Numeric parity is
covered elsewhere (interpret mode vs dense reference); this file is
only about "does Mosaic accept it".
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theroundtaible_tpu.engine.pallas import attention as pattn

H, K, D = 8, 4, 256          # gemma-2b-shaped GQA heads
S = 512                      # cache length
PAGE = 128                   # engine page size


def _mesh(shape, axes):
    n = int(np.prod(shape))
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} virtual devices")
    return jax.sharding.Mesh(
        np.array(jax.devices()[:n]).reshape(shape), axes)


def _lower_tpu(f, *args):
    jax.jit(f).trace(*args).lower(lowering_platforms=("tpu",))


def _qkv(b, t):
    q = jnp.zeros((b, t, H, D), jnp.bfloat16)
    k = jnp.zeros((b, S, K, D), jnp.bfloat16)
    v = jnp.zeros((b, S, K, D), jnp.bfloat16)
    return q, k, v


# (None, None) = llama/qwen; softcap = gemma-2; window = mistral —
# each flag switches real kernel code paths (tanh, window masks)
@pytest.mark.parametrize("softcap,window", [(None, None), (30.0, None),
                                            (None, 64)])
def test_single_device_kernels_lower(softcap, window):
    b = 2
    q, k, v = _qkv(b, 1)
    valid = jnp.full((b,), 37, jnp.int32)

    def decode(q, k, v, valid):
        return pattn.ragged_decode_attention(
            q, k, v, valid, sliding_window=window, softcap=softcap,
            interpret=False)

    _lower_tpu(decode, q, k, v, valid)

    qp, _, _ = _qkv(b, 128)
    offs = jnp.zeros((b,), jnp.int32)

    def prefill(q, k, v, offs, valid):
        return pattn.flash_prefill_attention(
            q, k, v, offs, valid, sliding_window=window,
            softcap=softcap, interpret=False)

    _lower_tpu(prefill, qp, k, v, offs, valid)


@pytest.mark.parametrize("t", [1, 128])
def test_flash_spmd_lowers_on_data_model_mesh(t):
    mesh = _mesh((2, 4), ("data", "model"))
    b = 2
    q, k, v = _qkv(b, t)
    pos = jnp.zeros((b,), jnp.int32)
    valid = jnp.full((b,), 200, jnp.int32)

    def f(q, k, v, pos, valid):
        out = pattn.flash_attention_spmd(mesh, q, k, v, pos, valid,
                                         interpret=False)
        assert out is not None, "spmd wrapper declined supported layout"
        return out

    _lower_tpu(f, q, k, v, pos, valid)


def test_paged_vmem_budget_shrinks_or_declines():
    """All kv heads ride one block, so the paged working set scales with
    kh: large-GQA shapes must shrink block_q (not fail Mosaic on chip),
    and absurd ones must decline to the gather-view fallback."""
    from theroundtaible_tpu.engine.pallas.attention import (
        _paged_prefill_block_q, paged_prefill_supported)
    bq = _paged_prefill_block_q(2048, 128, 128, 8, 8)   # 70B-class GQA
    assert bq is not None and bq < 128
    assert paged_prefill_supported(2048, 128, 128, 8, 8)
    assert not paged_prefill_supported(2048, 512, 512, 16, 16)


# gemma-2b-shaped w4a16 matmuls, sharded: every decode-hot projection
# class with its TP convention (sharding.int4_shard_axis), at dims whose
# PER-SHARD blocks exist on a 4-way model axis.
INT4_SPMD_CASES = [
    ("bte,ef->btf", "col", (1, 1, 2048), (2048, 16384)),     # mlp up/gate
    ("btf,fe->bte", "row", (1, 1, 16384), (16384, 2048)),    # mlp down
    ("bte,ehd->bthd", "col", (1, 1, 2048), (2048, 8, 256)),  # qkv
    ("bthd,hde->bte", "row", (1, 1, 8, 256), (8, 256, 2048)),  # o_proj
    ("bte,ve->btv", "col", (1, 1, 2048), (32768, 2048)),     # lm head
]


@pytest.mark.quant_kernels
@pytest.mark.parametrize("spec,tp,ashape,wshape", INT4_SPMD_CASES)
def test_int4_spmd_lowers_on_data_model_mesh(spec, tp, ashape, wshape,
                                             monkeypatch):
    """Chipless Mosaic lowering of the shard-aware w4a16 dispatch
    (ISSUE 3): the per-shard kernels inside shard_map — including the
    row-parallel psum — must cross-lower for TPU without a chip, same
    discipline as the attention spmd wrappers above."""
    from theroundtaible_tpu.engine.models.common import Int4Leaf
    from theroundtaible_tpu.engine.pallas import int4mm
    from theroundtaible_tpu.engine.quant import _quantize_leaf_int4

    monkeypatch.setattr(int4mm, "_interpret", lambda: False)
    # Lowering is one step short of the compile the v5e's compiler
    # refuses (int4mm.MOSAIC_REFUSAL, tests/test_chip_compile.py): lift
    # the plan-time gate so this keeps guarding what the repair needs.
    monkeypatch.setattr(int4mm, "MOSAIC_REFUSAL", {})
    mesh = _mesh((2, 4), ("data", "model"))
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal(wshape).astype(np.float32) * 0.02,
                    jnp.bfloat16)
    leaf = _quantize_leaf_int4(w, (0,), jnp.bfloat16, False, 64, 4)
    assert isinstance(leaf, Int4Leaf)
    a = jnp.asarray(rng.standard_normal(ashape).astype(np.float32),
                    jnp.bfloat16)

    def f(a, q4, s4):
        y, reason = int4mm.einsum_int4_spmd(
            mesh, spec, a,
            Int4Leaf(q4=q4, s4=s4, axis=leaf.axis, group=leaf.group),
            tp=tp)
        assert y is not None, f"spmd dispatch declined {spec}: {reason}"
        return y

    _lower_tpu(f, a, leaf.q4, leaf.s4)


def test_int4_vmem_budget_declines_not_mosaic():
    """Oversized shapes must decline BEFORE any pallas_call is emitted —
    the plan's VMEM estimate is the runtime guarantee that no dispatch
    can reach a Mosaic allocation failure on chip (acceptance: every
    kernel dispatch has a budget estimate that declines to XLA)."""
    from theroundtaible_tpu.engine.pallas.int4mm import (
        _plan_pack_contract, _plan_pack_out)
    # healthy decode shapes plan fine
    assert _plan_pack_out(8, 2048, 8192, 32)[0] is not None
    assert _plan_pack_contract(8, 1024, 32768, 32)[0] is not None
    # the accumulators span the full output axis: a huge P overruns
    plan, reason = _plan_pack_out(64, 2048, 1 << 21, 32)
    assert plan is None and reason.startswith("vmem:")
    # contract kernel: whole-cp operand blocks overrun at huge cp
    plan, reason = _plan_pack_contract(64, 1 << 15, 512, 32)
    assert plan is None and reason.startswith("vmem:")
    # prefill-M cap stays a distinct, expected reason
    assert _plan_pack_out(128, 2048, 8192, 32)[1] == "rows:prefill-m"


@pytest.mark.parametrize("pool_replicas", [1, 2])
def test_paged_spmd_lowers_pool_direct(pool_replicas):
    """The pool-direct paged path, incl. per-replica page pools
    (ReplicaGroupPlan serving): page axis sharded over 'data', tables
    rebased per shard — the exact composition that has never run
    outside interpret mode."""
    mesh = _mesh((2, 2), ("data", "model"))
    b, pages_per_seq, pool_pages = 4, 4, 16
    q = jnp.zeros((b, 1, H, D), jnp.bfloat16)
    kp = jnp.zeros((pool_pages, PAGE, K, D), jnp.bfloat16)
    vp = jnp.zeros((pool_pages, PAGE, K, D), jnp.bfloat16)
    table = jnp.zeros((b, pages_per_seq), jnp.int32)
    valid = jnp.full((b,), 100, jnp.int32)

    def f(q, kp, vp, table, valid):
        out = pattn.paged_decode_spmd(mesh, q, kp, vp, table, valid,
                                      interpret=False,
                                      pool_replicas=pool_replicas)
        assert out is not None, "paged spmd declined supported layout"
        return out

    _lower_tpu(f, q, kp, vp, table, valid)

    qp = jnp.zeros((b, 128, H, D), jnp.bfloat16)
    offs = jnp.zeros((b,), jnp.int32)

    def g(q, kp, vp, table, offs, valid):
        out = pattn.paged_prefill_spmd(mesh, q, kp, vp, table, offs,
                                       valid, interpret=False,
                                       pool_replicas=pool_replicas)
        assert out is not None, "paged prefill spmd declined"
        return out

    _lower_tpu(g, qp, kp, vp, table, offs, valid)


# --- ragged paged attention (ISSUE 8) ---


def _ragged_args(t_blocks=4, n_seq=3, pages_per_seq=4, pool_pages=16):
    """A mixed flat buffer: seq 0 a 2-block prefill chunk, seq 1 a
    decode token (1 real row), the rest inert — the composition one
    ragged dispatch serves."""
    t = t_blocks * pattn.RAGGED_BLOCK_Q
    q = jnp.zeros((t, H, D), jnp.bfloat16)
    kp = jnp.zeros((pool_pages, PAGE, K, D), jnp.bfloat16)
    vp = jnp.zeros((pool_pages, PAGE, K, D), jnp.bfloat16)
    tables = jnp.zeros((n_seq, pages_per_seq), jnp.int32)
    seq_of_block = jnp.asarray(
        np.array([0, 0, 1, 2], np.int32)[:t_blocks])
    block_qstart = jnp.asarray(
        np.array([0, 8, 0, 0], np.int32)[:t_blocks])
    query_offsets = jnp.asarray(np.array([128, 200, 0], np.int32))
    kv_valid = jnp.asarray(np.array([144, 201, 1], np.int32))
    return q, kp, vp, tables, seq_of_block, block_qstart, \
        query_offsets, kv_valid


# (None, None) = llama/qwen; softcap = gemma-2; window = mistral —
# same flag matrix as the batched kernels: each switches real kernel
# code (tanh, window masks) inside the shared accumulate.
@pytest.mark.ragged_attn
@pytest.mark.parametrize("softcap,window", [(None, None), (30.0, None),
                                            (None, 64)])
def test_ragged_kernel_lowers(softcap, window):
    args = _ragged_args()

    def f(*a):
        return pattn.ragged_paged_attention(
            *a, sliding_window=window, softcap=softcap,
            interpret=False)

    _lower_tpu(f, *args)


@pytest.mark.ragged_attn
def test_ragged_spmd_lowers_on_model_mesh():
    """The SPMD head-sharded variant: kv heads on 'model', flat buffer
    and metadata replicated — the flash_attention_spmd pattern over the
    ragged kernel."""
    mesh = _mesh((1, 4), ("data", "model"))
    args = _ragged_args()

    def f(*a):
        out = pattn.ragged_paged_spmd(mesh, *a, interpret=False)
        assert out is not None, "ragged spmd declined supported layout"
        return out

    _lower_tpu(f, *args)


def test_ragged_spmd_declines_data_axis_and_bad_heads():
    """Fallback-decline units: a data-sharded mesh (the pool's page
    axis shards there — a flat buffer cannot mix replicas' rows) and a
    non-dividing head layout both return None, never a mis-sharded
    kernel; the engine records the reason and serves the prologue."""
    args = _ragged_args()
    mesh = _mesh((2, 2), ("data", "model"))
    assert pattn.ragged_paged_spmd(mesh, *args, interpret=False) is None
    mesh3 = _mesh((1, 3), ("data", "model"))
    assert pattn.ragged_paged_spmd(mesh3, *args,
                                   interpret=False) is None


def test_ragged_vmem_budget_declines_not_mosaic():
    """Oversized pool shapes must decline with a machine-readable
    reason BEFORE any pallas_call is emitted — the same no-Mosaic-
    failure-on-chip guarantee as the int4 plans."""
    assert pattn.ragged_decline_reason(PAGE, D, K, H // K) is None
    r = pattn.ragged_decline_reason(512, 512, 16, 16)
    assert r is not None and r.startswith("vmem:")
    r = pattn.ragged_decline_reason(96, D)
    assert r is not None and r.startswith("page_size:")
