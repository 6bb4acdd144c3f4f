"""Pallas TPU grouped batched LoRA matmul (ISSUE 10).

The multi-LoRA serving path adds, per target projection, a low-rank
delta `x @ A_id^T @ B_id` on top of the shared base matmul, where `id`
is each ROW's adapter slot (0 = the zero "base" adapter). The XLA
baseline (engine/lora.py `_xla_grouped`) is a masked dense BMM over the
whole adapter stack — correct everywhere, but it computes every slot's
first matmul for every row. This module is the fast path: a
scalar-prefetched BGMV (batched gather matrix-vector) kernel in the
mold of Punica/S-LoRA's grouped kernels — per-row adapter ids steer the
A/B block DMAs, so each grid row streams ONLY its own adapter's tensors
from HBM, and consecutive rows sharing an adapter (a ragged buffer's
per-sequence runs, a co-batched session's rows) elide the re-fetch
entirely: Pallas skips a block DMA whose index map output is unchanged,
which is exactly the "grouped" property without a host-side sort.

Layouts (chosen so no in-kernel shuffle is ever needed, the int4mm
rule): A is stored TRANSPOSED as `a_t [S, r, C]` (lane dim = the
contraction C, 128-aligned for every real embed/hidden dim) and B as
`b_s [S, r, O]` (lane dim = the output axis). The kernel computes
`xa = x · a_t[id]^T` (contract C) then `xa · b_s[id]` (contract r) in
one grid step per (row, output-block).

Dispatch discipline mirrors pallas/int4mm exactly:

- `plan_bgmv` validates blocking/alignment/VMEM BEFORE any pallas_call
  is emitted, returning a machine-readable decline reason — no shape
  can reach a Mosaic failure on chip, and every decline surfaces as
  `fallback_reason` in the engine's `lora_paths` provenance sink.
- rows are capped at 64 ("rows:prefill-m"): these are DECODE kernels.
  Prefill's big-M dispatches keep the XLA grouped path, where the
  masked dense BMM amortizes over T (LoRA FLOPs are ~r/C of the base
  matmul — noise next to prefill compute).
- `lora_bgmv_spmd` runs the single-device kernel per shard inside
  shard_map, partitioned the way sharding.lora_stack_specs places the
  stacked tensors (megatron column-parallel: B's output axis sharded,
  no collective; row-parallel: A's contraction axis sharded + one psum
  over "model" — the same all-reduce the base matmul's sharded einsum
  inserts). Plans are validated against the PER-SHARD shapes before
  entering shard_map.
- on non-TPU backends the kernel runs in interpret mode when forced
  via ROUNDTABLE_LORA_MM=1 — how the CPU suite validates it.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def enabled() -> bool:
    """Kernel path on by default on real TPU; ROUNDTABLE_LORA_MM=1
    forces it elsewhere (interpret mode — the test path), =0 disables
    everywhere (the A/B lever, mirroring ROUNDTABLE_INT4_MM)."""
    v = os.environ.get("ROUNDTABLE_LORA_MM", "")
    if v == "0":
        return False
    if v == "1":
        return True
    return jax.default_backend() == "tpu"


# Mirror of int4mm._VMEM_BUDGET: the resident working set must fit or
# the dispatch declines to the XLA grouped path.
_VMEM_BUDGET = 12 * 1024 * 1024

# Decode kernels only — the int4mm._plan_rows rule. One grid step per
# row is a GEMV; past this many rows the XLA masked BMM amortizes
# better and the grid bookkeeping stops paying for itself.
_MAX_ROWS = 64


def _bgmv_vmem_est(m: int, c_dim: int, r: int, bo: int) -> int:
    # whole-array x block + per-id a/b blocks (double-buffered) + the
    # whole-rows out block, sized at 4 B/elt (>= any real dtype)
    x_blk = m * c_dim * 4
    a_blk = 2 * r * c_dim * 4
    b_blk = 2 * r * bo * 4
    out_blk = m * bo * 4
    return x_blk + a_blk + b_blk + out_blk


def plan_bgmv(m_rows: int, c_dim: int, r: int, o_dim: int):
    """((bo,), None) or (None, reason) for a grouped BGMV at these
    dims. Reasons are stable strings — they surface as the
    `fallback_reason` in the engine's lora_paths provenance."""
    if m_rows > _MAX_ROWS:
        return None, "rows:prefill-m"
    if r < 1 or r > 512:
        return None, "rank:unsupported"
    if c_dim % 128:
        return None, "dims:contract-misaligned"
    if o_dim % 128:
        return None, "dims:out-misaligned"
    for bo in (512, 256, 128):
        if o_dim % bo:
            continue
        if _bgmv_vmem_est(m_rows, c_dim, r, bo) <= _VMEM_BUDGET:
            return (bo,), None
    return None, "vmem:bgmv"


def _bgmv_kernel(ids_ref, x_ref, a_ref, b_ref, o_ref):
    # One grid step = one (output-block, row): xa = x_i · a^T (contract
    # the lane axis C), then xa · b (contract r). Both products in f32
    # on the MXU; the row's adapter blocks were DMA'd by the
    # scalar-prefetched index maps below. x and out ride WHOLE-array
    # blocks (Mosaic rejects 1-sublane row blocks on a taller array):
    # their index maps are constant per inner sweep, so the x DMA
    # happens once and the out block flushes once per output block.
    i = pl.program_id(1)
    x = x_ref[pl.ds(i, 1), :]          # [1, C] — this row
    a = a_ref[0]                       # [r, C]
    b = b_ref[0]                       # [r, bo]
    xa = jax.lax.dot_general(x, a, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    o_ref[pl.ds(i, 1), :] = jax.lax.dot_general(
        xa.astype(x.dtype), b, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bo", "interpret"))
def _bgmv(ids, x2, a_t, b_s, bo: int, interpret: bool):
    """ids [M] int32, x2 [M, C], a_t [S, r, C], b_s [S, r, O] →
    delta [M, O] f32. Grid (O/bo, M) with the ROW innermost: the out
    block's index is constant across the inner sweep (one flush per
    output block, every row written exactly once), and the id of row i
    steers the A/B block index maps — identical consecutive ids elide
    the DMA, which is the grouped property."""
    m, c_dim = x2.shape
    o_dim = b_s.shape[2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(o_dim // bo, m),
        in_specs=[
            pl.BlockSpec((m, c_dim), lambda j, i, ids: (0, 0)),
            pl.BlockSpec((1, a_t.shape[1], c_dim),
                         lambda j, i, ids: (ids[i], 0, 0)),
            pl.BlockSpec((1, b_s.shape[1], bo),
                         lambda j, i, ids: (ids[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((m, bo), lambda j, i, ids: (0, j)),
    )
    return pl.pallas_call(
        _bgmv_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, o_dim), jnp.float32),
        interpret=interpret,
    )(ids.astype(jnp.int32), x2, a_t, b_s)


def lora_bgmv_or_reason(x2: jax.Array, a_t: jax.Array, b_s: jax.Array,
                        ids: jax.Array):
    """(delta [M, O] f32, None) on the kernel path, (None, reason) when
    this dispatch declines — the caller then serves the XLA grouped
    path and records the reason (the einsum_int4_or_reason contract)."""
    m, c_dim = x2.shape
    s, r, o_dim = b_s.shape
    plan, reason = plan_bgmv(m, c_dim, r, o_dim)
    if plan is None:
        return None, reason
    (bo,) = plan
    return _bgmv(ids, x2, a_t, b_s, bo, _interpret()), None


# --- shard-aware dispatch (multi-device meshes) ---


def lora_bgmv_spmd(mesh, x2: jax.Array, a_t: jax.Array, b_s: jax.Array,
                   ids: jax.Array, tp: Optional[str] = None):
    """The grouped kernel under a multi-device mesh: per-shard
    single-device dispatch inside shard_map, partitioned the way
    sharding.lora_stack_specs places the stacked tensors (the
    einsum_int4_spmd sibling).

    tp="col" (q/k/v, gate/up): B's OUTPUT axis carries the model
    shards — each shard computes its own delta slice, no collective.
    tp="row" (o_proj, down_proj): A's CONTRACTION axis carries them —
    per-shard partial deltas combine with one psum over "model",
    exactly the all-reduce the base matmul's sharded einsum inserts.
    A dim the mesh does not divide is served replicated (matching
    sharding._fallback_replicated placement). Returns
    (delta, None) or (None, fallback_reason)."""
    from jax.sharding import PartitionSpec as P

    from ..compat import shard_map
    from ..sharding import MODEL_AXIS, lora_shard_axis, model_axis_size

    m, c_dim = x2.shape
    s, r, o_dim = b_s.shape
    m_shards = model_axis_size(mesh)

    which = lora_shard_axis(tp)
    if m_shards <= 1:
        which = None
    if which == "out" and o_dim % m_shards:
        which = None
    if which == "in" and c_dim % m_shards:
        which = None

    div = m_shards if which is not None else 1
    c_local = c_dim // (div if which == "in" else 1)
    o_local = o_dim // (div if which == "out" else 1)
    plan, reason = plan_bgmv(m, c_local, r, o_local)
    if plan is None:
        return None, (reason if which is None else reason + "/sharded")
    (bo,) = plan

    x_spec = P(None, MODEL_AXIS if which == "in" else None)
    a_spec = P(None, None, MODEL_AXIS if which == "in" else None)
    b_spec = P(None, None, MODEL_AXIS if which == "out" else None)
    out_spec = P(None, MODEL_AXIS if which == "out" else None)

    def body(ids_l, x_l, a_l, b_l):
        y = _bgmv(ids_l, x_l, a_l, b_l, bo, _interpret())
        if which == "in":
            y = jax.lax.psum(y, MODEL_AXIS)
        return y

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(None), x_spec, a_spec, b_spec),
                   out_specs=out_spec, check_vma=False)
    return fn(ids.astype(jnp.int32), x2, a_t, b_s), None
