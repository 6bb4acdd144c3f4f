"""Pallas TPU fused w4a16 matmul: dequantize int4 weights in VMEM, inside
the matmul, so HBM streams the PACKED bytes.

Why a kernel at all: the XLA path (models/common.py `_einsum` →
`dequant_int4`) expresses dequant as bitcast → convert → grouped-scale
multiply → reshape and hopes XLA fuses that chain into the dot's operand
read. On real TPU it does not: int4 decode ran at 22.9 tok/s
(interleave layout) then 31.6 tok/s (bitcast layout) against bf16's 130
and int8's 205 (measured once before PR 1; not re-measured) — the
dequantized bf16 weight
was materialized (and copied) in HBM every token, so int4 streamed MORE
bytes than bf16. int8 escapes because its dequant is a plain
convert (fusable operand) plus an OUTPUT-side scale; int4's grouped
scale multiplies the weight on the CONTRACTED side of the dot and XLA
TPU will not fold a multiply-by-different-shaped-operand into a dot
input. (Reference compute equivalent: llama.cpp's q4 kernels, reached
through src/adapters/local-llm.ts — its default serving precision —
dequantize in registers for exactly this reason.)

These kernels make the fusion structural instead of heuristic. The pack
layout (engine/quant.py: two signed nibbles per byte along the weight's
LAST axis, even element in the low nibble, per-`group` scales) was
chosen so NO shuffle is ever needed in-kernel:

- `_mm_pack_out` — every per-layer matmul (qkv/o/gate/up/down: the
  packed last axis is a NON-contracted output axis). Byte k of a row
  holds output columns 2k (low nibble) and 2k+1 (high), and both share
  scale group k // (g/2). The kernel extracts nibbles with two
  arithmetic shifts, applies the group scale, and runs TWO dots — one
  producing even output columns, one odd — accumulating over contraction
  blocks in VMEM scratch. The only reorder is interleaving the two
  [bm, bp] OUTPUT accumulators at the end: 2·bm·bp elements once per
  output block, vs. the E·F weight interleave the XLA path choked on.
- `_mm_pack_contract` — the tied-embedding lm head ([V, E] packed along
  E, which the head matmul CONTRACTS). Splitting the ACTIVATION into
  even/odd columns (x[:, 0::2], x[:, 1::2] — a [M, E] strided slice,
  done once outside the kernel) turns the matmul into
  dot(x_even, low^T) + dot(x_odd, high^T): no weight interleave, no
  output interleave, scale group k // (g/2) again shared.

`einsum_int4` is the dispatch seam `_einsum` calls: it classifies the
einsum spec (contracted axes a prefix of the weight → pack-on-output;
suffix → pack-on-contraction), flattens to 2-D, pads M to sublane
multiples, and declines (with a machine-readable reason — the
`fallback_reason` the engine's path-provenance report and the benches
surface) whenever blocking/grouping/VMEM cannot be arranged — the
caller then falls back to the XLA dequant path, so MoE expert matmuls
("bte,xef->btxf") and tiny routers serve unchanged. Every dispatch is
budgeted against `_VMEM_BUDGET` BEFORE the pallas_call is emitted, so
no shape can reach a Mosaic VMEM failure on chip. These are DECODE
kernels: M is capped at 64 rows (decode and the post-last_pos-gather
lm head are always ≤ batch), because the grid iterates p innermost so
grouped scales stream once per contraction block — which makes the f32
output block round-trip per contraction block, negligible at decode M
and ruinous at prefill M. Prefill int4 keeps the XLA path, where the
materialized dequant amortizes over T.

Multi-device (the ISSUE 3 tentpole): a pallas_call inside jit-under-
GSPMD is an opaque unpartitionable custom call, so the kernels CANNOT
simply run on a sharded mesh — `einsum_int4_spmd` instead partitions
the matmul the way sharding.param_specs already shards the weight
(megatron column-parallel for qkv/gate/up/lm-head — each shard computes
its own output slice, no collective; row-parallel for o/down — each
shard contracts its input slice and one psum over the "model" axis
combines, exactly the all-reduce the XLA path's sharded einsum inserts)
and runs the single-device kernel per shard inside `shard_map` (via
engine/compat.py's seam). The plan is checked against the
PER-SHARD shapes before entering shard_map, so the body's dispatch
never declines mid-trace; a weight axis the mesh does not divide is
served replicated (matching sharding._fallback_replicated's placement,
so the in_specs never force a per-dispatch weight regather). On non-TPU
backends the kernels run in Pallas interpret mode when forced via
ROUNDTABLE_INT4_MM=1 — how the CPU suite validates them, single-device
and sharded (tests/test_int4mm.py).
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
    """Kernel path on by default on real TPU; ROUNDTABLE_INT4_MM=1
    forces it elsewhere (interpret mode — the test path), =0 disables
    everywhere (the A/B lever for microbenches)."""
    v = os.environ.get("ROUNDTABLE_INT4_MM", "")
    if v == "0":
        return False
    if v == "1":
        return True
    return jax.default_backend() == "tpu"


def _pick_block(n: int, candidates: tuple[int, ...],
                multiple_of: int = 1) -> Optional[int]:
    for c in candidates:
        if n % c == 0 and c % multiple_of == 0:
            return c
    return None


def _nibbles(q_ref, dtype):
    """int8 packed byte block → (low, high) int4 values in `dtype`.
    Arithmetic shifts in int32 sign-extend both nibbles; no shuffle."""
    q = q_ref[...].astype(jnp.int32)
    low = ((q << 28) >> 28).astype(dtype)
    high = (q >> 4).astype(dtype)
    return low, high


def _mm_out_kernel(x_ref, q_ref, s_ref, o_ref, acc_lo, acc_hi, *,
                   gp: int, bg: int, bp: int, n_c: int):
    # Grid is (m, c, p) with p INNERMOST: the whole-axis scale block's
    # index (c, 0) is then constant across each p sweep, so Pallas
    # elides its DMA and scales stream once per contraction block —
    # with p outside c they re-streamed every step, ~doubling HBM
    # traffic on the up/gate shape. The price: accumulators span the
    # FULL output axis (scratch [bm, P] per nibble, ≤ 8 MB at the
    # largest bm·P), and each (c==last, p) step flushes its slice.
    c, j = pl.program_id(1), pl.program_id(2)
    x = x_ref[...]
    low, high = _nibbles(q_ref, x.dtype)
    # s_ref carries the FULL scale axis for this contraction block
    # (Mosaic wants lane-aligned or whole-axis block minors; the per-p
    # slab bg = bp/gp is narrower than a lane) — slice it here.
    s = s_ref[:, pl.ds(j * bg, bg)]
    srep = jnp.repeat(s, gp, axis=1)               # [bc, bp]
    dims = (((1,), (0,)), ((), ()))
    lo = jax.lax.dot_general(x, low * srep, dims,
                             preferred_element_type=jnp.float32)
    hi = jax.lax.dot_general(x, high * srep, dims,
                             preferred_element_type=jnp.float32)
    sl = pl.ds(j * bp, bp)

    @pl.when(c == 0)
    def _set():
        acc_lo[:, sl] = lo
        acc_hi[:, sl] = hi

    @pl.when(c > 0)
    def _add():
        acc_lo[:, sl] += lo
        acc_hi[:, sl] += hi

    @pl.when(c == n_c - 1)
    def _done():
        a_lo, a_hi = acc_lo[:, sl], acc_hi[:, sl]
        bm = a_lo.shape[0]
        # interleave OUTPUT columns: even ← low nibble, odd ← high
        o_ref[...] = jnp.stack([a_lo, a_hi], axis=-1).reshape(bm, 2 * bp)


@functools.partial(jax.jit,
                   static_argnames=("gp", "bm", "bp", "bc", "interpret"))
def _mm_pack_out(x, q4, s4, gp: int, bm: int, bp: int, bc: int,
                 interpret: bool):
    """x [M, C] · unpack(q4 [C, P], s4 [C, P//gp]) → [M, 2P] f32."""
    m, c_dim = x.shape
    _, p_dim = q4.shape
    grid = (m // bm, c_dim // bc, p_dim // bp)
    kernel = functools.partial(_mm_out_kernel, gp=gp, bg=bp // gp,
                               bp=bp, n_c=grid[1])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bc), lambda i, k, j: (i, k)),
            pl.BlockSpec((bc, bp), lambda i, k, j: (k, j)),
            pl.BlockSpec((bc, p_dim // gp), lambda i, k, j: (k, 0)),
        ],
        out_specs=pl.BlockSpec((bm, 2 * bp), lambda i, k, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, 2 * p_dim), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bm, p_dim), jnp.float32),
            pltpu.VMEM((bm, p_dim), jnp.float32),
        ],
        interpret=interpret,
    )(x, q4, s4)


def _mm_contract_kernel(xe_ref, xo_ref, q_ref, s_ref, o_ref, *, gp: int):
    xe, xo = xe_ref[...], xo_ref[...]
    low, high = _nibbles(q_ref, xe.dtype)
    srep = jnp.repeat(s_ref[...], gp, axis=1)      # [bn, Cp]
    dims = (((1,), (1,)), ((), ()))                # contract minor×minor
    o_ref[...] = (
        jax.lax.dot_general(xe, low * srep, dims,
                            preferred_element_type=jnp.float32)
        + jax.lax.dot_general(xo, high * srep, dims,
                              preferred_element_type=jnp.float32))


@functools.partial(jax.jit,
                   static_argnames=("gp", "bm", "bn", "interpret"))
def _mm_pack_contract(x_even, x_odd, q4, s4, gp: int, bm: int, bn: int,
                      interpret: bool):
    """x_even/x_odd [M, Cp] · unpack(q4 [N, Cp], s4 [N, Cp//gp])ᵀ
    → [M, N] f32. Contraction fits one block (lm-head E is small)."""
    m, cp = x_even.shape
    n_dim = q4.shape[0]
    kernel = functools.partial(_mm_contract_kernel, gp=gp)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n_dim // bn),
        in_specs=[
            pl.BlockSpec((bm, cp), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, cp), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, cp), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, cp // gp), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n_dim), jnp.float32),
        interpret=interpret,
    )(x_even, x_odd, q4, s4)


def _classify(spec: str, leaf):
    """Classify an einsum spec against a packed leaf: ((mode, n_cont,
    gp), None) with mode "out" (weight = contracted-prefix + kept, pack
    axis kept-minor) or "contract" (kept + one contracted pack axis —
    the tied lm head), or (None, reason) when the kernels cannot serve
    the spec at all. Reasons are stable strings — they surface as the
    `fallback_reason` in path-provenance reports."""
    lhs, out_dims = spec.split("->")
    a_dims, b_dims = lhs.split(",")
    cont = [d for d in b_dims if d in a_dims]
    kept = [d for d in b_dims if d not in a_dims]
    if not cont or not kept:
        return None, "spec:no-contraction-or-kept"
    if a_dims[-len(cont):] != "".join(cont):
        return None, "spec:cont-not-activation-suffix"
    batch = a_dims[:-len(cont)]
    if out_dims != batch + "".join(kept):
        return None, "spec:out-layout"
    if leaf.axis != leaf.q4.ndim - 1:
        # non-minor pack: fall back (XLA path asserts loudly)
        return None, "pack:non-minor-axis"
    if leaf.group % 2:
        return None, "pack:odd-group"
    gp = leaf.group // 2
    if list(b_dims) == cont + kept:
        return ("out", len(cont), gp), None
    if list(b_dims) == kept + cont and len(cont) == 1:
        return ("contract", 1, gp), None
    return None, "spec:mixed-kept-contracted"   # MoE expert layouts


# What the v5e's compiler (Mosaic, JAX 0.9.0) answers each kernel at real
# Int4Leaf shapes — reproduced without a chip by the xfail(strict) cases
# of tests/test_chip_compile.py, which tell the repair PR when an entry
# may go. While an entry stands, the plan declines with it wherever the
# kernel would be compiled for the chip, so describe()["int4_paths"]
# carries the reason from the first trace and no dispatch ever reaches
# the compiler's refusal (a runtime degradation rung).
MOSAIC_REFUSAL = {
    "out": "cannot statically prove that index in dimension 1 is a "
           "multiple of 128 (vector.load of the scale block's lane "
           "slice, s_ref[:, pl.ds(j * bg, bg)])",
    "contract": "infer-vector-layout: unsupported shape cast "
                "(tpu.reshape of the scale block to [bn, G, 1] in "
                "jnp.repeat)",
}


def _mosaic_refusal(mode: str) -> Optional[str]:
    """`mosaic:<the compiler's message>` when this kernel would be
    compiled for the chip and the compiler is known to refuse it; None
    in interpret mode (the CPU parity suites) or once it compiles."""
    if _interpret() or mode not in MOSAIC_REFUSAL:
        return None
    return f"mosaic:{MOSAIC_REFUSAL[mode]}"


def _plan_rows(m_rows: int) -> Optional[int]:
    """Padded block_m for m_rows, or None above 64: the kernels are
    DECODE kernels (weight-streaming-bound GEMVs, where fused dequant
    is the whole win). Prefill's big-M matmuls keep the XLA path —
    there the materialized dequant amortizes over T, while the
    write-at-last output revisiting would round-trip the [M, 2P] f32
    output once per contraction block."""
    mp = max(8, -(-m_rows // 8) * 8)
    return None if mp > 64 else mp


def _plan_pack_out(m_rows: int, c_dim: int, p_dim: int, gp: int):
    """((bm, bp, bc), None) or (None, reason) for the pack-on-output
    kernel at these (possibly per-shard) dims. Block search walks the
    candidates until the working set fits `_VMEM_BUDGET`, so a plan is
    emitted only for shapes Mosaic can actually allocate."""
    refused = _mosaic_refusal("out")
    if refused:
        return None, refused
    bm = _plan_rows(m_rows)
    if bm is None:
        return None, "rows:prefill-m"
    for bp in (512, 256, 128):
        if p_dim % bp or bp % gp:
            continue
        for bc in (512, 1024, 256, 128):
            if c_dim % bc:
                continue
            if _pack_out_vmem_est(bm, bp, bc, p_dim, gp) <= _VMEM_BUDGET:
                return (bm, bp, bc), None
    if (_pick_block(p_dim, (512, 256, 128), multiple_of=gp) is None
            or _pick_block(c_dim, (512, 1024, 256, 128)) is None):
        return None, "blocks:unblockable"
    return None, "vmem:pack-out"


def _plan_pack_contract(m_rows: int, cp: int, n_dim: int, gp: int):
    """((bm, bn), None) or (None, reason) for the pack-on-contraction
    kernel. The whole (packed) contraction rides one block, so the
    budget check shrinks bn until the x/q/s working set fits — replacing
    the old magic `cp > 4096` gate with an actual per-shape estimate."""
    refused = _mosaic_refusal("contract")
    if refused:
        return None, refused
    bm = _plan_rows(m_rows)
    if bm is None:
        return None, "rows:prefill-m"
    if cp % 128 or cp % gp:
        return None, "blocks:cp-misaligned"
    for bn in (512, 256, 128):
        if n_dim % bn:
            continue
        if _pack_contract_vmem_est(bm, bn, cp, gp) <= _VMEM_BUDGET:
            return (bm, bn), None
    if _pick_block(n_dim, (512, 256, 128)) is None:
        return None, "blocks:unblockable"
    return None, "vmem:pack-contract"


def einsum_int4(spec: str, a: jax.Array, leaf) -> Optional[jax.Array]:
    """Run `jnp.einsum(spec, a, dequant(leaf))` through the fused
    kernels when the spec/shape/grouping allow; None → caller falls
    back to the XLA dequant path. Result is f32 (matches the XLA path's
    preferred_element_type)."""
    return einsum_int4_or_reason(spec, a, leaf)[0]


def einsum_int4_or_reason(spec: str, a: jax.Array, leaf):
    """(result, None) on the kernel path, (None, fallback_reason) when
    this dispatch declines — the reason feeds the engine's
    path-provenance report so a silent XLA fallback is attributable."""
    cls, reason = _classify(spec, leaf)
    if cls is None:
        return None, reason
    mode, n_cont, gp = cls
    if mode == "out":
        return _dispatch_pack_out(a, leaf, n_cont, gp)
    return _dispatch_pack_contract(a, leaf, gp)


def plan_reason(spec: str, a_shape: tuple, leaf) -> Optional[str]:
    """Why `einsum_int4` would decline this dispatch (None = kernel
    path) — shape-only, no arrays traced: the benches use it to emit
    `fallback_reason` provenance without burning a dispatch."""
    cls, reason = _classify(spec, leaf)
    if cls is None:
        return reason
    mode, n_cont, gp = cls
    a_size = 1
    for s in a_shape:
        a_size *= s
    q4 = leaf.q4
    if mode == "out":
        c_dim = 1
        for s in q4.shape[:n_cont]:
            c_dim *= s
        return _plan_pack_out(a_size // c_dim, c_dim, q4.size // c_dim,
                              gp)[1]
    cp = q4.shape[-1]
    return _plan_pack_contract(a_size // (2 * cp), cp, q4.size // cp,
                               gp)[1]


# Mirror of attention._VMEM_BUDGET: a conservative per-core VMEM cap the
# kernel's resident working set must fit, else dispatch declines and the
# XLA dequant path serves. Advisor r5: _mm_pack_out's accumulators span
# the FULL output axis (scratch 2·[bm, P] f32 — the price of the
# p-innermost grid that streams scales once), so a large-enough mlp_dim
# overflowed Mosaic's scratch allocation ON CHIP instead of falling back.
_VMEM_BUDGET = 12 * 1024 * 1024


def _pack_out_vmem_est(bm: int, bp: int, bc: int, p_dim: int,
                       gp: int) -> int:
    scratch = 2 * bm * p_dim * 4          # f32 accumulators span full P
    x_blk = 2 * bm * bc * 4               # double-buffered, ≤ f32
    q_blk = 2 * bc * bp                   # packed int4 bytes
    s_blk = 2 * bc * (p_dim // gp) * 4    # whole-axis scale block
    out_blk = bm * 2 * bp * 4             # f32 output block
    return scratch + x_blk + q_blk + s_blk + out_blk


def _pack_contract_vmem_est(bm: int, bn: int, cp: int, gp: int) -> int:
    # the whole (packed) contraction axis rides one block per operand
    x_blk = 2 * 2 * bm * cp * 4           # x_even + x_odd, double-buffered
    q_blk = 2 * bn * cp                   # packed int4 bytes
    s_blk = 2 * bn * (cp // gp) * 4
    out_blk = bm * bn * 4                 # f32 output block
    return x_blk + q_blk + s_blk + out_blk


def _pad_to(x2: jax.Array, bm: int) -> jax.Array:
    m = x2.shape[0]
    return x2 if m == bm else jnp.pad(x2, ((0, bm - m), (0, 0)))


def _dispatch_pack_out(a, leaf, n_cont: int, gp: int):
    q4, s4 = leaf.q4, leaf.s4
    c_dim = 1
    for s in q4.shape[:n_cont]:
        c_dim *= s
    p_dim = q4.size // c_dim
    kept_shape = q4.shape[n_cont:-1] + (q4.shape[-1] * 2,)
    x2 = a.reshape(-1, c_dim)
    plan, reason = _plan_pack_out(x2.shape[0], c_dim, p_dim, gp)
    if plan is None:
        return None, reason
    bm, bp, bc = plan
    m = x2.shape[0]
    y = _mm_pack_out(_pad_to(x2, bm), q4.reshape(c_dim, p_dim),
                     s4.reshape(c_dim, p_dim // gp), gp, bm, bp, bc,
                     _interpret())
    return y[:m].reshape(a.shape[:-n_cont] + kept_shape), None


def _dispatch_pack_contract(a, leaf, gp: int):
    q4, s4 = leaf.q4, leaf.s4
    cp = q4.shape[-1]
    n_dim = q4.size // cp
    x2 = a.reshape(-1, 2 * cp)
    plan, reason = _plan_pack_contract(x2.shape[0], cp, n_dim, gp)
    if plan is None:
        return None, reason
    bm, bn = plan
    m = x2.shape[0]
    x_even = _pad_to(x2[:, 0::2], bm)
    x_odd = _pad_to(x2[:, 1::2], bm)
    y = _mm_pack_contract(x_even, x_odd, q4.reshape(n_dim, cp),
                          s4.reshape(n_dim, cp // gp), gp, bm, bn,
                          _interpret())
    return y[:m].reshape(a.shape[:-1] + q4.shape[:-1]), None


# --- shard-aware dispatch (multi-device meshes) ---


def einsum_int4_spmd(mesh, spec: str, a: jax.Array, leaf, tp=None):
    """The fused kernels under a multi-device mesh: per-shard
    single-device dispatch inside shard_map, partitioned
    the way sharding.param_specs already shards the weight.

    `tp` is the call site's TP convention hint ("col" / "row" — see
    sharding.int4_shard_axis); it picks WHICH weight axis carries the
    model shards so the shard_map in_specs match the weights' resident
    placement (a mismatched spec would regather the weight every
    dispatch — the one thing a weight-streaming-bound decode cannot
    afford). Returns (result, None) or (None, fallback_reason):

    - the plan is validated against the PER-SHARD shapes before the
      shard_map is entered, so the body's dispatch never declines (and
      no shape can reach a Mosaic VMEM failure on chip);
    - a weight axis the mesh does not divide is served replicated —
      matching sharding._fallback_replicated, which replicated exactly
      those weights at placement time;
    - row-parallel shards contract locally and psum over "model",
      exactly the all-reduce the XLA path's sharded einsum inserts."""
    from jax.sharding import PartitionSpec as P

    from ..compat import shard_map
    from ..sharding import MODEL_AXIS, int4_shard_axis, model_axis_size

    cls, reason = _classify(spec, leaf)
    if cls is None:
        return None, reason
    mode, n_cont, gp = cls
    q4, s4 = leaf.q4, leaf.s4
    m_shards = model_axis_size(mesh)

    w_ax, needs_psum = int4_shard_axis(tp, q4.ndim, n_cont, mode)
    if m_shards <= 1:
        w_ax, needs_psum = None, False
    if w_ax is not None and (q4.shape[w_ax] % m_shards
                             or s4.shape[w_ax] % m_shards):
        # Mirrors _fallback_replicated: a dim the mesh doesn't divide
        # was REPLICATED at placement, so replicated in_specs match.
        w_ax, needs_psum = None, False

    div = m_shards if w_ax is not None else 1
    if mode == "out":
        c_dim = 1
        for s in q4.shape[:n_cont]:
            c_dim *= s
        p_dim = q4.size // c_dim
        m_rows = a.size // c_dim
        c_local = c_dim // (div if (w_ax is not None and w_ax < n_cont)
                            else 1)
        p_local = p_dim // (div if (w_ax is not None and w_ax >= n_cont)
                            else 1)
        plan, reason = _plan_pack_out(m_rows, c_local, p_local, gp)
    else:
        cp = q4.shape[-1]
        n_dim = q4.size // cp
        m_rows = a.size // (2 * cp)
        plan, reason = _plan_pack_contract(m_rows, cp, n_dim // div, gp)
    if plan is None:
        return None, (reason if w_ax is None else reason + "/sharded")

    def ax_spec(ndim: int, ax: Optional[int]) -> P:
        return P(*[MODEL_AXIS if i == ax else None for i in range(ndim)])

    w_spec = ax_spec(q4.ndim, w_ax)
    s_spec = ax_spec(s4.ndim, w_ax)
    if mode == "out":
        out_ndim = (a.ndim - n_cont) + (q4.ndim - n_cont)
        a_ax = (a.ndim - n_cont + w_ax) \
            if (w_ax is not None and w_ax < n_cont) else None
        out_ax = ((a.ndim - n_cont) + (w_ax - n_cont)) \
            if (w_ax is not None and w_ax >= n_cont) else None
    else:
        out_ndim = a.ndim
        a_ax = None
        out_ax = (a.ndim - 1) if w_ax is not None else None
    a_spec = ax_spec(a.ndim, a_ax)
    out_spec = ax_spec(out_ndim, out_ax)

    from ..models.common import Int4Leaf

    def body(al, q4l, s4l):
        leaf_l = Int4Leaf(q4=q4l, s4=s4l, axis=leaf.axis,
                          group=leaf.group)
        if mode == "out":
            y, why = _dispatch_pack_out(al, leaf_l, n_cont, gp)
        else:
            y, why = _dispatch_pack_contract(al, leaf_l, gp)
        if y is None:   # unreachable: plan checked on these exact shapes
            raise AssertionError(f"sharded int4 dispatch declined: {why}")
        if needs_psum:
            y = jax.lax.psum(y, MODEL_AXIS)
        return y

    fn = shard_map(body, mesh=mesh, in_specs=(a_spec, w_spec, s_spec),
                   out_specs=out_spec, check_vma=False)
    return fn(a, q4, s4), None
