"""Pallas TPU attention kernels for the serving hot path.

Replaces the dense softmax(QK^T)V in models/common.py for the two op shapes
that dominate serving (SURVEY.md §7.3 hard part 1 — ragged per-knight KV
slots; reference compute equivalent: llama.cpp attention reached through
src/adapters/local-llm.ts):

- flash_prefill_attention: blockwise online-softmax attention for prefill
  chunks against a position-aligned KV cache. The dense path materializes
  [B, H, T, S] logits against the FULL cache every chunk; this kernel
  streams KV blocks through VMEM and — via scalar-prefetched per-row valid
  lengths — never fetches blocks beyond a row's causal/valid frontier.
- ragged_decode_attention: single-position decode attention over the padded
  cache. Rows with valid=600 in an S=8192 cache read 600 tokens of KV, not
  8192: the kv-block index map clamps to the row's frontier, and Pallas
  elides the DMA when consecutive grid steps map to the same block (the
  grid step itself is still paid; the paged decode walk pays none).
- paged_decode_attention: the same ragged decode DIRECTLY against the page
  POOL [P, page_size, K, D] (engine/paging.py), so decode never
  materializes the position-aligned [B, S, K, D] gather view — during
  decode the pool keeps its whole resident-memory advantage (the
  gather view copies every row's whole max_seq_len span out). It is a
  WALK, not a grid over the table's width: a grid step loops over a
  block of batch rows, and each row loops over the pages it holds
  (table[b, lo..hi] from the scalar-prefetched page table), a few pages
  a trip, copied HBM -> VMEM by explicit double-buffered DMAs while the
  trip before is multiplied. A page is copied flattened to [ps*K, D] and
  multiplied as it lies, all q heads at once with foreign kv heads
  masked — no per-head strided read (see "the walk" below).

Both kernels handle GQA natively (kv head = q head // group) so the
[B, S, K, D] cache is never repeated to [B, S, H, D] in HBM, and support
Mistral's sliding window and Gemma-2-style logit softcap.

On non-TPU backends the kernels run in Pallas interpret mode — this is how
the CPU test suite validates them against the dense reference path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..models.common import MASK_VALUE as NEG_INF

_LANES = 128  # TPU lane width; m/l scratch is replicated across lanes


def _dequant_kv(x, s, kv_bits: int, dtype):
    """In-kernel dequant of one KV block (ISSUE 11): payload [bkv, Dp]
    int8 + per-cell scales [bkv, G] f32 -> values [bkv, D] in `dtype`.
    int4 payloads unpack through kv_quant.unpack_int4 (the ONE copy of
    the nibble-order contract — shift arithmetic, which Mosaic lowers
    but whose interleaving reshape the v5e compiler then refuses:
    MOSAIC_INT4_KV_REFUSAL below, so int4 pools decline at plan time on
    the chip); the grouped scale multiply repeats each group's scale
    along the lanes (the [bkv, G, D/G] reshape compiles only for a
    block that came from a strided read). This is the kernel-side
    twin of kv_quant.dequantize_cells — same unpack, same scale
    math, so the kernel and XLA fallback cannot drift."""
    if kv_bits == 4:
        from ..kv_quant import unpack_int4
        x = unpack_int4(x)
    d = x.shape[-1]
    per_group = d // s.shape[-1]
    return (x.astype(jnp.float32)
            * jnp.repeat(s.astype(jnp.float32), per_group, axis=-1)) \
        .astype(dtype)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pool_heads(pool) -> int:
    """kv heads of a page pool [P, ps, K, D]. A LATENT pool is [P, ps, W]:
    one head, whose values are the first `v_dim` columns of its keys
    (absorbed multi-head latent attention — the paged kernels then take
    `v_pool=None` and copy each page once)."""
    return pool.shape[2] if pool.ndim == 4 else 1


# --- heads narrower than a lane row ---
#
# XLA tiles a bfloat16 pool [P, ps, K, D] (K, 128): at D = 64 every row of
# a page is padded to 128 lanes in HBM and the pool is twice its bytes.
# Such a pool is stored PACKED instead, `lane_pack` heads of one token
# side by side in a whole lane row: [P, ps, K / f, f * D] (a free reshape
# of what a layer writes: [.., K, D] -> [.., K / f, f * D]). To every
# kernel below that IS a pool of K / f heads of f * D, and they serve it
# unchanged — the wrappers hand them queries that carry their own head's
# D values in its part of the row and zeros in the rest (`_pack_queries`),
# so a score is the product with the own head alone, the softmax is the
# own head's, and of the weighted sum's f * D columns the own head's part
# is kept (`_own_part`). The group grows f times (the q heads of f kv
# heads share a row), the MXU multiplies f times the columns, and the
# bytes a walk copies are the heads' own.


def lane_pack(kh: int, d: int) -> int:
    """Heads of one token that share a 128-lane row of a pool: 2 for
    64-wide heads that come in pairs, else 1 (the plain [K, D] cell)."""
    return 2 if 2 * d == _LANES and kh % 2 == 0 else 1


def _packed(d: int, k_pool, v_pool, k_scale) -> int:
    """`lane_pack` of a pool as it is handed over with queries of `d`:
    the pool's rows are f * d wide."""
    if v_pool is None or k_scale is not None or k_pool.shape[-1] == d:
        return 1
    f, rest = divmod(k_pool.shape[-1], d)
    if rest or f * d != _LANES:
        raise ValueError(f"a pool of rows {k_pool.shape[-1]} wide cannot "
                         f"hold heads of {d}")
    return f


def _pack_parts(h: int, rows: int, f: int):
    """[H, f] bool: the part of its pool row query head h's kv head lies
    in (`rows` pool rows a token: kv head k is part k % f of row
    k // f)."""
    part = (jnp.arange(h) // (h // (rows * f))) % f
    return part[:, None] == jnp.arange(f)[None, :]


def _pack_queries(q: jax.Array, f: int, rows: int) -> jax.Array:
    """q [..., H, D] -> [..., H, f * D]: each head's values in its own
    part of the row, zeros in the rest."""
    own = _pack_parts(q.shape[-2], rows, f)[..., None]     # [H, f, 1]
    return jnp.where(own, q[..., None, :], jnp.zeros((), q.dtype)) \
        .reshape(*q.shape[:-1], f * q.shape[-1])


def _own_part(out: jax.Array, f: int, rows: int) -> jax.Array:
    """out [..., H, f * D] -> [..., H, D]: each head's own part."""
    h = out.shape[-2]
    parts = out.reshape(*out.shape[:-1], f, out.shape[-1] // f)
    own = _pack_parts(h, rows, f)[..., None]
    return jnp.sum(jnp.where(own, parts, jnp.zeros((), out.dtype)),
                   axis=-2)


def _cache_packed(kernel, q, k, v, *args, **kw):
    """`kernel(q, k, v, ...)` over a position-aligned cache [B, S, K, D]
    of heads that pack: the cache viewed [B, S, K / f, f * D] (the
    kernels transpose it anyway), for the chip's sake alone."""
    f = lane_pack(k.shape[2], q.shape[-1])
    rows = k.shape[2] // f
    view = (k.shape[0], k.shape[1], rows, f * k.shape[3])
    return _own_part(kernel(_pack_queries(q, f, rows), k.reshape(view),
                            v.reshape(view), *args, **kw), f, rows)


def _on_packed(kernel, q, k_pool, v_pool, *args, **kw):
    """`kernel(q, k_pool, v_pool, ...)` over a packed pool, or None
    where the pool holds plain [K, D] cells (which the chip's kernels do
    not take at a width that packs: the rows would be padded)."""
    d, scaled, interpret = q.shape[-1], kw.get("k_scale"), kw.get("interpret")
    f = _packed(d, k_pool, v_pool, scaled)
    if f > 1:
        rows = k_pool.shape[-2]
        return _own_part(kernel(_pack_queries(q, f, rows), k_pool, v_pool,
                                *args, **kw), f, rows)
    if (v_pool is not None and scaled is None
            and lane_pack(k_pool.shape[-2], d) > 1
            and not (_interpret() if interpret is None else interpret)):
        raise ValueError(
            f"a pool of {d}-wide heads is stored "
            f"{lane_pack(k_pool.shape[-2], d)} heads a lane row "
            "(pallas/attention.py: lane_pack)")
    return None


def _page_block(k_ref, v_ref, khi: int, v_dim: Optional[int]):
    """(keys, values) of kv head `khi` from one page's block refs: a
    latent page [1, ps, W] (`v_dim`; no v ref) is keys and, in its first
    columns, values."""
    if v_dim is None:
        return k_ref[0, :, khi, :], v_ref[0, :, khi, :]
    k = k_ref[0]
    return k, k[:, :v_dim]


def _pick_block(n: int, candidates: tuple[int, ...]) -> Optional[int]:
    for c in candidates:
        if n % c == 0:
            return c
    return None


def spmd_partitionable(num_heads: int, num_kv_heads: int,
                       n_model: int) -> bool:
    """Can flash_attention_spmd partition this head layout over an n_model-
    way model axis? Single source of truth shared with the engine's
    _resolve_attn so config-time choice and kernel-time dispatch can't
    drift. True when q heads divide AND (kv heads divide, or MQA's single
    kv head replicates)."""
    if num_heads % n_model:
        return False
    return num_kv_heads % n_model == 0 or num_kv_heads == 1


def supported(t: int, s: int, d: int, kh: int = 1) -> bool:
    """Can the kernels serve these shapes? (TPU wants lane-aligned D, or
    heads that pair up into lane rows — `lane_pack`; any shape goes in
    interpret mode.)"""
    if _pick_block(s, (512, 256, 128, 64, 32, 16, 8)) is None:
        return False
    if t > 1 and _pick_block(t, (128, 64, 32, 16, 8)) is None:
        return False
    if not _interpret() and d % 128 != 0 and lane_pack(kh, d) == 1:
        return False
    return True


# --- prefill kernel ---


def _prefill_mask(q_start, kv_start, valid, *, group: int, block_q: int,
                  block_kv: int, sliding_window: Optional[int],
                  rows=None):
    """Which (q row, kv column) pairs of one accumulation attend: the
    causal frontier, the valid length, the window and, where given, the
    live `rows` (see _prefill_accumulate) -> bool [G*bq, bkv]. The same
    for every kv head, so a kernel that loops over heads builds it
    once."""
    # positions only depend on the q row WITHIN the block, identical
    # across the group; build [bq, bkv] then tile over the group rows
    r = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
    q_pos = q_start + r
    kv_pos = kv_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1)
    mask = (kv_pos <= q_pos) & (kv_pos < valid)
    if sliding_window is not None:
        mask &= kv_pos > q_pos - sliding_window
    if rows is not None:
        mask &= (r >= rows[0]) & (r < rows[1])
    return jnp.broadcast_to(mask[None], (group, block_q, block_kv)) \
        .reshape(group * block_q, block_kv)


def _prefill_accumulate(q, k, v, q_start, kv_start, valid, state, *,
                        group: int, block_q: int, block_kv: int,
                        sliding_window: Optional[int],
                        softcap: Optional[float],
                        k_scale=None, v_scale=None, kv_bits: int = 8,
                        rows=None, mask=None):
    """One online-softmax accumulation of a q block [G*bq, D] against one
    kv block [bkv, D] whose first entry holds absolute position kv_start.
    Shared by the contiguous (_prefill_kernel) and paged
    (_paged_prefill_kernel) prefill kernels — the two differ ONLY in how
    the kv block is addressed, so the math lives here once. Pure
    value-in/value-out over `state` = (m, l, acc) so callers can keep
    per-kv-head running state in scratch slices (the paged kernels loop
    heads in-kernel; a ref-mutating helper would pin the scratch
    layout).

    `k_scale`/`v_scale` [bkv, G] (ISSUE 11): the kv block arrived as a
    quantized page — dequantize in-kernel before the dots, so the bytes
    streamed from HBM are the int8/int4 payload + scales and the math
    past this line is IDENTICAL to the bf16 path (the numeric core of
    the quantized-parity discipline).

    `rows` = (lo, hi), where given: only q rows lo <= r < hi of the
    block are LIVE. The state of every other row comes back bit for bit
    as it went in (the ragged walk's tile can overlap a neighbouring
    sequence's rows, whose state is then their own). `mask`: the ready
    _prefill_mask (q_start, kv_start, valid, the window and the live
    rows are then unused)."""
    if k_scale is not None:
        k = _dequant_kv(k, k_scale, kv_bits, q.dtype)
        v = _dequant_kv(v, v_scale, kv_bits, q.dtype)
    m_prev, l_prev, acc_prev = state
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)            # [G*bq, bkv]
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    some_dead = rows is not None or mask is not None
    if mask is None:
        mask = _prefill_mask(q_start, kv_start, valid, group=group,
                             block_q=block_q, block_kv=block_kv,
                             sliding_window=sliding_window, rows=rows)
    s = jnp.where(mask, s, NEG_INF)

    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, :1])
    if some_dead:
        # a row with nothing live yet has m == NEG_INF and would weigh
        # every masked column 1: keep the dead rows' sums untouched
        p = jnp.where(mask, p, 0.0)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # [G*bq, D]
    return m_new, l_new, acc_prev * alpha[:, :1] + pv


def _prefill_blk_bounds(q_start, valid, block_q: int, block_kv: int,
                        sliding_window: Optional[int]):
    """(lo, hi) kv-block bounds for one q block — shared by the kernels
    and their index maps so the skip logic cannot drift."""
    hi = jnp.minimum((q_start + block_q - 1) // block_kv,
                     (valid - 1) // block_kv)
    if sliding_window is None:
        lo = jnp.int32(0)
    else:
        lo = jnp.maximum(0, (q_start - sliding_window + 1) // block_kv)
    return lo, hi


def _prefill_kernel(offs_ref, valid_ref, q_ref, k_ref, v_ref, o_ref,
                    m_scr, l_scr, acc_scr, *, block_q: int, block_kv: int,
                    num_kv_blocks: int, group: int,
                    sliding_window: Optional[int],
                    softcap: Optional[float]):
    # Grid (B, KV_heads, T_blocks, S_blocks): one step computes a whole GQA
    # group (all `group` query heads sharing one kv head) against one kv
    # block, so each kv block is DMA'd exactly once per (row, kv head) and
    # the output block flushes once per (row, kv head, q block) — s-block
    # steps keep the same output index, and the index maps clamp skipped
    # steps to the frontier so they fetch nothing new.
    b = pl.program_id(0)
    tb = pl.program_id(2)
    sb = pl.program_id(3)

    @pl.when(sb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    offs = offs_ref[b]
    valid = valid_ref[b]
    q_start = offs + tb * block_q
    lo, hi = _prefill_blk_bounds(q_start, valid, block_q, block_kv,
                                 sliding_window)

    @pl.when((sb >= lo) & (sb <= hi))
    def _compute():
        m_scr[:], l_scr[:], acc_scr[:] = _prefill_accumulate(
            q_ref[0, 0].reshape(group * block_q, -1), k_ref[0, 0],
            v_ref[0, 0], q_start, sb * block_kv, valid,
            (m_scr[:], l_scr[:], acc_scr[:]), group=group,
            block_q=block_q, block_kv=block_kv,
            sliding_window=sliding_window, softcap=softcap)

    @pl.when(sb == num_kv_blocks - 1)
    def _finish():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        d = o_ref.shape[-1]
        o_ref[0, 0] = (acc_scr[:] / l).astype(o_ref.dtype) \
            .reshape(group, block_q, d)


def flash_prefill_attention(
    q: jax.Array,                 # [B, T, H, D] (pre-scaled, rope'd)
    k: jax.Array,                 # [B, S, K, D] position-aligned cache
    v: jax.Array,                 # [B, S, K, D]
    offsets: jax.Array,           # [B] absolute position of q row start
    kv_valid: jax.Array,          # [B] valid cache entries per row
    *,
    sliding_window: Optional[int] = None,
    softcap: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Blockwise causal attention of a prefill chunk against the cache.

    Rows are assumed position-contiguous (position of q[:, i] is
    offsets[b] + i) — true for every chunked-prefill call in the engine.
    Returns [B, T, H, D] in q's dtype.
    """
    b, t, h, d = q.shape
    s, kh = k.shape[1], k.shape[2]
    group = h // kh
    block_q = _pick_block(t, (128, 64, 32, 16, 8))
    block_kv = _pick_block(s, (512, 256, 128, 64, 32, 16, 8))
    if block_q is None or block_kv is None:
        raise ValueError(f"unsupported shapes T={t} S={s}")
    interpret = _interpret() if interpret is None else interpret
    if not interpret and lane_pack(kh, d) > 1:
        return _cache_packed(
            flash_prefill_attention, q, k, v, offsets, kv_valid,
            sliding_window=sliding_window, softcap=softcap,
            interpret=interpret)

    # [B, T, H, D] → [B, K, G, T, D]: q heads grouped by their kv head
    # (head kh*G+g shares kv head kh, matching the dense path's repeat)
    qt = q.transpose(0, 2, 1, 3).reshape(b, kh, group, t, d)
    kt = k.transpose(0, 2, 1, 3)        # [B, K, S, D]
    vt = v.transpose(0, 2, 1, 3)
    num_kv_blocks = s // block_kv

    def kv_index(bi, khi, tb, sb, offs_ref, valid_ref):
        q_start = offs_ref[bi] + tb * block_q
        lo_blk, hi_blk = _prefill_blk_bounds(
            q_start, valid_ref[bi], block_q, block_kv, sliding_window)
        sb = jnp.clip(sb, lo_blk, jnp.maximum(hi_blk, 0))
        return (bi, khi, sb, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kh, t // block_q, num_kv_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, group, block_q, d),
                         lambda bi, khi, tb, sb, o_, v_:
                         (bi, khi, 0, tb, 0)),
            pl.BlockSpec((1, 1, block_kv, d), kv_index),
            pl.BlockSpec((1, 1, block_kv, d), kv_index),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, group, block_q, d),
            lambda bi, khi, tb, sb, o_, v_: (bi, khi, 0, tb, 0)),
        scratch_shapes=[
            pltpu.VMEM((group * block_q, _LANES), jnp.float32),
            pltpu.VMEM((group * block_q, _LANES), jnp.float32),
            pltpu.VMEM((group * block_q, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _prefill_kernel, block_q=block_q, block_kv=block_kv,
        num_kv_blocks=num_kv_blocks, group=group,
        sliding_window=sliding_window, softcap=softcap)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        interpret=interpret,
        name="flash_prefill_attention",
    )(offsets.astype(jnp.int32), kv_valid.astype(jnp.int32), qt, kt, vt)
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _paged_prefill_kernel(table_ref, offs_ref, valid_ref, q_ref, k_ref,
                          *rest,
                          block_q: int, page_size: int,
                          num_page_blocks: int, kh: int, group: int,
                          sliding_window: Optional[int],
                          softcap: Optional[float],
                          kv_bits: int = 8, quantized: bool = False,
                          v_dim: Optional[int] = None):
    # Identical math to _prefill_kernel (shared _prefill_accumulate); the
    # paged differences: the kv block for grid step sb is pool page
    # table[b, sb], and ALL kv heads ride one (1, ps, K, D) block with a
    # static in-kernel head loop — a per-head block (1, ps, 1, D) is
    # Mosaic-ILLEGAL for K > 1 (second-minor block dim 1 is neither
    # 8-aligned nor the full K axis; unseen on hardware until GQA
    # because gemma's MQA pool has K == 1), and total DMA bytes are the
    # same either way (each page read once with every head). Quantized
    # pools (ISSUE 11) ride two extra per-page scale blocks whose index
    # map is the kv block's, dequantized inside _prefill_accumulate.
    # A latent pool (`v_dim`, see _pool_heads) has no v operand.
    v_ref = None
    if v_dim is None:
        v_ref, *rest = rest
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    tb = pl.program_id(1)
    sb = pl.program_id(2)

    @pl.when(sb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    offs = offs_ref[b]
    valid = valid_ref[b]
    q_start = offs + tb * block_q
    lo, hi = _prefill_blk_bounds(q_start, valid, block_q, page_size,
                                 sliding_window)

    @pl.when((sb >= lo) & (sb <= hi))
    def _compute():
        for khi in range(kh):
            k, v = _page_block(k_ref, v_ref, khi, v_dim)
            m_scr[khi], l_scr[khi], acc_scr[khi] = _prefill_accumulate(
                q_ref[0, khi].reshape(group * block_q, -1),
                k, v, q_start,
                sb * page_size, valid,
                (m_scr[khi], l_scr[khi], acc_scr[khi]), group=group,
                block_q=block_q, block_kv=page_size,
                sliding_window=sliding_window, softcap=softcap,
                k_scale=(ks_ref[0, :, khi, :] if quantized else None),
                v_scale=(vs_ref[0, :, khi, :] if quantized else None),
                kv_bits=kv_bits)

    @pl.when(sb == num_page_blocks - 1)
    def _finish():
        d = o_ref.shape[-1]
        for khi in range(kh):
            l = jnp.maximum(l_scr[khi, :, :1], 1e-30)
            o_ref[0, khi] = (acc_scr[khi] / l).astype(o_ref.dtype) \
                .reshape(group, block_q, d)


def paged_prefill_supported(t: int, page_size: int, d: int,
                            kh: int = 1, group: int = 1) -> bool:
    """Can paged_prefill_attention serve this chunk/pool shape? kh/group
    as in paged_decode_supported — block_q shrinks until the kh-scaled
    working set fits VMEM, declining only when even block_q=8 doesn't."""
    if _paged_prefill_block_q(t, page_size, d, kh, group) is None:
        return False
    return paged_decode_supported(page_size, d, kh, group)


def paged_pool_direct_supported(chunk: int, page_size: int, d: int,
                                kh_local: int, group: int) -> bool:
    """The ONE build-time gate for pool-direct paged serving:
    pool-direct runs prefill chunks AND decode steps off the pool, so
    BOTH kernels must accept the shape. A layout only the decode kernel
    fits would otherwise raise mid-request in the prefill wrapper
    instead of serving the gather view (ISSUE 1: degrade, don't crash).
    `chunk` is the largest serving bucket — the block_q search shrinks
    from there, so smaller buckets only relax the estimate. Pass the
    LOCAL kv-head count.

    paged_prefill_supported's last clause IS the decode gate, so one
    delegation covers both kernels without duplicating the conjunction
    here."""
    return paged_prefill_supported(chunk, page_size, d, kh_local, group)


def paged_prefill_attention(
    q: jax.Array,                 # [B, T, H, D] (pre-scaled, rope'd)
    k_pool: jax.Array,            # [P, page_size, K, D] page pool
    v_pool: Optional[jax.Array],  # [P, page_size, K, D]; None: latent
    table: jax.Array,             # [B, pages_per_seq] int32 page table
    offsets: jax.Array,           # [B] absolute position of q row start
    kv_valid: jax.Array,          # [B] valid cache entries per row
    *,
    sliding_window: Optional[int] = None,
    softcap: Optional[float] = None,
    interpret: Optional[bool] = None,
    k_scale: Optional[jax.Array] = None,   # [P, ps, K, G] (ISSUE 11)
    v_scale: Optional[jax.Array] = None,
    kv_bits: int = 8,
    v_dim: Optional[int] = None,  # latent pool: values = keys[..., :v_dim]
) -> jax.Array:
    """Blockwise causal prefill attention straight off the page pool.

    The caller must have scattered this chunk's K/V into the rows'
    pages already (engine/paged_forward.py); pages below a row's offset
    may be ALIASED donor pages — the kernel only reads. The kv block
    index map reads the page table, so only pages inside each q block's
    causal/window frontier are DMA'd and the [B, S, K, D] gather view is
    never built. Returns [B, T, H, D] in q's dtype.

    `k_scale`/`v_scale` (ISSUE 11): the pool holds quantized pages —
    int8 payload (int4: D/2 packed nibbles when kv_bits=4) with
    per-cell scales; the scale blocks ride the SAME page index map as
    the kv blocks and dequant happens in-kernel.

    `v_pool=None` with `v_dim` (a latent pool [P, ps, W], _pool_heads):
    one kv head whose values are the first `v_dim` columns of its keys;
    each page is copied once and the result is [B, T, H, v_dim]."""
    packed = _on_packed(
        paged_prefill_attention, q, k_pool, v_pool, table, offsets,
        kv_valid, sliding_window=sliding_window, softcap=softcap,
        interpret=interpret, k_scale=k_scale, v_scale=v_scale,
        kv_bits=kv_bits, v_dim=v_dim)
    if packed is not None:
        return packed
    b, t, h, d = q.shape
    page_size, kh = k_pool.shape[1], _pool_heads(k_pool)
    latent = v_pool is None
    dv = v_dim if latent else d
    group = h // kh
    pages_per_seq = table.shape[1]
    quantized = k_scale is not None
    block_q = _paged_prefill_block_q(t, page_size, d, kh, group)
    if block_q is None or not paged_decode_supported(page_size, d, kh,
                                                     group):
        raise ValueError(f"unsupported shapes T={t} ps={page_size} D={d}")
    interpret = _interpret() if interpret is None else interpret

    qt = q.transpose(0, 2, 1, 3).reshape(b, kh, group, t, d)

    def kv_index(bi, tb, sb, table_ref, offs_ref, valid_ref):
        q_start = offs_ref[bi] + tb * block_q
        lo_blk, hi_blk = _prefill_blk_bounds(
            q_start, valid_ref[bi], block_q, page_size, sliding_window)
        sb = jnp.clip(sb, lo_blk, jnp.maximum(hi_blk, 0))
        return (table_ref[bi, sb],) + (0,) * (k_pool.ndim - 1)

    in_specs = [
        pl.BlockSpec((1, kh, group, block_q, d),
                     lambda bi, tb, sb, t_, o_, v_:
                     (bi, 0, 0, tb, 0)),
        pl.BlockSpec((1,) + k_pool.shape[1:], kv_index),
    ]
    operands = [qt, k_pool]
    if not latent:
        in_specs.append(
            pl.BlockSpec((1, page_size, kh, v_pool.shape[-1]), kv_index))
        operands.append(v_pool)
    if quantized:
        in_specs += [
            pl.BlockSpec((1, page_size, kh, k_scale.shape[-1]), kv_index),
            pl.BlockSpec((1, page_size, kh, v_scale.shape[-1]), kv_index),
        ]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, t // block_q, pages_per_seq),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, kh, group, block_q, dv),
            lambda bi, tb, sb, t_, o_, v_: (bi, 0, 0, tb, 0)),
        scratch_shapes=[
            pltpu.VMEM((kh, group * block_q, _LANES), jnp.float32),
            pltpu.VMEM((kh, group * block_q, _LANES), jnp.float32),
            pltpu.VMEM((kh, group * block_q, dv), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_prefill_kernel, block_q=block_q, page_size=page_size,
        num_page_blocks=pages_per_seq, kh=kh, group=group,
        sliding_window=sliding_window, softcap=softcap,
        kv_bits=kv_bits, quantized=quantized,
        v_dim=v_dim if latent else None)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qt.shape[:-1] + (dv,), q.dtype),
        interpret=interpret,
        name="mla_paged_prefill" if latent else "paged_prefill_attention",
    )(table.astype(jnp.int32), offsets.astype(jnp.int32),
      kv_valid.astype(jnp.int32), *operands)
    return out.reshape(b, kh * group, t, dv).transpose(0, 2, 1, 3)


def paged_prefill_spmd(
    mesh,
    q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
    table: jax.Array, offsets: jax.Array, kv_valid: jax.Array,
    *,
    sliding_window: Optional[int] = None,
    softcap: Optional[float] = None,
    interpret: Optional[bool] = None,
    pool_replicas: int = 1,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    kv_bits: int = 8,
) -> Optional[jax.Array]:
    """paged_prefill_attention under a (data, model) mesh — the same
    partitioning as paged_decode_spmd (kv heads on "model" matching the
    pool's sharding; table/offsets/valid row-aligned with the batch;
    pool_replicas > 1 shards the page axis over "data" and rebases each
    shard's table to its local range — see paged_decode_spmd). Scale
    pools (ISSUE 11) partition exactly like the kv pools — same page
    and kv-head axes."""
    from ..compat import shard_map
    from jax.sharding import PartitionSpec as P

    b, t, h, d = q.shape
    page_size, kh = k_pool.shape[1], k_pool.shape[2]
    axes_t = _spmd_axes(mesh, h, kh, b)
    if axes_t is None:
        return None
    batch_ax, head_ax, kv_head_ax = axes_t
    kh_local = kh // dict(mesh.shape).get(kv_head_ax, 1) \
        if kv_head_ax else kh
    if not paged_prefill_supported(t, page_size, d, kh_local, h // kh):
        return None
    page_ax = None
    if pool_replicas > 1:
        if (batch_ax != "data"
                or dict(mesh.shape).get("data", 1) != pool_replicas):
            return None
        page_ax = "data"
    per_replica = k_pool.shape[0] // pool_replicas

    q_spec = P(batch_ax, None, head_ax, None)
    pool_spec = P(page_ax, None, kv_head_ax, None)
    quantized = k_scale is not None

    def body(ql, kp, vp, tl, ol, vl, *sc):
        if page_ax is not None:
            tl = tl - jax.lax.axis_index("data") * per_replica
        ks, vs = sc if sc else (None, None)
        return paged_prefill_attention(
            ql, kp, vp, tl, ol, vl, sliding_window=sliding_window,
            softcap=softcap, interpret=interpret,
            k_scale=ks, v_scale=vs, kv_bits=kv_bits)

    in_specs = (q_spec, pool_spec, pool_spec,
                P(batch_ax, None), P(batch_ax), P(batch_ax))
    args = [q, k_pool, v_pool, table.astype(jnp.int32),
            offsets.astype(jnp.int32), kv_valid.astype(jnp.int32)]
    if quantized:
        in_specs += (pool_spec, pool_spec)
        args += [k_scale, v_scale]
    fn = shard_map(body, mesh=mesh,
                   in_specs=in_specs,
                   out_specs=q_spec, check_vma=False)
    return fn(*args)


# --- decode kernel ---


def _spmd_axes(mesh, h: int, kh: int, b: int):
    """(batch_ax, head_ax, kv_head_ax) for partitioning attention over a
    (data, model) mesh, or None when the head layout can't partition —
    the ONE derivation shared by the contiguous and paged SPMD wrappers.

    kv-head rule: when kh divides the model axis, each device's
    contiguous q-head slice maps exactly onto its kv-head slice (q head
    j ↔ kv head j // group), so both shard on "model". MQA (kh == 1)
    replicates the single kv head — matching _fallback_replicated's
    cache/pool layout — and shards only q heads. Any other non-dividing
    kh would scramble the q↔kv grouping per device
    (spmd_partitionable rejects it)."""
    axes = dict(mesh.shape)
    n_model = axes.get("model", 1)
    n_data = axes.get("data", 1)
    if not spmd_partitionable(h, kh, n_model):
        return None
    kv_head_ax = ("model" if n_model > 1 and kh % n_model == 0 else None)
    batch_ax = "data" if (n_data > 1 and b % n_data == 0) else None
    head_ax = "model" if n_model > 1 else None
    return batch_ax, head_ax, kv_head_ax


def flash_attention_spmd(
    mesh,
    q: jax.Array,                 # [B, T, H, D] (T==1 → decode)
    k: jax.Array,                 # [B, S, K, D] position-aligned cache
    v: jax.Array,                 # [B, S, K, D]
    offsets: jax.Array,           # [B] absolute position of q row start
    kv_valid: jax.Array,          # [B] valid cache entries per row
    *,
    sliding_window: Optional[int] = None,
    softcap: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> Optional[jax.Array]:
    """The kernels under a multi-device (data, model) mesh via shard_map.

    A plain pallas_call inside a pjit'd program is not SPMD-partitionable;
    this wrapper partitions the problem the way TP shards it anyway — kv
    heads on "model" (each device already holds its heads' slice of the
    page pools, the engine's pool_sharding), batch rows on "data" — and
    runs the kernel per-device on its local heads. Attention is embarrassingly
    parallel over (batch, kv head), so the body needs NO collectives; the
    o_proj contraction after (sharded over query heads) stays outside and
    gets its all-reduce from XLA as usual.

    Returns None when the shapes don't partition (heads don't divide the
    model axis — the engine's dense path is the fallback, matching
    _fallback_replicated's cache layout in that case).
    """
    from ..compat import shard_map
    from jax.sharding import PartitionSpec as P

    b, t, h, d = q.shape
    s, kh = k.shape[1], k.shape[2]
    axes_t = _spmd_axes(mesh, h, kh, b)
    if axes_t is None or not supported(t, s, d, kh):
        return None
    batch_ax, head_ax, kv_head_ax = axes_t

    q_spec = P(batch_ax, None, head_ax, None)
    kv_spec = P(batch_ax, None, kv_head_ax, None)
    row_spec = P(batch_ax)
    out_spec = q_spec

    def body(ql, kl, vl, offs_l, valid_l):
        if t > 1:
            return flash_prefill_attention(
                ql, kl, vl, offs_l, valid_l,
                sliding_window=sliding_window, softcap=softcap,
                interpret=interpret)
        return ragged_decode_attention(
            ql, kl, vl, valid_l,
            sliding_window=sliding_window, softcap=softcap,
            interpret=interpret)

    fn = shard_map(body, mesh=mesh,
                   in_specs=(q_spec, kv_spec, kv_spec, row_spec, row_spec),
                   out_specs=out_spec, check_vma=False)
    return fn(q, k, v, offsets.astype(jnp.int32),
              kv_valid.astype(jnp.int32))


def _decode_accumulate(q, k, v, kv_start, valid, state, *,
                       group: int, block_kv: int,
                       sliding_window: Optional[int],
                       softcap: Optional[float],
                       k_scale=None, v_scale=None, kv_bits: int = 8,
                       kv_pos=None):
    """One online-softmax accumulation of a single-position query group
    [G, D] against one kv block [bkv, D] whose first entry holds absolute
    position kv_start. Shared by the contiguous (_decode_kernel) and
    paged (_paged_decode_kernel) decode kernels — the two differ ONLY in
    how the kv block is addressed, so the math lives here once. Pure
    value-in/value-out over `state` = (m, l, acc) — see
    _prefill_accumulate for why. `k_scale`/`v_scale`: quantized-page
    blocks dequantize in-kernel first (ISSUE 11 — ditto). `kv_pos`
    [G, bkv], where given, is each (q row, kv row) pair's position in
    place of kv_start + column: the paged walk's rows interleave the kv
    heads, and a row of a q row's foreign head carries a position no
    `valid` reaches."""
    if k_scale is not None:
        k = _dequant_kv(k, k_scale, kv_bits, q.dtype)
        v = _dequant_kv(v, v_scale, kv_bits, q.dtype)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                # [G, bkv]
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    if kv_pos is None:
        kv_pos = kv_start + jax.lax.broadcasted_iota(
            jnp.int32, (group, block_kv), 1)
    mask = kv_pos < valid
    if sliding_window is not None:
        mask &= kv_pos > (valid - 1) - sliding_window
    s = jnp.where(mask, s, NEG_INF)

    m_prev, l_prev, acc_prev = state
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, :1])
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_prev * alpha[:, :1] + pv


def _decode_kernel(valid_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, block_kv: int,
                   num_kv_blocks: int, group: int,
                   sliding_window: Optional[int],
                   softcap: Optional[float]):
    b = pl.program_id(0)
    sb = pl.program_id(2)

    @pl.when(sb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    valid = valid_ref[b]
    hi = (valid - 1) // block_kv
    if sliding_window is None:
        lo = jnp.int32(0)
    else:
        lo = jnp.maximum(0, (valid - sliding_window) // block_kv)

    @pl.when((sb >= lo) & (sb <= hi))
    def _compute():
        m_scr[:], l_scr[:], acc_scr[:] = _decode_accumulate(
            q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], sb * block_kv, valid,
            (m_scr[:], l_scr[:], acc_scr[:]), group=group,
            block_kv=block_kv, sliding_window=sliding_window,
            softcap=softcap)

    @pl.when(sb == num_kv_blocks - 1)
    def _finish():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0, 0] = (acc_scr[:] / l).astype(o_ref.dtype)


# VMEM working-set budget for the paged kernels. The compiler's scoped
# limit on a v5e is 16 MiB, and a kernel asks it for more than the
# buffers it declares — Mosaic's own temporaries — so the estimates
# below are held to 12 MiB. All kv heads ride one block since the
# per-head pool block is Mosaic-illegal, so q/out/kv blocks and scratch
# all scale with kh — large-GQA shapes must shrink block_q or decline to
# the gather-view fallback INSTEAD of failing Mosaic compilation on chip.
_VMEM_BUDGET = 12 * 1024 * 1024


def _paged_vmem_est(page_size: int, d: int, kh: int, group: int,
                    block_q: int) -> int:
    """What the paged prefill and ragged kernels DECLARE, in bytes. What
    VMEM really holds (PR 28, from compiling for a v5e): a bf16 page
    block (1, ps, K, D) is stored at the array's own bytes — Mosaic
    tiles it (K, 128) like the pool in HBM; a copy-only kernel takes
    twelve [128, 8, 128] pages a slot pair and not sixteen — so the kv
    term is right as it stands. What this sum leaves out is the
    compiler's scratch for the per-head strided reads
    `k_ref[0, :, khi, :]`, about the kv term again: a kernel with 8 MiB
    of page buffers read that way asked for 16.4 MiB (the "block stored
    at twice its size" of ISSUE 28's sketch was this). The 4 MiB
    between the budget and the scope has covered it at every width
    tests/test_chip_compile.py compiles."""
    scratch = kh * group * block_q * (2 * _LANES + d) * 4   # f32 m/l/acc
    q_out = 2 * kh * group * block_q * d * 2                # bf16 blocks
    kv = 2 * 2 * page_size * kh * d * 2                     # 2×(k+v) bufs
    return scratch + q_out + kv


def _paged_prefill_block_q(t: int, page_size: int, d: int, kh: int,
                           group: int) -> Optional[int]:
    for bq in (128, 64, 32, 16, 8):
        if t % bq == 0 and _paged_vmem_est(page_size, d, kh, group,
                                           bq) <= _VMEM_BUDGET:
            return bq
    return None


# --- paged decode: the walk ---
#
# One grid step serves a block of _WALK_ROW_BLOCK batch rows (the whole
# batch, at the serving sizes). Each row walks ITS OWN pages,
# table[b, lo..hi], `n` pages a trip: the pools stay in HBM (pl.ANY) and
# a trip's pages are copied by explicit DMAs into one of two VMEM slots,
# the next trip's copies — or the next row's first — started before the
# present trip is waited for. A page arrives FLATTENED: the HBM ref
# [P, ps, K, D] is viewed as [P, ps*K, D] (no bytes move: XLA tiles the
# pool (K, 128), so a page's rows already lie token-major with the heads
# interleaved), which lands dense in VMEM and is multiplied as it lies —
# all q heads against all (token, head) rows, the foreign heads masked
# out of the softmax. No per-head strided read is left, which is what
# the grid kernel spent its time on: on a v5e at Mistral-7B's heads this
# walk runs at the speed of its copies.
#
# That holds where the K heads of a token fill whole tiles (_token_major:
# 2, 4 or a multiple of 8 heads, of four bytes together at least). Any
# other pool XLA stores HEAD-major — physically [P, K, ps, D], a page is
# K dense [ps, D] blocks — and would re-lay out, whole, on every call
# for a row-major [P, ps, K, D] operand, padding the head axis; the
# flattened view of THAT reads the padding (wrong numbers on the chip,
# PERF.md PR 28). Such a pool is handed over as XLA has it, [P, K*ps, D]
# — again a bitcast — and only the map from a row to its (head, token)
# differs, built once (_page_rows).
#
# A quantized pool's scales walk with the pages in the form XLA stores
# them: it keeps f32[P, ps, K, G] token-minor (`{1,3,2,0:T(1,128)}`, a
# page is K*G dense rows of ps scales), so the operand is that view,
# [P, K*G, ps] — a bitcast, where the row-major [P, ps, K, G] every
# BlockSpec kernel asks for makes XLA re-lay the WHOLE pool out, G padded
# to a lane row, on every call (PERF.md, PR 28). In VMEM a trip's scale
# rows are turned into the column a flattened page needs — one scale a
# (head, token) row — by one small one-hot product a page
# (_scale_columns): the MXU is the only unit that moves lanes to rows.

# A trip should move at least this much of keys and values: below it
# the trip's fixed work (scalar reads of the table, DMA issue, the
# softmax bookkeeping) shows against the copy.
_WALK_TRIP_BYTES = 1 << 20
_WALK_ROW_BLOCK = 16      # batch rows a grid step holds q and out for
_FOREIGN = 1 << 30        # "position" of a row of another kv head


def _token_major(kh: int, itemsize: int) -> bool:
    """Does XLA keep a pool [P, ps, K, D] row-major, the heads of a
    token filling whole tiles (see "the walk")?"""
    return kh * itemsize >= 4 and (kh in (2, 4) or kh % 8 == 0)


def _page_rows(shape, axis: int, *, page_size: int, kh: int,
               token_major: bool):
    """(head, token) of each row of a flattened page, as int32 arrays of
    `shape` with the page's rows along `axis`."""
    c = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    if token_major:
        return c % kh, c // kh
    return c // page_size, c % page_size


def _walk_page_bytes(page_size: int, kh: int, dk: int, itemsize: int,
                     scale_groups: int, latent: bool = False) -> int:
    """Bytes of one page's keys AND values as VMEM holds them: rows of
    `dk` cells, lane-padded (a latent page is both at once); the f32
    scales of a page are K*G rows (a whole sublane tile at least) of ps
    lanes."""
    rows = page_size * kh
    lanes = -(-dk // _LANES) * _LANES
    page = (1 if latent else 2) * rows * lanes * itemsize
    if scale_groups:
        page += 2 * (-(-kh * scale_groups // 8) * 8) * (
            -(-page_size // _LANES) * _LANES) * 4
    return page


def _walk_vmem_est(n: int, page_size: int, d: int, kh: int, group: int,
                   dk: int, itemsize: int, scale_groups: int,
                   latent: bool = False) -> int:
    cols = n * page_size * kh
    hq = -(-kh * group // 8) * 8
    bufs = 2 * n * _walk_page_bytes(page_size, kh, dk, itemsize,
                                    scale_groups, latent)  # two slots
    # q and out blocks of a row block, double-buffered by the pipeline
    q_out = 2 * 2 * _WALK_ROW_BLOCK * hq * d * 2
    scores = 4 * hq * cols * 4       # row index, s, p, the mask's temps
    deq = 0
    if scale_groups:
        deq = 2 * cols * d * (4 + 2)                   # f32, then bf16
        # the one-hot [ps*K, ps]; the picked scales [cols, K*G] and the
        # columns [cols, G] of both pools, f32 in whole lane rows
        deq += page_size * kh * page_size * 4 + 2 * 2 * cols * _LANES * 4
    return bufs + q_out + scores + deq


def _walk_pages(page_size: int, d: int, kh: int, group: int,
                dk: Optional[int] = None, itemsize: int = 2,
                scale_groups: int = 0, latent: bool = False
                ) -> Optional[int]:
    """Pages a trip of the decode walk moves — from what the operands
    show: the page's stored bytes and the VMEM budget — or None when not
    even one page a trip fits."""
    dk = d if dk is None else dk
    page = _walk_page_bytes(page_size, kh, dk, itemsize, scale_groups,
                            latent)
    n = 1
    while n * page < _WALK_TRIP_BYTES:
        n *= 2
    est = functools.partial(_walk_vmem_est, page_size=page_size, d=d,
                            kh=kh, group=group, dk=dk, itemsize=itemsize,
                            scale_groups=scale_groups, latent=latent)
    while n > 1 and est(n) > _VMEM_BUDGET:
        n //= 2
    return n if est(n) <= _VMEM_BUDGET else None


def paged_decode_decline_reason(page_size: int, d: int, kh: int = 1,
                                group: int = 1, *, itemsize: int = 2,
                                scale_groups: int = 0,
                                dk: Optional[int] = None,
                                latent: bool = False) -> Optional[str]:
    """Why paged_decode_attention cannot serve this pool shape, or None
    when it can. Pass the LOCAL kv-head count and GQA group; `itemsize`
    is a page cell's (2: bf16, 1: int8/int4 payloads, whose f32 scale
    pools carry `scale_groups` groups a cell). Page size must be a
    legal block for the prefill kernels that share the pool; one page a
    trip must fit the VMEM budget; and on the chip (any shape goes in
    interpret mode) D must be lane-aligned — or 64 with the kv heads in
    pairs, which an unquantized pool stores two heads a lane row
    (`lane_pack`; asked about (K, D) it answers for that cell) — and a
    quantized pool's pages must fill whole lane rows of scales (a scale
    page is K*G rows of ps lanes). Every head count is served: see
    _token_major."""
    if page_size not in (512, 256, 128, 64, 32, 16, 8):
        return f"page_size:{page_size}"
    if dk is None and not latent and not scale_groups:
        # (the cell such heads are stored in: lane_pack)
        f = lane_pack(kh, d)
        d, kh, group = d * f, kh // f, group * f
    if _walk_pages(page_size, d, kh, group, dk, itemsize,
                   scale_groups, latent) is None:
        return f"vmem:ps={page_size},d={d},kh={kh},g={group}"
    if _interpret():
        return None
    if d % 128 != 0:
        return f"head_dim:{d}"
    if scale_groups and page_size % _LANES:
        return f"scale_page:{page_size}"
    return None


def paged_decode_supported(page_size: int, d: int, kh: int = 1,
                           group: int = 1, **kw) -> bool:
    """Can paged_decode_attention serve this pool shape? An unsupported
    layout must route to the gather view, not fail Mosaic."""
    return paged_decode_decline_reason(page_size, d, kh, group,
                                       **kw) is None


def _scale_columns(scales, onehot, first_pos, valid, *, page_size: int,
                   kh: int, token_major: bool):
    """A trip's scale rows [n, K*G, ps] (a page's scales as the pool
    stores them: one row a head and group, a lane a token) -> the column
    block [n*ps*K, G] its flattened pages take, a row of a page being
    one (head, token) by _page_rows. `onehot` [ps*K, ps] picks each
    row's token in one exact product; the row's own head is then the
    one lane group kept. Tokens from `valid` on read as scale 0 whatever
    the pool (or a slot no copy reached) holds there: they are masked
    out of the softmax, and a zero keeps them out of the weighted sum."""
    n, kg, _ = scales.shape
    groups = kg // kh
    pr = page_size * kh
    lane = jax.lax.broadcasted_iota(jnp.int32, (pr, kg), 1)
    head, _ = _page_rows((pr, kg), 0, page_size=page_size, kh=kh,
                         token_major=token_major)
    tok = jax.lax.broadcasted_iota(jnp.int32, (kg, page_size), 1)
    pages = []
    for j in range(n):
        live = first_pos + j * page_size + tok < valid
        picked = jax.lax.dot_general(
            onehot, jnp.where(live, scales[j], 0.0),
            (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)            # [pr, K*G]
        pages.append(jnp.concatenate(
            [jnp.sum(jnp.where(lane == head * groups + g, picked, 0.0),
                     axis=-1, keepdims=True) for g in range(groups)],
            axis=-1))
    return jnp.concatenate(pages, axis=0)


def _paged_decode_kernel(table_ref, valid_ref, q_ref, *rest,
                         page_size: int, n: int, kh: int,
                         group: int, rows: int, token_major: bool,
                         sliding_window: Optional[int],
                         softcap: Optional[float],
                         kv_bits: int = 8, quantized: bool = False,
                         v_dim: Optional[int] = None):
    # See "the walk" above. valid INCLUDES the current step's entry,
    # which the caller has already written into the pool (q position =
    # valid - 1). Quantized pools (ISSUE 11): the two scale pools walk
    # with the pages and the rows dequantize inside _decode_accumulate.
    # `rest`: the pools in HBM (k, v and, if quantized, their scales),
    # the output, one two-slot VMEM buffer a pool, the position scratch,
    # the scales' one-hot (quantized), the DMA semaphores. A latent
    # pool (`v_dim`, see _pool_heads) is the ONE pool: a page is copied
    # once and its first `v_dim` columns are the values.
    n_kv = 2 if v_dim is None else 1
    n_pools = n_kv + (2 if quantized else 0)
    hbms, o_ref = rest[:n_pools], rest[n_pools]
    bufs, sem = rest[n_pools + 1:2 * n_pools + 1], rest[-1]
    pos_scr = rest[2 * n_pools + 1]
    kbuf, vbuf = bufs[0], bufs[n_kv - 1]
    block, hq, _ = q_ref.shape
    d = o_ref.shape[-1]
    pr = page_size * kh                 # rows of one flattened page
    cols = n * pr
    first_row = pl.program_id(0) * block
    layout = dict(page_size=page_size, kh=kh, token_major=token_major)
    # (pool in HBM, its two-slot buffer, where page j of a trip lands)
    lanes = [(hbm.reshape(hbm.shape[0], pr, hbm.shape[-1]), buf,
              lambda j: pl.ds(j * pr, pr)) for hbm, buf in zip(hbms[:n_kv],
                                                               bufs[:n_kv])]
    lanes += [(hbm, buf, lambda j: j) for hbm, buf in zip(hbms[n_kv:],
                                                         bufs[n_kv:])]

    @pl.when(pl.program_id(0) == 0)
    def _():
        # Column c of a trip is one (head, token) of page c // pr; q
        # row r belongs to kv head r // group. Foreign heads get a
        # position no `valid` reaches, so ONE compare masks both.
        col = jax.lax.broadcasted_iota(jnp.int32, (hq, cols), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (hq, cols), 0)
        head, tok = _page_rows((hq, cols), 1, **layout)
        head, tok = head % kh, tok % page_size      # col runs over n pages
        pos_scr[...] = jnp.where(head == row // group,
                                 col // pr * page_size + tok, _FOREIGN)
        # A trip's last pages may lie past the row's frontier and are
        # then not copied: what the slot holds there is masked out of
        # the scores, but the weighted sum multiplies it by zero —
        # which a NaN left in fresh VMEM would survive.
        vbuf[...] = jnp.zeros_like(vbuf)
        if quantized:
            onehot = rest[-2]
            _, tok = _page_rows(onehot.shape, 0, **layout)
            t = jax.lax.broadcasted_iota(jnp.int32, onehot.shape, 1)
            onehot[...] = (tok == t).astype(onehot.dtype)

    def span(b):
        valid = valid_ref[b]
        hi = jnp.maximum(valid - 1, 0) // page_size
        if sliding_window is None:
            lo = jnp.int32(0)
        else:
            lo = jnp.maximum(0, (valid - sliding_window) // page_size)
        pages = jnp.where(valid > 0, hi - lo + 1, 0)
        return valid, lo, hi, (pages + n - 1) // n

    def copies(b, lo, hi, t, slot, go):
        # Start (go=True) or await trip t of row b into `slot`; a wait
        # only needs the copy's shape, not its source page.
        for j in range(n):
            at = lo + t * n + j

            @pl.when(at <= hi)
            def _():
                page = table_ref[b, at] if go else 0
                for i, (hbm, buf, where) in enumerate(lanes):
                    c = pltpu.make_async_copy(
                        hbm.at[page], buf.at[slot, where(j)],
                        sem.at[i, slot])
                    c.start() if go else c.wait()

    last = jnp.minimum(first_row + block, rows) - 1

    def one_row(i, carry):
        g0, started = carry     # trips so far; was my first trip started
        b = first_row + i
        valid, lo, hi, trips = span(b)
        nb = jnp.minimum(b + 1, last)
        _, nlo, nhi, ntrips = span(nb)
        hand_on = (b < last) & (ntrips > 0) & (trips > 0)

        @pl.when((trips > 0) & (started == 0))
        def _():
            copies(b, lo, hi, 0, g0 % 2, True)

        q = q_ref[i]                                    # [hq, d]

        def trip(t, state):
            slot = (g0 + t) % 2

            @pl.when(t + 1 < trips)
            def _():
                copies(b, lo, hi, t + 1, 1 - slot, True)

            @pl.when((t + 1 == trips) & hand_on)
            def _():
                copies(nb, nlo, nhi, 0, 1 - slot, True)

            copies(b, lo, hi, t, slot, False)
            first_pos = (lo + t * n) * page_size
            scales = {}
            if quantized:
                scales = {
                    name: _scale_columns(
                        buf[slot], rest[-2][...], first_pos, valid,
                        **layout)
                    for name, buf in zip(("k_scale", "v_scale"), bufs[2:])}
            keys = kbuf[slot]
            vals = vbuf[slot] if v_dim is None else keys[:, :v_dim]
            return _decode_accumulate(
                q, keys, vals, 0, valid, state, group=hq,
                block_kv=cols, sliding_window=sliding_window,
                softcap=softcap, kv_bits=kv_bits,
                kv_pos=first_pos + pos_scr[...], **scales)

        _, l, acc = jax.lax.fori_loop(
            0, trips, trip,
            (jnp.full((hq, _LANES), NEG_INF, jnp.float32),
             jnp.zeros((hq, _LANES), jnp.float32),
             jnp.zeros((hq, d), jnp.float32)))
        o_ref[i] = (acc / jnp.maximum(l[:, :1], 1e-30)).astype(o_ref.dtype)
        return g0 + trips, hand_on.astype(jnp.int32)

    jax.lax.fori_loop(0, last - first_row + 1, one_row,
                      (jnp.int32(0), jnp.int32(0)))


def paged_decode_spmd(
    mesh,
    q: jax.Array,                 # [B, 1, H, D]
    k_pool: jax.Array,            # [P, page_size, K, D]
    v_pool: jax.Array,            # [P, page_size, K, D]
    table: jax.Array,             # [B, pages_per_seq]
    kv_valid: jax.Array,          # [B]
    *,
    sliding_window: Optional[int] = None,
    softcap: Optional[float] = None,
    interpret: Optional[bool] = None,
    pool_replicas: int = 1,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    kv_bits: int = 8,
) -> Optional[jax.Array]:
    """paged_decode_attention under a multi-device (data, model) mesh.

    Same partitioning as flash_attention_spmd: kv heads ride "model"
    (each device's pool slice holds its heads' pages — the engine's
    paged pool sharding), and batch rows ride "data" when divisible —
    the page table and valid lengths shard row-aligned with the batch
    (replicated when the batch doesn't divide). MQA replicates the
    single kv head and shards only q heads. Returns None when the head
    layout doesn't partition — the engine then serves paged decode
    through the gather view instead.

    pool_replicas > 1 (VERDICT r4 #4): the pool's PAGE axis is sharded
    over "data" (per-replica pools, engine/paging.py), so each data
    shard holds pages [r*P/R, (r+1)*P/R) and the batch MUST arrive
    replica-grouped: block r's rows reference only replica r's pages
    (the engine's ReplicaGroupPlan pads and permutes the batch to make
    this hold). The body rebases each shard's table to its local page
    range via axis_index — the gather view is never built. Returns None
    when the batch doesn't divide over "data" (serving always pads) or
    the mesh's data size disagrees with pool_replicas.
    """
    from ..compat import shard_map
    from jax.sharding import PartitionSpec as P

    b, t, h, d = q.shape
    page_size, kh = k_pool.shape[1], k_pool.shape[2]
    axes_t = _spmd_axes(mesh, h, kh, b)
    if axes_t is None:
        return None
    batch_ax, head_ax, kv_head_ax = axes_t
    kh_local = kh // dict(mesh.shape).get(kv_head_ax, 1) \
        if kv_head_ax else kh
    if not paged_decode_supported(page_size, d, kh_local, h // kh):
        return None
    page_ax = None
    if pool_replicas > 1:
        if (batch_ax != "data"
                or dict(mesh.shape).get("data", 1) != pool_replicas):
            return None
        page_ax = "data"
    per_replica = k_pool.shape[0] // pool_replicas

    q_spec = P(batch_ax, None, head_ax, None)
    pool_spec = P(page_ax, None, kv_head_ax, None)
    quantized = k_scale is not None

    def body(ql, kp, vp, tl, vl, *sc):
        if page_ax is not None:
            tl = tl - jax.lax.axis_index("data") * per_replica
        ks, vs = sc if sc else (None, None)
        return paged_decode_attention(
            ql, kp, vp, tl, vl, sliding_window=sliding_window,
            softcap=softcap, interpret=interpret,
            k_scale=ks, v_scale=vs, kv_bits=kv_bits)

    in_specs = (q_spec, pool_spec, pool_spec,
                P(batch_ax, None), P(batch_ax))
    args = [q, k_pool, v_pool, table.astype(jnp.int32),
            kv_valid.astype(jnp.int32)]
    if quantized:
        in_specs += (pool_spec, pool_spec)
        args += [k_scale, v_scale]
    fn = shard_map(body, mesh=mesh,
                   in_specs=in_specs,
                   out_specs=q_spec, check_vma=False)
    return fn(*args)


def paged_decode_attention(
    q: jax.Array,                 # [B, 1, H, D] this step's query
    k_pool: jax.Array,            # [P, page_size, K, D] page pool
    v_pool: Optional[jax.Array],  # [P, page_size, K, D]; None: latent
    table: jax.Array,             # [B, pages_per_seq] int32 page table
    kv_valid: jax.Array,          # [B] valid entries INCLUDING this step
    *,
    sliding_window: Optional[int] = None,
    softcap: Optional[float] = None,
    interpret: Optional[bool] = None,
    k_scale: Optional[jax.Array] = None,   # [P, ps, K, G] (ISSUE 11)
    v_scale: Optional[jax.Array] = None,
    kv_bits: int = 8,
    v_dim: Optional[int] = None,  # latent pool: values = keys[..., :v_dim]
) -> jax.Array:
    """Single-position decode attention straight off the page pool.

    The caller must have written this step's K/V into each row's frontier
    page already (a [B]-row scatter — engine/paged_forward.py). Each row
    walks the pages of its own valid prefix (from the window's first
    page, for a sliding window) and nothing else of the table's width,
    several pages a trip, copied while the trip before is multiplied
    (see "the walk" above); the [B, S, K, D] gather view the engine's
    fallback path materializes is never built, and the pool keeps its
    prefill-friendly [P, ps, K, D] layout and stays an operand of the
    call as it is. A row with nothing valid costs no trip and yields
    zeros. Returns [B, 1, H, D]. `k_scale`/`v_scale` (ISSUE 11):
    quantized pools dequantize in-kernel — the scale pools walk with
    the pages. `v_pool=None` with `v_dim`: a latent pool [P, ps, W]
    (_pool_heads) — one pool walks, each page is copied once, and the
    result is [B, 1, H, v_dim].
    """
    packed = _on_packed(
        paged_decode_attention, q, k_pool, v_pool, table, kv_valid,
        sliding_window=sliding_window, softcap=softcap,
        interpret=interpret, k_scale=k_scale, v_scale=v_scale,
        kv_bits=kv_bits, v_dim=v_dim)
    if packed is not None:
        return packed
    b, t, h, d = q.shape
    assert t == 1, "decode kernel serves exactly one position"
    page_size, kh = k_pool.shape[1], _pool_heads(k_pool)
    latent = v_pool is None
    dv = v_dim if latent else d
    group = h // kh
    quantized = k_scale is not None
    shape = dict(dk=k_pool.shape[-1], itemsize=k_pool.dtype.itemsize,
                 scale_groups=k_scale.shape[-1] if quantized else 0,
                 latent=latent)
    reason = paged_decode_decline_reason(page_size, d, kh, group, **shape)
    if reason is not None:
        raise ValueError(f"unsupported pool shape: {reason}")
    interpret = _interpret() if interpret is None else interpret
    n = min(_walk_pages(page_size, d, kh, group, **shape), table.shape[1])
    trip_rows = n * page_size * kh
    block = min(b, _WALK_ROW_BLOCK)

    token_major = _token_major(kh, k_pool.dtype.itemsize)
    pools = [k_pool] if latent else [k_pool, v_pool]
    if not token_major and not latent:
        # the pool as XLA stores it, head-major: see "the walk" above
        pools = [p.swapaxes(1, 2).reshape(
            p.shape[0], kh * page_size, p.shape[-1]) for p in pools]
    n_kv = len(pools)
    bufs = [pltpu.VMEM((2, trip_rows, p.shape[-1]), p.dtype) for p in pools]
    consts = [pltpu.VMEM((h, trip_rows), jnp.int32)]
    if quantized:
        # the scale pools as XLA stores them: see "the walk" above
        pools += [jnp.transpose(s, (0, 2, 3, 1)).reshape(
            s.shape[0], -1, page_size) for s in (k_scale, v_scale)]
        bufs += [pltpu.VMEM((2, n) + p.shape[1:], p.dtype)
                 for p in pools[n_kv:]]
        consts += [pltpu.VMEM((page_size * kh, page_size), jnp.float32)]

    def rows_blk(width):
        return pl.BlockSpec((block, h, width), lambda i, t_, v_: (i, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(pl.cdiv(b, block),),
        in_specs=[rows_blk(d)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
        out_specs=rows_blk(dv),
        scratch_shapes=bufs + consts + [
            pltpu.SemaphoreType.DMA((len(bufs), 2))],
    )
    kernel = functools.partial(
        _paged_decode_kernel, page_size=page_size, n=n, kh=kh,
        group=group, rows=b, token_major=token_major,
        sliding_window=sliding_window, softcap=softcap, kv_bits=kv_bits,
        quantized=quantized, v_dim=v_dim if latent else None)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, dv), q.dtype),
        interpret=interpret,
        name="mla_paged_decode" if latent else "paged_decode_attention",
    )(table.astype(jnp.int32), kv_valid.astype(jnp.int32), q[:, 0], *pools)
    return out.reshape(b, 1, h, dv)


# --- ragged paged attention (ISSUE 8): the ragged walk ---
#
# Mixed prefill chunks and decode tokens in ONE dispatch (arxiv
# 2604.15464 "Ragged Paged Attention"): the query is a FLAT token buffer
# [T, H, D] carved into per-sequence row runs, each sequence attending
# its own page-table pages. The host builder
# (serving_loop.build_ragged_batch) aligns every run to RAGGED_BLOCK_Q=8
# rows — the MXU sublane minimum, so a decode token (a 1-row sequence)
# occupies exactly one hardware tile — and scalar-prefetched per-TILE
# metadata maps each 8 rows to their sequence: one compiled program
# serves every prefill/decode mix of a fixed token budget, which is what
# retires the scheduler's pow2 row buckets on this path.
#
# The kernel's query block is NOT the packing's 8. A grid step holds a
# block of up to 128 flat rows (_ragged_block_q: what the VMEM estimate
# allows for the pool's shape; 64 in latent mode), q and out pipelined
# by their BlockSpecs, the float32 softmax state of every row in
# scratch. Inside, the step goes over the SEGMENTS of its block — the
# stretch of one run that lies in it (_ragged_segments) — and walks each
# segment's own pages, table[seq, lo..hi], up to the causal frontier of
# the segment's last row and no further, `n` pages a trip
# (_ragged_trip_pages: 512 kv columns a product, so that the per-row
# work of a product — rescaling m, l and acc, their loads and stores —
# is shared by four pages): the pools stay in HBM (pl.ANY) and a trip's
# pages are copied by explicit DMAs into one of two VMEM slots, the next
# trip's copies — or the first trip of the block's next segment — started
# before the present trip is waited for. So a run's pages are copied once
# a block the run lies in — twice for a 240-token leader — and not once
# every 8 rows; nothing is paid for the width of the page table; and an
# inert pad tile costs one skipped iteration.
#
# A trip's kv heads are parted ONCE, into dense [n*ps, D] blocks, and
# then multiplied TILE by tile (_ragged_tile_rows): the block's rows in
# tiles of 16-64, only those the segment touches and, of them, only
# those whose positions reach the trip — a decode row or a verify tile
# pays for one tile of products, a follower of 44 tokens for two, and a
# long chunk skips the (tile, trip) pairs above the diagonal. Within a
# tile the kv heads' products stand side by side under one mask: they
# are independent, and one's latency hides behind the others' work
# (with the heads in an inner LOOP of their own the same products took
# twice as long on a v5e). A tile can overlap a neighbouring run's
# rows: they are dead to this segment (`rows` of _prefill_mask), their
# state passes through bit for bit, and every row of the output block
# is written once, by the step that owns it.
#
# The pools are operands as XLA stores them. Token-major [P, ps, K, D]
# (_token_major): a bfloat16 page is copied as the 32-bit words it is
# stored in — a token's K heads are K/2 rows of words, a word a pair of
# heads — and a pair is parted by one strided load and two shifts, a
# quarter of what the same slice costs as a strided read of bfloat16
# rows (which other dtypes take). Head-major [P, K*ps, D], the view the
# decode walk takes: a kv head is a dense block of its page and lands
# dense, one copy a head. A latent pool [P, ps, W] is keys and, in its
# first columns, values. QUANTIZED pools keep the grid kernel the walk
# replaced (_ragged_grid_kernel) until their scale pools walk too
# (ROADMAP S1b).
#
# RAGGED_BLOCK_Q has ONE owner (serving_loop): the host builder aligns
# runs and sizes seq_of_block/block_qstart with it, and the segment map
# + VMEM estimate here must agree — two definitions would let a lone
# tuning change silently mis-map tiles to sequences.
from ..serving_loop import RAGGED_BLOCK_Q  # noqa: E402

# Test-visibility counters (tests/conftest.py `ragged_attn` marker
# guard): how many ragged dispatches the engine seam issued since the
# last reset, split kernel vs XLA fallback. A guard that sees zero
# kernel dispatches on a marked test knows the ragged path silently fell
# back (or never ran). The kernel wrapper also counts its own traces so
# direct-kernel unit tests register without an engine.
import threading as _threading

_ragged_lock = _threading.Lock()
_ragged_kernel_count = 0
_ragged_fallback_count = 0


def reset_ragged_counters() -> None:
    global _ragged_kernel_count, _ragged_fallback_count
    with _ragged_lock:
        _ragged_kernel_count = 0
        _ragged_fallback_count = 0


def note_ragged_dispatch(kernel: bool) -> None:
    global _ragged_kernel_count, _ragged_fallback_count
    with _ragged_lock:
        if kernel:
            _ragged_kernel_count += 1
        else:
            _ragged_fallback_count += 1


def ragged_kernel_dispatches() -> int:
    return _ragged_kernel_count


def ragged_fallback_dispatches() -> int:
    return _ragged_fallback_count


def ragged_decline_reason(page_size: int, d: int, kh: int = 1,
                          group: int = 1, *, dk: Optional[int] = None,
                          dv: Optional[int] = None, itemsize: int = 2,
                          q_itemsize: int = 2, latent: bool = False,
                          quantized: bool = False) -> Optional[str]:
    """Why the ragged kernel cannot serve this pool shape, or None when
    it can — the machine-readable `fallback_reason` the engine records
    per dispatch (the int4mm plan_reason pattern). Pass the LOCAL
    kv-head count under SPMD; `dk` / `dv` are the pool's key and the
    result's value widths where they are not `d` (a latent pool) and
    `itemsize` a page cell's. The VMEM estimate is the walk's own
    (_ragged_vmem_est) at the packing's 8-row block, the smallest query
    block it can take — or, for a `quantized` pool, the grid kernel's
    (_paged_vmem_est). Asked about 64-wide heads in pairs it answers for
    the cell an unquantized pool stores them in (`lane_pack`)."""
    if page_size not in (512, 256, 128, 64, 32, 16, 8):
        return f"page_size:{page_size}"
    if dk is None and not latent and not quantized:
        # (the cell such heads are stored in: lane_pack)
        f = lane_pack(kh, d)
        d, kh, group = d * f, kh // f, group * f
    if quantized:
        fits = _paged_vmem_est(page_size, d, kh, group,
                               RAGGED_BLOCK_Q) <= _VMEM_BUDGET
    else:
        fits = _ragged_block_q(
            RAGGED_BLOCK_Q, page_size, d, kh, group, dk=dk or d,
            dv=dv or d, itemsize=itemsize, q_itemsize=q_itemsize,
            latent=latent) is not None
    if not fits:
        return f"vmem:ps={page_size},d={d},kh={kh},g={group}"
    if not _interpret() and d % 128 != 0:
        return f"head_dim:{d}"
    return None


def ragged_supported(page_size: int, d: int, kh: int = 1,
                     group: int = 1, **shape) -> bool:
    return ragged_decline_reason(page_size, d, kh, group, **shape) is None


# What the v5e's compiler (Mosaic, JAX 0.9.0) answers the int4 page
# dequant (_dequant_kv → kv_quant.unpack_int4) inside the paged decode,
# paged prefill and ragged kernels — reproduced without a chip by the
# xfail(strict) cases of tests/test_chip_compile.py. While it stands,
# kv_quant_decline_reason declines int4 pools wherever the kernel would
# be compiled for the chip, so the engine records the reason at
# construction and serves the XLA dequant paths by plan, not by a
# runtime degradation rung.
MOSAIC_INT4_KV_REFUSAL = (
    "infer-vector-layout: unsupported shape cast (tpu.reshape of the "
    "packed page block, vector<ps x D/2 x i8> -> ps x D/2 x 1, in "
    "unpack_int4)")


def kv_quant_decline_reason(page_size: int, d: int, kh: int, group: int,
                            bits: int = 8,
                            quant_group: int = 32) -> Optional[str]:
    """Why the Pallas kernels cannot serve a QUANTIZED pool of this
    shape, or None when they can — the machine-readable
    `fallback_reason` the engine records (the int4mm plan_reason
    pattern, ISSUE 11). The bf16 kernel gates (page_size block
    legality, VMEM, lane-aligned D) apply unchanged, and the decode
    walk's with the pool's real cells (payload rows and lane-padded
    scale rows); int4 additionally needs an even head_dim whose packed
    width and scale grouping are well-formed. A declined shape serves
    through the XLA dequant fallback (gather view / ragged dense path)
    — the pages stay quantized either way, only the dequant site
    moves."""
    if bits not in (8, 4):
        return f"kv_bits:{bits}"
    base = ragged_decline_reason(page_size, d, kh, group,
                                 quantized=True)
    if base is not None:
        return base
    from ..kv_quant import KVQuantSpec
    spec = KVQuantSpec(bits=bits, group=quant_group)
    if bits == 8 or (d % 2 == 0 and d % spec.effective_group(d) == 0):
        base = paged_decode_decline_reason(
            page_size, d, kh, group, itemsize=1, dk=spec.packed_dim(d),
            scale_groups=spec.num_groups(d))
        if base is not None:
            return base
    if bits == 4:
        if d % 2:
            return f"int4_head_dim:{d}"
        g = spec.effective_group(d)
        if d % g or g % 2:
            # effective_group clamps to >= 2; a grouping that doesn't
            # tile D evenly means no well-formed scale layout exists.
            return f"int4_group:d={d},g={quant_group}"
        if not _interpret():
            return f"mosaic:{MOSAIC_INT4_KV_REFUSAL}"
    return None


def kv_quant_kernel_supported(page_size: int, d: int, kh: int,
                              group: int, bits: int = 8,
                              quant_group: int = 32) -> bool:
    return kv_quant_decline_reason(page_size, d, kh, group, bits,
                                   quant_group) is None


def _ragged_grid_kernel(table_ref, blkseq_ref, blkq_ref, qoffs_ref,
                        valid_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                        o_ref, m_scr, l_scr, acc_scr, *,
                        page_size: int, num_page_blocks: int, kh: int,
                        group: int, sliding_window: Optional[int],
                        softcap: Optional[float], kv_bits: int):
    # QUANTIZED pools only: their scale pools do not walk yet (ROADMAP
    # S1b), so they keep the grid every pool had before the walk — a
    # step for every RAGGED_BLOCK_Q query rows and every entry of the
    # page table, the steps past the block's frontier skipped but paid,
    # every page copied again by every 8-row block, per-page scale
    # blocks riding the kv index map and dequantized inside
    # _prefill_accumulate.
    qb = pl.program_id(0)
    sb = pl.program_id(1)

    @pl.when(sb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    seq = blkseq_ref[qb]
    q_start = qoffs_ref[seq] + blkq_ref[qb]
    valid = valid_ref[seq]
    lo, hi = _prefill_blk_bounds(q_start, valid, RAGGED_BLOCK_Q,
                                 page_size, sliding_window)

    @pl.when((sb >= lo) & (sb <= hi))
    def _compute():
        for khi in range(kh):
            m_scr[khi], l_scr[khi], acc_scr[khi] = _prefill_accumulate(
                q_ref[khi].reshape(group * RAGGED_BLOCK_Q, -1),
                k_ref[0, :, khi, :], v_ref[0, :, khi, :], q_start,
                sb * page_size, valid,
                (m_scr[khi], l_scr[khi], acc_scr[khi]), group=group,
                block_q=RAGGED_BLOCK_Q, block_kv=page_size,
                sliding_window=sliding_window, softcap=softcap,
                k_scale=ks_ref[0, :, khi, :], v_scale=vs_ref[0, :, khi, :],
                kv_bits=kv_bits)

    @pl.when(sb == num_page_blocks - 1)
    def _finish():
        d = o_ref.shape[-1]
        for khi in range(kh):
            l = jnp.maximum(l_scr[khi, :, :1], 1e-30)
            o_ref[khi] = (acc_scr[khi] / l).astype(o_ref.dtype) \
                .reshape(group, RAGGED_BLOCK_Q, d)


def _ragged_grid_attention(qt, k_pool, v_pool, k_scale, v_scale, meta, *,
                           kv_bits: int, sliding_window, softcap,
                           interpret: bool):
    """ragged_paged_attention over QUANTIZED pools (_ragged_grid_kernel):
    `qt` [K, G, T, D], `meta` the five scalar-prefetched arrays."""
    kh, group, t, d = qt.shape
    page_size = k_pool.shape[1]

    def kv_index(qb, sb, table_ref, blkseq_ref, blkq_ref, qoffs_ref,
                 valid_ref):
        seq = blkseq_ref[qb]
        q_start = qoffs_ref[seq] + blkq_ref[qb]
        lo_blk, hi_blk = _prefill_blk_bounds(
            q_start, valid_ref[seq], RAGGED_BLOCK_Q, page_size,
            sliding_window)
        sb = jnp.clip(sb, lo_blk, jnp.maximum(hi_blk, 0))
        return (table_ref[seq, sb], 0, 0, 0)

    def rows_blk(width):
        return pl.BlockSpec((kh, group, RAGGED_BLOCK_Q, width),
                            lambda qb, sb, *_: (0, 0, qb, 0))

    rows = group * RAGGED_BLOCK_Q
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(t // RAGGED_BLOCK_Q, meta[0].shape[1]),
        in_specs=[rows_blk(d)] + [
            pl.BlockSpec((1,) + p.shape[1:], kv_index)
            for p in (k_pool, v_pool, k_scale, v_scale)],
        out_specs=rows_blk(d),
        scratch_shapes=[
            pltpu.VMEM((kh, rows, _LANES), jnp.float32),
            pltpu.VMEM((kh, rows, _LANES), jnp.float32),
            pltpu.VMEM((kh, rows, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _ragged_grid_kernel, page_size=page_size,
        num_page_blocks=meta[0].shape[1], kh=kh, group=group,
        sliding_window=sliding_window, softcap=softcap, kv_bits=kv_bits)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qt.shape, qt.dtype),
        interpret=interpret,
        name="ragged_paged_attention",
    )(*meta, qt, k_pool, v_pool, k_scale, v_scale)


# What the ragged walk asks the compiler for, and what its own estimate
# is held to. A v5e core has 128 MiB of VMEM, of which a kernel gets the
# 16 MiB default scope unless it says otherwise; the walk holds a whole
# query block's softmax state beside its q, out and page buffers, and
# the products' temporaries come on top — the third between the two.
_RAGGED_VMEM_LIMIT = 48 * 1024 * 1024
_RAGGED_VMEM_BUDGET = 32 * 1024 * 1024
# kv columns of one product: every page more in a trip divides the
# per-row work of a product (the rescaling of m, l and acc, their loads
# and stores) by the pages it covers.
_RAGGED_TRIP_COLS = 512


def _ragged_trip_pages(page_size: int) -> int:
    """Pages a trip of the ragged walk copies and multiplies at once."""
    return max(1, min(_RAGGED_TRIP_COLS // page_size, 4))


def _ragged_tile_rows(group: int, block_q: int) -> int:
    """Query rows of one product of the ragged walk: a block is
    multiplied tile by tile so that a short run pays for its own rows
    and not for the block's. A tile is whole bf16 sublane tiles (16
    rows) where the block has them, and with the GQA group 128 rows of
    the product at least (on a v5e, kernel alone: at 32/8 x 128 a
    leaders' segment takes 1.12 ms in tiles of 32 rows against 1.45 in
    tiles of 16, fifteen verify tiles 0.53 against 0.46; at group 16,
    tiles of 16 beat 32 and 64 on every shape)."""
    r = 16
    while r * group < 128 and r < block_q:
        r *= 2
    return min(r, block_q)


def _ragged_vmem_est(block_q: int, page_size: int, d: int, kh: int,
                     group: int, *, dk: int, dv: int, itemsize: int = 2,
                     q_itemsize: int = 2, latent: bool = False) -> int:
    """What the ragged walk DECLARES for a query block of `block_q`
    rows, in bytes: the float32 softmax state of the block (m and l a
    lane row each, acc), its q and out blocks (double-buffered by the
    pipeline), and three times a trip's pages — two slots a pool, keys
    and (unless latent) values as the pool holds them, and the trip's
    kv heads once more, sliced out dense."""
    rows = kh * group * block_q
    state = rows * (2 * _LANES + dv) * 4
    q_out = 2 * rows * (d + dv) * q_itemsize
    page = page_size * kh * (dk if latent else dk + dv) * itemsize
    return state + q_out + 3 * _ragged_trip_pages(page_size) * page


def _ragged_block_q(t: int, page_size: int, d: int, kh: int, group: int,
                    **shape) -> Optional[int]:
    """Query rows a grid step of the ragged walk holds: the largest
    block that divides the flat buffer and fits the VMEM budget — 128
    at 32 heads x 128, 32 in latent mode (64 heads x 640) — or None
    when not even the packing's own 8 fit."""
    for bq in (128, 64, 32, 16, 8):
        if t % bq == 0 and _ragged_vmem_est(
                bq, page_size, d, kh, group,
                **shape) <= _RAGGED_VMEM_BUDGET:
            return bq
    return None


def ragged_query_block(t: int, page_size: int, d: int, kh: int,
                       group: int, *, dk: Optional[int] = None,
                       dv: Optional[int] = None, quantized: bool = False,
                       **shape) -> int:
    """Query rows the ragged kernel multiplies a page against at once
    for a flat buffer of `t` rows and this pool shape
    (ragged_decline_reason's arguments): the walk's block, or the
    packing's own 8 where the grid kernel serves (quantized pools)."""
    if quantized:
        return RAGGED_BLOCK_Q
    return _ragged_block_q(t, page_size, d, kh, group, dk=dk or d,
                           dv=dv or d, **shape)


def _ragged_tile_kinds(seq_of_block, block_qstart, tiles: int, xp):
    """(brk, pad) per packing tile (RAGGED_BLOCK_Q rows), `xp` numpy or
    jax.numpy: does a new stretch begin at the tile — another sequence,
    a run that does not carry on from the tile before, or a new query
    block of `tiles` tiles — and is the tile one of the inert pad tiles
    behind the last run, all but the first of which repeat their
    sequence's start (block_qstart 0 behind a tile of the same
    sequence)."""
    nb = seq_of_block.shape[0]
    idx = xp.arange(nb, dtype=xp.int32)
    prev_seq = xp.concatenate([xp.full((1,), -1, xp.int32),
                               seq_of_block[:-1]])
    prev_q = xp.concatenate([xp.zeros((1,), xp.int32), block_qstart[:-1]])
    same = seq_of_block == prev_seq
    cont = same & (block_qstart == prev_q + RAGGED_BLOCK_Q)
    return ~cont | (idx % tiles == 0), same & (block_qstart == 0)


def _ragged_segments(seq_of_block, block_qstart, tiles: int):
    """Per packing tile, how many tiles the run SEGMENT that starts
    there spans, else 0. A segment is the stretch of one sequence's run
    inside one query block of `tiles` tiles: the kernel walks a
    segment's pages once. Of the inert pad tiles only the first is a
    segment — the rest cost the kernel one skipped iteration each and
    read nothing."""
    nb = seq_of_block.shape[0]
    idx = jnp.arange(nb, dtype=jnp.int32)
    brk, pad = _ragged_tile_kinds(seq_of_block, block_qstart, tiles, jnp)
    nxt = jnp.concatenate([jnp.where(brk, idx, nb)[1:],
                           jnp.full((1,), nb, jnp.int32)])
    end = jax.lax.cummin(nxt, axis=0, reverse=True)
    return jnp.where(brk & ~pad, end - idx, 0).astype(jnp.int32)


def _ragged_next_segment(segments, tiles: int):
    """Per packing tile, the tile OF ITS QUERY BLOCK at which the
    block's next segment starts, else -1: the walk starts that
    segment's first trip before it waits for this one's last."""
    nb = segments.shape[0]
    idx = jnp.arange(nb, dtype=jnp.int32)
    at = jnp.concatenate([jnp.where(segments > 0, idx, nb)[1:],
                          jnp.full((1,), nb, jnp.int32)])
    nxt = jax.lax.cummin(at, axis=0, reverse=True)
    return jnp.where((nxt < nb) & (nxt // tiles == idx // tiles),
                     nxt % tiles, -1).astype(jnp.int32)


def ragged_page_visits(batch: dict, *, page_size: int, block_q: int,
                       sliding_window: Optional[int] = None
                       ) -> tuple[int, int]:
    """(page_visits, page_visits_by_eights) of one ragged dispatch, on
    the host from serving_loop.build_ragged_batch's arrays: the sum over
    runs of (query blocks x pages each reads) with query blocks of
    `block_q` rows — the walk's segments — and the same sum at the
    packing's 8-row blocks, which is what the grid kernel did (and does
    for quantized pools). Their quotient is how far the walk reaches on
    the traffic at hand: about 1 for a segment of decode rows and
    verify tiles, 10-30 for a leaders' segment."""
    import numpy as np
    seq = np.asarray(batch["seq_of_block"], np.int32)
    q_tile = np.asarray(batch["block_qstart"], np.int32)
    first_pos = np.asarray(batch["query_offsets"])[seq] + q_tile
    valid = np.asarray(batch["kv_valid"])[seq]

    def visits(tiles: int) -> int:
        brk, pad = _ragged_tile_kinds(seq, q_tile, tiles, np)
        at = np.flatnonzero(brk)
        rows = np.diff(np.append(at, len(seq))) * RAGGED_BLOCK_Q
        at, rows = at[~pad[at]], rows[~pad[at]]
        # _prefill_blk_bounds, in numpy: nothing here touches a device
        hi = np.minimum((first_pos[at] + rows - 1) // page_size,
                        (valid[at] - 1) // page_size)
        lo = 0 if sliding_window is None else np.maximum(
            0, (first_pos[at] - sliding_window + 1) // page_size)
        return int(np.maximum(hi - lo + 1, 0).sum())

    return visits(block_q // RAGGED_BLOCK_Q), visits(1)


def _ragged_kernel(table_ref, blkseq_ref, blkq_ref, seg_ref, next_ref,
                   qoffs_ref, valid_ref, q_ref, *rest,
                   page_size: int, n: int, block_q: int, tile: int,
                   kh: int, group: int, token_major: bool, words: bool,
                   sliding_window: Optional[int],
                   softcap: Optional[float],
                   v_dim: Optional[int] = None):
    # See "the ragged walk" above. Grid (T / block_q,): a step holds the
    # q and out blocks of block_q flat rows and the softmax state of
    # every one of them. `rest`: the pools in HBM (k, and v unless
    # latent), the output block, one two-slot buffer of n pages a pool,
    # m / l / acc, the DMA semaphores, `flow` (SMEM: trips so far in
    # this step — the slot in turn is its parity — and whether the
    # segment about to start had its first trip started already) and,
    # for a token-major pool, a trip's kv heads sliced out dense.
    n_pools = 2 if v_dim is None else 1
    hbms, o_ref = rest[:n_pools], rest[n_pools]
    bufs = rest[n_pools + 1:2 * n_pools + 1]
    m_scr, l_scr, acc_scr, sem, flow = \
        rest[2 * n_pools + 1:2 * n_pools + 6]
    heads = rest[2 * n_pools + 6:]
    tiles = block_q // RAGGED_BLOCK_Q
    first = pl.program_id(0) * tiles
    cols = n * page_size                # kv columns of one product
    per_head = v_dim is None and not token_major
    if words:
        # A bfloat16 page as the 32-bit words it is stored in: a
        # token's K heads are K/2 rows of D words, a word a PAIR of
        # heads (the even one its low half).
        hbms = [h.reshape(h.shape[0], page_size * kh, h.shape[-1])
                .bitcast(jnp.uint32) for h in hbms]

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    flow[0] = 0
    flow[1] = 0

    @pl.when(pl.program_id(0) == 0)
    def _():
        # A trip's last pages may lie past the segment's frontier and
        # are then not copied: what the slot holds there is masked out
        # of the scores, but the weighted sum multiplies it by zero —
        # which a NaN left in fresh VMEM would survive.
        for buf in bufs:
            buf[...] = jnp.zeros_like(buf)

    def copies(seq, lo, hi, t, slot, go):
        # Start (go) or await trip t of a segment into `slot`; a wait
        # only needs the copy's shape, not its source page. A head-major
        # page [K*ps, D] is K dense blocks: each lands behind its own
        # head's blocks of the trip's other pages. (A loop, not n
        # copies of its body: the compiler takes a tenth less time over
        # it on a checkout's first run.)
        page_rows = page_size * kh // 2 if words else page_size

        def one_page(j, carry):
            at = lo + t * n + j
            rows = pl.ds(pl.multiple_of(j * page_rows, page_rows),
                         page_rows)

            @pl.when(at <= hi)
            def _():
                page = table_ref[seq, at] if go else 0
                for i, (hbm, buf) in enumerate(zip(hbms, bufs)):
                    if not per_head:
                        pairs = [(hbm.at[page], buf.at[slot, rows])]
                    else:
                        pairs = [(hbm.at[page, pl.ds(khi * page_size,
                                                     page_size)],
                                  buf.at[slot, khi, rows])
                                 for khi in range(kh)]
                    for src, dst in pairs:
                        c = pltpu.make_async_copy(src, dst,
                                                  sem.at[i, slot])
                        c.start() if go else c.wait()
            return carry

        jax.lax.fori_loop(0, n, one_page, 0)

    def stage(slot):
        # A token-major trip's kv heads, sliced out once into dense
        # [n*ps, D] blocks: the strided read is paid here and not by
        # every tile of products. As 32-bit words it is one strided
        # load a pair of heads, which two shifts part — a bfloat16 is
        # the high half of its float32 (on a v5e a quarter of the time
        # the same slice takes as a read of bfloat16 rows).
        if not token_major or v_dim is not None:
            return
        for buf, dense in zip(bufs, heads):
            if not words:
                for khi in range(kh):
                    dense[khi] = buf[slot, :, khi, :]
                continue
            for pair in range(kh // 2):
                w = buf[slot, pl.ds(pair, cols, stride=kh // 2), :]
                for i, half in enumerate((w << 16,
                                          w & jnp.uint32(0xFFFF0000))):
                    dense[2 * pair + i] = jax.lax.bitcast_convert_type(
                        half, jnp.float32).astype(dense.dtype)

    def head(slot, khi):
        # (keys, values) of kv head khi of the trip in `slot`
        if v_dim is not None:
            k = bufs[0][slot]
            return k, k[:, :v_dim]
        if token_major:
            return tuple(dense[khi] for dense in heads)
        return tuple(buf[slot, khi] for buf in bufs)

    def span(t):
        # (sequence, first position, valid length, first and last page,
        # trips) of the segment that starts at tile t of this block
        seq = blkseq_ref[first + t]
        q_start = qoffs_ref[seq] + blkq_ref[first + t]
        valid = valid_ref[seq]
        lo, hi = _prefill_blk_bounds(
            q_start, valid, seg_ref[first + t] * RAGGED_BLOCK_Q,
            page_size, sliding_window)
        return (seq, q_start, valid, lo, hi,
                jax.lax.div(jnp.maximum(hi - lo + 1, 0) + (n - 1), n))

    def segment(t, n_tiles):
        seq, q_start, valid, lo, hi, trips = span(t)
        a = t * RAGGED_BLOCK_Q              # live rows [a, b) of the block
        b = a + n_tiles * RAGGED_BLOCK_Q
        g0, started = flow[0], flow[1]
        nt = next_ref[first + t]
        nseq, _, _, nlo, nhi, ntrips = span(jnp.maximum(nt, 0))
        hand_on = (nt >= 0) & (ntrips > 0) & (trips > 0)

        @pl.when((trips > 0) & (started == 0))
        def _():
            copies(seq, lo, hi, 0, g0 & 1, True)

        def trip(p, carry):
            slot = (g0 + p) & 1

            # under this trip's products: my own next trip, or behind
            # my last the first of the block's next segment
            own = p + 1 < trips

            @pl.when(own | hand_on)
            def _():
                copies(jnp.where(own, seq, nseq), jnp.where(own, lo, nlo),
                       jnp.where(own, hi, nhi), jnp.where(own, p + 1, 0),
                       1 - slot, True)

            copies(seq, lo, hi, p, slot, False)
            stage(slot)
            kv_start = (lo + p * n) * page_size
            # rows before the first that reaches this trip attend none
            # of it: the block's tiles from that row's on
            t_lo = jax.lax.div(a + jnp.maximum(kv_start - q_start, 0), tile)
            t_hi = jax.lax.div(b + (tile - 1), tile)

            def one_tile(ti, c):
                # The kv heads' products are independent of one another
                # and share the mask: unrolled side by side, one's
                # latency hides behind the others' work.
                off = pl.multiple_of(ti * tile, tile)
                mask = _prefill_mask(
                    q_start + off - a, kv_start, valid, group=group,
                    block_q=tile, block_kv=cols,
                    sliding_window=sliding_window, rows=(a - off, b - off))
                for khi in range(kh):
                    k, v = head(slot, khi)
                    at = (khi, slice(None), pl.ds(off, tile), slice(None))
                    state = _prefill_accumulate(
                        q_ref[at].reshape(group * tile, -1), k, v,
                        None, None, None,
                        tuple(r[at].reshape(group * tile, r.shape[-1])
                              for r in (m_scr, l_scr, acc_scr)),
                        group=group, block_q=tile, block_kv=cols,
                        sliding_window=None, softcap=softcap, mask=mask)
                    for r, x in zip((m_scr, l_scr, acc_scr), state):
                        r[at] = x.reshape(group, tile, r.shape[-1])
                return c

            jax.lax.fori_loop(t_lo, t_hi, one_tile, 0)
            return carry

        jax.lax.fori_loop(0, trips, trip, 0)
        flow[0] = g0 + trips
        flow[1] = hand_on.astype(jnp.int32)

    def one(t, carry):
        n_tiles = seg_ref[first + t]

        @pl.when(n_tiles > 0)
        def _():
            segment(t, n_tiles)

        return carry

    jax.lax.fori_loop(0, tiles, one, 0)
    for khi in range(kh):
        l = jnp.maximum(l_scr[khi][..., :1], 1e-30)
        o_ref[khi] = (acc_scr[khi] / l).astype(o_ref.dtype)


def ragged_paged_attention(
    q: jax.Array,                 # [T, H, D] flat token buffer
    k_pool: jax.Array,            # [P, page_size, K, D] page pool
    v_pool: Optional[jax.Array],  # [P, page_size, K, D]; None: latent
    tables: jax.Array,            # [S, pages_per_seq] int32 page tables
    seq_of_block: jax.Array,      # [T/8] sequence id of each q block
    block_qstart: jax.Array,      # [T/8] block start row WITHIN its seq
    query_offsets: jax.Array,     # [S] absolute position of seq's row 0
    kv_valid: jax.Array,          # [S] valid kv entries AFTER this call
    *,
    sliding_window: Optional[int] = None,
    softcap: Optional[float] = None,
    interpret: Optional[bool] = None,
    k_scale: Optional[jax.Array] = None,   # [P, ps, K, G] (ISSUE 11)
    v_scale: Optional[jax.Array] = None,
    kv_bits: int = 8,
    v_dim: Optional[int] = None,  # latent pool: values = keys[..., :v_dim]
) -> jax.Array:
    """Mixed prefill/decode attention over a flat token buffer, straight
    off the page pool.

    The flat buffer holds each sequence's query tokens as a contiguous
    run aligned to RAGGED_BLOCK_Q rows (the host builder pads runs with
    inert rows); row j of sequence s has absolute position
    query_offsets[s] + (row within the run), causal within the segment.
    The caller must have scattered every real token's K/V into its
    sequence's frontier pages already (engine/paged_forward.py). One
    compiled shape serves every prefill/decode composition of the same
    T — the no-recompile property the scheduler's ragged segments rely
    on. Each run's pages are walked once a query block, up to the
    block's causal frontier (see "the ragged walk" above). Returns
    [T, H, D] in q's dtype; pad-row outputs are finite garbage (zeros
    for the inert pad tiles) and must be dropped by the caller.
    `v_pool=None` with `v_dim`: a latent pool (paged_prefill_attention),
    the result [T, H, v_dim].
    """
    packed = _on_packed(
        ragged_paged_attention, q, k_pool, v_pool, tables, seq_of_block,
        block_qstart, query_offsets, kv_valid,
        sliding_window=sliding_window, softcap=softcap,
        interpret=interpret, k_scale=k_scale, v_scale=v_scale,
        kv_bits=kv_bits, v_dim=v_dim)
    if packed is not None:
        return packed
    t, h, d = q.shape
    page_size, kh = k_pool.shape[1], _pool_heads(k_pool)
    latent = v_pool is None
    dv = v_dim if latent else d
    group = h // kh
    quantized = k_scale is not None
    if t % RAGGED_BLOCK_Q:
        raise ValueError(
            f"flat buffer T={t} must be a multiple of {RAGGED_BLOCK_Q}")
    shape = dict(dk=k_pool.shape[-1], dv=dv,
                 itemsize=k_pool.dtype.itemsize,
                 q_itemsize=q.dtype.itemsize, latent=latent)
    reason = ragged_decline_reason(page_size, d, kh, group,
                                   quantized=quantized, **shape)
    if reason is not None:
        raise ValueError(f"unsupported ragged shape: {reason}")
    interpret = _interpret() if interpret is None else interpret
    # Wrapper-level count (trace time under jit, per call eagerly):
    # lets direct-kernel unit tests satisfy the ragged_attn guard; the
    # engine seam's per-dispatch count is the exact provenance.
    note_ragged_dispatch(kernel=True)

    meta = [a.astype(jnp.int32) for a in (
        tables, seq_of_block, block_qstart, query_offsets, kv_valid)]
    if quantized:
        # [T, H, D] → [K, G, T, D]: q heads grouped by their kv head
        out = _ragged_grid_attention(
            q.reshape(t, kh, group, d).transpose(1, 2, 0, 3), k_pool,
            v_pool, k_scale, v_scale, meta, kv_bits=kv_bits,
            sliding_window=sliding_window, softcap=softcap,
            interpret=interpret)
        return out.transpose(2, 0, 1, 3).reshape(t, h, dv)
    return _ragged_walk(q, k_pool, v_pool, *meta,
                        sliding_window=sliding_window, softcap=softcap,
                        interpret=interpret,
                        v_dim=v_dim if latent else None)


@functools.partial(jax.jit, static_argnames=(
    "sliding_window", "softcap", "interpret", "v_dim"))
def _ragged_walk(q, k_pool, v_pool, tables, seq_of_block, block_qstart,
                 query_offsets, kv_valid, *, sliding_window, softcap,
                 interpret: bool, v_dim: Optional[int]):
    """ragged_paged_attention over unquantized pools (_ragged_kernel).
    A jit of its own: a model's layers call it with the same shapes, so
    the kernel's body is traced once a process and lowered once a
    program — not once a layer a program, which is what an unrolled
    layer's pallas_call costs at every start (PERF.md, set-up)."""
    t, h, d = q.shape
    page_size, kh = k_pool.shape[1], _pool_heads(k_pool)
    latent = v_pool is None
    dv = v_dim if latent else d
    group = h // kh
    block_q = _ragged_block_q(
        t, page_size, d, kh, group, dk=k_pool.shape[-1], dv=dv,
        itemsize=k_pool.dtype.itemsize, q_itemsize=q.dtype.itemsize,
        latent=latent)
    segments = _ragged_segments(seq_of_block, block_qstart,
                                block_q // RAGGED_BLOCK_Q)
    following = _ragged_next_segment(segments,
                                     block_q // RAGGED_BLOCK_Q)
    n = min(_ragged_trip_pages(page_size), tables.shape[1])
    token_major = latent or _token_major(kh, k_pool.dtype.itemsize)
    pools = [k_pool] if latent else [k_pool, v_pool]
    words = (token_major and not latent
             and k_pool.dtype == jnp.bfloat16)
    if words:
        # a trip's pages one behind the other, as the 32-bit words a
        # token's pairs of heads are stored in
        bufs = [pltpu.VMEM((2, n * page_size * kh // 2, p.shape[-1]),
                           jnp.uint32) for p in pools]
    elif token_major:
        # ... or as the pool holds them
        bufs = [pltpu.VMEM((2, n * page_size) + p.shape[2:], p.dtype)
                for p in pools]
    else:
        # the pool as XLA stores it, head-major: see "the walk" above
        pools = [p.swapaxes(1, 2).reshape(
            p.shape[0], kh * page_size, p.shape[-1]) for p in pools]
        bufs = [pltpu.VMEM((2, kh, n * page_size, p.shape[-1]), p.dtype)
                for p in pools]
    dense = [pltpu.VMEM((kh, n * page_size, p.shape[-1]), p.dtype)
             for p in pools if token_major and not latent]

    def rows_blk(width):
        return pl.BlockSpec((kh, group, block_q, width),
                            lambda i, *_: (0, 0, i, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(t // block_q,),
        in_specs=[rows_blk(d)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
        out_specs=rows_blk(dv),
        scratch_shapes=bufs + [
            pltpu.VMEM((kh, group, block_q, _LANES), jnp.float32),
            pltpu.VMEM((kh, group, block_q, _LANES), jnp.float32),
            pltpu.VMEM((kh, group, block_q, dv), jnp.float32),
            pltpu.SemaphoreType.DMA((len(pools), 2)),
            pltpu.SMEM((2,), jnp.int32)] + dense,
    )
    kernel = functools.partial(
        _ragged_kernel, page_size=page_size, n=n, block_q=block_q,
        tile=_ragged_tile_rows(group, block_q), kh=kh, group=group,
        token_major=token_major, words=words,
        sliding_window=sliding_window, softcap=softcap, v_dim=v_dim)
    # [T, H, D] → [K, G, T, D]: q heads grouped by their kv head
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((kh, group, t, dv), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_RAGGED_VMEM_LIMIT),
        name="mla_ragged" if latent else "ragged_paged_attention",
    )(tables, seq_of_block, block_qstart, segments, following,
      query_offsets, kv_valid,
      q.reshape(t, kh, group, d).transpose(1, 2, 0, 3), *pools)
    return out.transpose(2, 0, 1, 3).reshape(t, h, dv)


def ragged_paged_spmd(
    mesh,
    q: jax.Array,                 # [T, H, D] flat token buffer
    k_pool: jax.Array, v_pool: jax.Array,
    tables: jax.Array, seq_of_block: jax.Array,
    block_qstart: jax.Array, query_offsets: jax.Array,
    kv_valid: jax.Array,
    *,
    sliding_window: Optional[int] = None,
    softcap: Optional[float] = None,
    interpret: Optional[bool] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    kv_bits: int = 8,
) -> Optional[jax.Array]:
    """ragged_paged_attention under a model-axis mesh via shard_map —
    the flash_attention_spmd head-sharding pattern: kv heads ride
    "model" (matching the pool's sharding), q heads follow their kv
    head, and the flat token buffer plus every metadata array stays
    replicated (attention is embarrassingly parallel over kv heads, so
    the body needs no collectives). Returns None when the head layout
    doesn't partition, or when the mesh has a data axis — the pool's
    page axis shards over "data" on those meshes and a flat buffer
    mixing replicas' rows cannot (the engine then serves the prologue
    path and records the reason)."""
    from ..compat import shard_map
    from jax.sharding import PartitionSpec as P

    t, h, d = q.shape
    page_size, kh = k_pool.shape[1], k_pool.shape[2]
    axes = dict(mesh.shape)
    if axes.get("data", 1) > 1:
        return None
    n_model = axes.get("model", 1)
    if not spmd_partitionable(h, kh, n_model):
        return None
    kv_head_ax = "model" if n_model > 1 and kh % n_model == 0 else None
    head_ax = "model" if n_model > 1 else None
    kh_local = kh // n_model if kv_head_ax else kh
    if not ragged_supported(page_size, d, kh_local, h // kh):
        return None

    q_spec = P(None, head_ax, None)
    pool_spec = P(None, None, kv_head_ax, None)
    meta2 = P(None, None)
    meta1 = P(None)
    quantized = k_scale is not None

    def body(ql, kp, vp, tl, bl, bq, qo, vl, *sc):
        ks, vs = sc if sc else (None, None)
        return ragged_paged_attention(
            ql, kp, vp, tl, bl, bq, qo, vl,
            sliding_window=sliding_window, softcap=softcap,
            interpret=interpret, k_scale=ks, v_scale=vs,
            kv_bits=kv_bits)

    in_specs = (q_spec, pool_spec, pool_spec, meta2,
                meta1, meta1, meta1, meta1)
    args = [q, k_pool, v_pool, tables.astype(jnp.int32),
            seq_of_block.astype(jnp.int32),
            block_qstart.astype(jnp.int32),
            query_offsets.astype(jnp.int32),
            kv_valid.astype(jnp.int32)]
    if quantized:
        in_specs += (pool_spec, pool_spec)
        args += [k_scale, v_scale]
    fn = shard_map(body, mesh=mesh,
                   in_specs=in_specs,
                   out_specs=q_spec, check_vma=False)
    return fn(*args)


def ragged_decode_attention(
    q: jax.Array,                 # [B, 1, H, D] this step's query
    k: jax.Array,                 # [B, S, K, D] cache incl. this step's K
    v: jax.Array,                 # [B, S, K, D]
    kv_valid: jax.Array,          # [B] valid entries INCLUDING this step
    *,
    sliding_window: Optional[int] = None,
    softcap: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Single-position attention over each row's valid cache prefix.

    The query position is kv_valid-1 (decode always appends), so causality
    reduces to kv_pos < kv_valid. Returns [B, 1, H, D].
    """
    b, t, h, d = q.shape
    assert t == 1, "decode kernel serves exactly one position"
    s, kh = k.shape[1], k.shape[2]
    group = h // kh
    block_kv = _pick_block(s, (512, 256, 128, 64, 32, 16, 8))
    if block_kv is None:
        raise ValueError(f"unsupported cache length S={s}")
    interpret = _interpret() if interpret is None else interpret
    if not interpret and lane_pack(kh, d) > 1:
        return _cache_packed(
            ragged_decode_attention, q, k, v, kv_valid,
            sliding_window=sliding_window, softcap=softcap,
            interpret=interpret)

    # [B, 1, H, D] → [B, K, G, D]: rows of one kv-head's query group
    qt = q[:, 0].reshape(b, kh, group, d)
    kt = k.transpose(0, 2, 1, 3)        # [B, K, S, D]
    vt = v.transpose(0, 2, 1, 3)
    num_kv_blocks = s // block_kv

    def kv_index(bi, khi, sb, valid_ref):
        hi_blk = (valid_ref[bi] - 1) // block_kv
        if sliding_window is None:
            lo_blk = jnp.int32(0)
        else:
            lo_blk = jnp.maximum(
                0, (valid_ref[bi] - sliding_window) // block_kv)
        sb = jnp.clip(sb, lo_blk, jnp.maximum(hi_blk, 0))
        return (bi, khi, sb, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kh, num_kv_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, group, d),
                         lambda bi, khi, sb, v_: (bi, khi, 0, 0)),
            pl.BlockSpec((1, 1, block_kv, d), kv_index),
            pl.BlockSpec((1, 1, block_kv, d), kv_index),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, group, d),
            lambda bi, khi, sb, v_: (bi, khi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, _LANES), jnp.float32),
            pltpu.VMEM((group, _LANES), jnp.float32),
            pltpu.VMEM((group, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel, block_kv=block_kv, num_kv_blocks=num_kv_blocks,
        group=group, sliding_window=sliding_window, softcap=softcap)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        interpret=interpret,
        name="ragged_decode_attention",
    )(kv_valid.astype(jnp.int32), qt, kt, vt)
    return out.reshape(b, 1, h, d)
