"""Pool-direct paged serving forward (VERDICT r2 weak #7).

The engine's fallback paths gather `pool[table]` into a
position-aligned `[B, S, K, D]` view (the gather view) —
kernel-agnostic and correct, but the view exists ALONGSIDE the pool
(every row's whole max_seq_len span, the HBM budget paging exists to
avoid) and the gather/scatter traffic scales with max_seq_len rather
than tokens cached, per prefill chunk and per decode segment.

This module serves STRAIGHT off the pools — decode steps AND prefill
chunks: each layer scatters its K/V into the rows' pages (a [B, T]
position-indexed `.at[].set`), then attends through the page-table-
aware kernels (pallas paged_decode_attention / paged_prefill_attention)
whose kv block index maps read the table and fetch only pages inside
each row's causal/valid frontier. All block wiring (norms, residuals,
MLP, every family flag) comes from models/common.transformer_block via
its attn_fn hook — the same seam the ring/Ulysses cores use — so the
math is defined in exactly one place.

Write-exclusivity invariant: the engine's ensure_capacity copy-on-writes
any shared page in a row's write range before dispatch, and distinct
batch rows are distinct slots owning their frontier pages exclusively,
so the per-step scatter never touches an aliased page.

Multi-device: the kernel runs under shard_map via paged_decode_spmd
(kv heads on "model" — matching the engine's pool sharding — batch
rows on "data"). With pool_replicas > 1 the pool's page axis is also
data-sharded and the caller must deliver replica-grouped, padded
batches (engine ReplicaGroupPlan); the kernels rebase tables to each
shard's local page range. Head layouts that don't partition fall back
to the engine's gather-view serving at build time (engine.paged_direct),
so this module never traces an unpartitionable kernel.
"""

from __future__ import annotations

import collections
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import kv_quant as kvq
from .models import diffattn, mla
from .models.common import (MASK_VALUE, ModelConfig, Params, _einsum,
                            _softcap, current_spmd_mesh, embed_tokens,
                            gate_heads, gather_rows, layer_body, mlp,
                            project_qkv, rms_norm, transformer_block)
from .pallas import attention as pattn


def _cells(entries: jax.Array, pool: jax.Array) -> jax.Array:
    """A layer's keys or values [..., K, D] as the pool's cells: the same
    bytes where the pool holds two heads a lane row ([..., K / 2, 2 D]:
    ModelConfig.lane_pack; a latent entry [..., W] as it is)."""
    lead = entries.ndim - (pool.ndim - 2)
    return entries.reshape(entries.shape[:lead] + pool.shape[2:])


def _write_cells(pool: jax.Array, pages: jax.Array, offs: jax.Array,
                 entries: jax.Array) -> jax.Array:
    """`pool` with `entries` [..., K, D] written at (pages, offs) [...]:
    `pool.at[pages, offs].set(...)`, in the form XLA updates in place. A
    pool whose rows a token do not fill whole tiles (pallas/attention.py,
    `_token_major`: not 2, 4 or a multiple of 8) is STORED head-major,
    physically [P, K, ps, D]; a scatter over the row-major [P, ps, K, D]
    makes XLA re-lay the whole pool out and back on every call (PERF.md,
    PR 56: eight pools of 210 MB a decode step). Such a pool is written
    through the view it is stored in, [P, K * ps, D] — a bitcast, as the
    walks read it — K rows a token. (One device: a pool sharded over its
    heads keeps the row-major scatter GSPMD partitions.)"""
    cells = _cells(entries, pool)
    mesh = current_spmd_mesh()
    if (pool.ndim != 4 or pool.shape[2] == 1
            or pattn._token_major(pool.shape[2], pool.dtype.itemsize)
            or (mesh is not None and mesh.size > 1)):
        return pool.at[pages, offs].set(cells)
    p, ps, k, d = pool.shape
    rows = jnp.arange(k) * ps + offs[..., None]              # [..., K]
    view = pool.swapaxes(1, 2).reshape(p, k * ps, d)
    view = view.at[pages[..., None], rows].set(cells)
    return view.reshape(p, k, ps, d).swapaxes(1, 2)


@layer_body(static=("cfg", "pool_replicas", "quant_spec", "kernel_quant"))
def _paged_block(x, layer, pools, positions, pages, offs, table,
                 kv_valid_len, *, cfg: ModelConfig, pool_replicas: int,
                 quant_spec, kernel_quant: bool):
    """forward_paged's layer as a body (models/common.layer_body): one
    block over `pools` — that layer's (k_pool, v_pool, k_scale, v_scale),
    the scales None where the pool is not quantized — and the dispatch's
    arrays. -> (x, the four as the block leaves them)."""
    k_pool, v_pool, k_sc, v_sc = pools
    quant = k_sc is not None
    kv_bits = quant_spec.bits if quant else 8
    t = positions.shape[1]
    page_size = k_pool.shape[1]

    def attn_fn(h, layer):
        q, k, v = project_qkv(h, layer, cfg, positions)
        # Scatter this call's K/V into the rows' pages (write ranges
        # are exclusive after COW, see module docstring) BEFORE the
        # kernel reads the pool — quantize-on-write when the pool
        # is quantized (per-cell scales: a token's write never
        # touches its neighbours' quantization).
        if quant:
            k_q, k_s = kvq.quantize_cells(k, quant_spec)
            v_q, v_s = kvq.quantize_cells(v, quant_spec)
            k_pool2 = k_pool.at[pages, offs].set(k_q)
            v_pool2 = v_pool.at[pages, offs].set(v_q)
            k_sc2 = k_sc.at[pages, offs].set(k_s)
            v_sc2 = v_sc.at[pages, offs].set(v_s)
        else:
            k_pool2 = _write_cells(k_pool, pages, offs, k)
            v_pool2 = _write_cells(v_pool, pages, offs, v)
            k_sc2 = v_sc2 = None
        if quant and not kernel_quant:
            # Declined shape: dequantize the pool for a bf16 kernel
            # call (direct-caller fallback — the engine's serving
            # path uses the gather view for these shapes).
            kp, vp = (kvq.dequantize_cells(k_pool2, k_sc2,
                                           quant_spec, q.dtype),
                      kvq.dequantize_cells(v_pool2, v_sc2,
                                           quant_spec, q.dtype))
            ks = vs = None
        else:
            kp, vp = k_pool2, v_pool2
            ks, vs = k_sc2, v_sc2
        mesh = current_spmd_mesh()
        multi = mesh is not None and mesh.size > 1
        if t == 1:
            if multi:
                out = pattn.paged_decode_spmd(
                    mesh, q, kp, vp, table, kv_valid_len,
                    sliding_window=cfg.sliding_window,
                    softcap=cfg.attn_logit_softcap,
                    pool_replicas=pool_replicas,
                    k_scale=ks, v_scale=vs, kv_bits=kv_bits)
            else:
                out = pattn.paged_decode_attention(
                    q, kp, vp, table, kv_valid_len,
                    sliding_window=cfg.sliding_window,
                    softcap=cfg.attn_logit_softcap,
                    k_scale=ks, v_scale=vs, kv_bits=kv_bits)
        else:
            if multi:
                out = pattn.paged_prefill_spmd(
                    mesh, q, kp, vp, table,
                    positions[:, 0], kv_valid_len,
                    sliding_window=cfg.sliding_window,
                    softcap=cfg.attn_logit_softcap,
                    pool_replicas=pool_replicas,
                    k_scale=ks, v_scale=vs, kv_bits=kv_bits)
            else:
                out = pattn.paged_prefill_attention(
                    q, kp, vp, table, positions[:, 0],
                    kv_valid_len,
                    sliding_window=cfg.sliding_window,
                    softcap=cfg.attn_logit_softcap,
                    k_scale=ks, v_scale=vs, kv_bits=kv_bits)
        if out is None:
            # engine.paged_direct gates on spmd_partitionable and
            # serving buckets always satisfy the block check, so
            # this cannot happen in serving — fail loudly for direct
            # misuse rather than silently going dense.
            raise ValueError(
                "paged pool-direct serving under a multi-device "
                "mesh needs a head layout that partitions over the "
                f"model axis AND a block-legal chunk (T={t}, "
                f"ps={page_size})")
        out = _einsum("bthd,hde->bte", out, layer["o_proj"],
                      tp="row", lora="o_proj").astype(h.dtype)
        return out, (k_pool2, v_pool2, k_sc2, v_sc2)

    return transformer_block(x, layer, cfg, positions, None, None, None,
                             attn_fn=attn_fn)


def forward_paged(
    params: Params, cfg: ModelConfig,
    tokens: jax.Array,            # [B, T] token ids (T==1: decode step)
    positions: jax.Array,         # [B, T] absolute positions
    pools: list,                  # per-layer (k_pool, v_pool) [P,ps,K,Dp]
    table: jax.Array,             # [B, pages_per_seq] int32
    kv_valid_len: jax.Array,      # [B] valid entries AFTER this call
    pool_replicas: int = 1,       # data-axis shards of the page axis
    last_pos: Optional[jax.Array] = None,   # [B] row index into T
    scales: Optional[list] = None,  # per-layer (k_s, v_s) [P,ps,K,G]
    quant_spec=None,                # kv_quant.KVQuantSpec when scales
    kernel_quant: bool = True,      # False: shapes the kernel declined
) -> tuple[jax.Array, list]:
    """One serving step off the page pools — decode (T==1) or a prefill
    chunk (T==bucket); returns (logits [B,T,V], new_combined) — [B,1,V]
    when `last_pos` is given (hidden gathered before the lm head, same
    OOM guard as models/common.forward). Mirrors
    models/common.forward, with attention + cache update replaced by the
    pool-direct path: each layer scatters its K/V into the rows' pages
    ([B,T] position-indexed — pad-tail cells land on real decode-reserve
    pages or the scratch page, both overwritten/ignored before any
    read, same contract as the gather view) and attends through the
    page-table-aware kernel.

    Quantized pools (ISSUE 11): `scales` carries the per-layer per-cell
    scale pools — the scatter seam QUANTIZES each written token's K/V
    locally (its own absmax scale, neighbours untouched), and the
    kernels dequantize in-kernel via the scale operands. The returned
    list is then pools + scales in the engine's combined-pytree order.
    `kernel_quant=False` (a shape kv_quant_decline_reason declined on
    chip) dequantizes the WHOLE pool per layer before a bf16 kernel
    call — correct but memory-heavy; the engine records the reason and
    serves the gather view instead on the hot path, so this branch only
    backs direct callers."""
    page_size = pools[0][0].shape[1]
    b, t = tokens.shape
    pages = table[jnp.arange(b)[:, None],
                  positions // page_size]       # [B, T] page ids
    offs = positions % page_size

    x = embed_tokens(params["embedding"], tokens)
    if cfg.scale_embeddings:
        x = x * jnp.sqrt(jnp.float32(cfg.embed_dim)).astype(x.dtype)

    quant = scales is not None
    new_pools = []
    new_scales = []
    for li, (layer, (k_pool, v_pool)) in enumerate(
            zip(params["layers"], pools)):
        k_sc, v_sc = scales[li] if quant else (None, None)
        x, new_cache = _paged_block(
            x, layer, (k_pool, v_pool, k_sc, v_sc), positions, pages,
            offs, table, kv_valid_len, cfg=cfg,
            pool_replicas=pool_replicas, quant_spec=quant_spec,
            kernel_quant=kernel_quant)
        new_pools.append(new_cache[:2])
        if quant:
            new_scales.append(new_cache[2:])

    x = rms_norm(x, params["final_norm"], cfg.norm_eps,
                 cfg.rmsnorm_unit_offset)
    if last_pos is not None:
        x = gather_rows(x, last_pos)
    head = params["embedding"] if cfg.tie_embeddings else params["lm_head"]
    logits = _einsum("bte,ve->btv", x, head, tp="col")
    logits = _softcap(logits, cfg.final_logit_softcap)
    return logits, new_pools + new_scales


# --- ragged mixed prefill/decode forward (ISSUE 8) ---


def _ragged_xla_attention(q, k_pool, v_pool, tables, token_seq,
                          positions, kv_valid, cfg: ModelConfig,
                          k_sc=None, v_sc=None, quant_spec=None):
    """XLA fallback for the ragged kernel: per-token dense attention
    against each token's sequence slice of the gather view. Memory-
    heavy ([T, L, K, D] — the gather view's budget times the buffer's
    sequence fan-in) and FLOP-dense where the kernel would skip beyond
    the frontier: this is the recorded degrade path for pools the
    kernel declines (head_dim, page_size, VMEM), never the serving
    default. q [T, H, D] → [T, H, D]. Quantized pools dequantize at
    the gather (kv_quant.dequantize_cells — identical math to the
    in-kernel dequant, so kernel and fallback agree)."""
    t, h, d = q.shape
    page_size = k_pool.shape[1]
    # (the kv heads of the model, whatever rows an unquantized pool's
    # cell has: ModelConfig.lane_pack)
    kh = (1 if v_pool is None else k_pool.shape[2] if k_sc is not None
          else k_pool.shape[2] * k_pool.shape[3] // d)
    s, pp = tables.shape
    length = pp * page_size
    if v_pool is None:
        # A latent pool [P, ps, W] (models/mla.py): one head, whose
        # values are the first kv_lora_rank columns of its keys.
        kg = k_pool[tables].reshape(s, length, 1, d)
        vg = kg[..., :cfg.kv_lora_rank]
    elif k_sc is not None:
        # Gather FIRST, then dequantize the gathered slices — the
        # dequant cost scales with the view, not the whole pool.
        kg = kvq.dequantize_cells(k_pool[tables], k_sc[tables],
                                  quant_spec, q.dtype) \
            .reshape(s, length, kh, d)
        vg = kvq.dequantize_cells(v_pool[tables], v_sc[tables],
                                  quant_spec, q.dtype) \
            .reshape(s, length, kh, d)
    else:
        kg = k_pool[tables].reshape(s, length, kh, d)
        vg = v_pool[tables].reshape(s, length, kh, d)
    kt = kg[token_seq]                                # [T, L, K, D]
    vt = vg[token_seq]
    if h // kh > 1:
        kt = jnp.repeat(kt, h // kh, axis=2)          # [T, L, H, D]
        vt = jnp.repeat(vt, h // kh, axis=2)
    logits = jnp.einsum("thd,tlhd->thl", q, kt,
                        preferred_element_type=jnp.float32)
    logits = _softcap(logits, cfg.attn_logit_softcap)
    l_pos = jnp.arange(length)[None, :]
    mask = (l_pos <= positions[:, None]) \
        & (l_pos < kv_valid[token_seq][:, None])
    if cfg.sliding_window is not None:
        mask &= l_pos > positions[:, None] - cfg.sliding_window
    logits = jnp.where(mask[:, None, :], logits, MASK_VALUE)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("thl,tlhd->thd", probs, vt).astype(q.dtype)


@layer_body(static=("cfg", "attn_path", "quant_spec"))
def _ragged_block(x, layer, pools, positions, walk, token_pages,
                  token_offs, token_seq, copy_src, copy_dst, *,
                  cfg: ModelConfig, attn_path: str, quant_spec):
    """forward_ragged's layer as a body (models/common.layer_body): one
    block over the flat buffer [1, T, E], `pools` that layer's (k_pool,
    v_pool, k_scale, v_scale), `walk` the ragged kernel's (tables,
    seq_of_block, block_qstart, query_offsets, kv_valid). -> (x, the
    four as the block leaves them)."""
    k_pool, v_pool, k_sc, v_sc = pools
    tables, seq_of_block, block_qstart, query_offsets, kv_valid = walk
    quant = k_sc is not None
    kv_bits = quant_spec.bits if quant else 8
    pos2 = positions[None]

    def attn_fn(h, layer, k_pool=k_pool, v_pool=v_pool, k_sc=k_sc,
                v_sc=v_sc):
        q, k, v = project_qkv(h, layer, cfg, pos2)          # [1,T,H,D]
        if copy_src is not None:
            # Tree-path pre-COW (ISSUE 13): private frontier pages
            # receive the committed cells before this layer's
            # scatter can write draft cells into them.
            k_pool = k_pool.at[copy_dst].set(k_pool[copy_src])
            v_pool = v_pool.at[copy_dst].set(v_pool[copy_src])
            if quant:
                k_sc = k_sc.at[copy_dst].set(k_sc[copy_src])
                v_sc = v_sc.at[copy_dst].set(v_sc[copy_src])
        if quant:
            # Quantize-on-write (ISSUE 11): each flat-buffer token
            # writes its own payload + scale; pads land on the
            # scratch page, never read.
            k_q, k_s = kvq.quantize_cells(k[0], quant_spec)
            v_q, v_s = kvq.quantize_cells(v[0], quant_spec)
            k_pool2 = k_pool.at[token_pages, token_offs].set(k_q)
            v_pool2 = v_pool.at[token_pages, token_offs].set(v_q)
            k_sc2 = k_sc.at[token_pages, token_offs].set(k_s)
            v_sc2 = v_sc.at[token_pages, token_offs].set(v_s)
        else:
            k_pool2 = _write_cells(k_pool, token_pages, token_offs, k[0])
            v_pool2 = _write_cells(v_pool, token_pages, token_offs, v[0])
            k_sc2 = v_sc2 = None
        if attn_path == "kernel":
            mesh = current_spmd_mesh()
            if mesh is not None and mesh.size > 1:
                out = pattn.ragged_paged_spmd(
                    mesh, q[0], k_pool2, v_pool2, tables,
                    seq_of_block, block_qstart, query_offsets,
                    kv_valid, sliding_window=cfg.sliding_window,
                    softcap=cfg.attn_logit_softcap,
                    k_scale=k_sc2, v_scale=v_sc2, kv_bits=kv_bits)
                if out is None:
                    # The engine gates ragged_path on
                    # partitionability at build time — reaching
                    # here is direct misuse, fail loudly.
                    raise ValueError(
                        "ragged kernel cannot partition this head "
                        "layout — engine should have resolved "
                        "attn_path='xla'")
            else:
                out = pattn.ragged_paged_attention(
                    q[0], k_pool2, v_pool2, tables, seq_of_block,
                    block_qstart, query_offsets, kv_valid,
                    sliding_window=cfg.sliding_window,
                    softcap=cfg.attn_logit_softcap,
                    k_scale=k_sc2, v_scale=v_sc2, kv_bits=kv_bits)
        else:
            out = _ragged_xla_attention(
                q[0], k_pool2, v_pool2, tables, token_seq,
                positions, kv_valid, cfg, k_sc=k_sc2, v_sc=v_sc2,
                quant_spec=quant_spec)
        out = _einsum("bthd,hde->bte", out[None], layer["o_proj"],
                      tp="row", lora="o_proj").astype(h.dtype)
        return out, (k_pool2, v_pool2, k_sc2, v_sc2)

    return transformer_block(x, layer, cfg, pos2, None, None, None,
                             attn_fn=attn_fn)


def forward_ragged(
    params: Params, cfg: ModelConfig,
    tokens: jax.Array,            # [T] flat token buffer
    positions: jax.Array,         # [T] absolute positions
    pools: list,                  # per-layer (k_pool, v_pool) [P,ps,K,D]
    tables: jax.Array,            # [S, pages_per_seq] int32
    seq_of_block: jax.Array,      # [T/8] sequence id per q block
    block_qstart: jax.Array,      # [T/8] block start row within its seq
    query_offsets: jax.Array,     # [S] absolute position of seq's row 0
    kv_valid: jax.Array,          # [S] valid entries AFTER this call
    token_pages: jax.Array,       # [T] pool page per token (pads→scratch)
    token_offs: jax.Array,        # [T] in-page offset per token
    token_seq: jax.Array,         # [T] owning sequence per token
    last_rows: jax.Array,         # [S] flat row of each seq's last token
    attn_path: str = "kernel",    # "kernel" | "xla" (static)
    sample_rows: Optional[jax.Array] = None,  # [S, R] rows to score
    scales: Optional[list] = None,  # per-layer (k_s, v_s) (ISSUE 11)
    quant_spec=None,
    copy_src: Optional[jax.Array] = None,  # [C] page pre-COW (ISSUE 13)
    copy_dst: Optional[jax.Array] = None,
) -> tuple[jax.Array, list]:
    """One MIXED prefill/decode step over the flat token buffer
    (serving_loop.build_ragged_batch layout): every sequence's chunk or
    decode token runs in the SAME dispatch — the admission prologue's
    replacement. Each layer scatters the buffer's K/V into the owning
    sequences' pages (pads land on the scratch page, never read), then
    attends through the ragged page-table kernel — or, with
    attn_path="xla", the dense per-token fallback the engine records a
    fallback_reason for. Returns (per-sequence last-token logits
    [S, V], new_pools); pad sequence rows carry garbage the caller
    drops. Block wiring comes from transformer_block's attn_fn hook,
    exactly like forward_paged.

    `sample_rows` [S, R] (ISSUE 9, the speculative verify): score R
    flat-buffer rows per sequence instead of one — each speculating
    row's whole ``[last, drafts...]`` run gets logits in this single
    forward, and the causal mask makes each position's logits EXACTLY
    what 1-token decode would compute given the accepted prefix (the
    output-invariance core). Returns ([S, R, V], new_pools); the lm
    head still runs on S*R gathered rows, never the full buffer.

    `copy_src`/`copy_dst` [C] (ISSUE 13, tree verify): whole pages
    device-copied pool->pool per layer BEFORE the K/V scatter — the
    pre-COW that gives each tree path's private frontier page the
    committed cells its causal reads need (pads are scratch->scratch
    self-copies; scales ride with their pages, as in the page cache's
    own copier). With this, a token TREE is just more sequences of the
    same flat buffer: per-path tables keep sibling writes apart, the
    ordinary causal mask is exact along every root-to-leaf path, and
    no kernel changes at all."""
    x = embed_tokens(params["embedding"], tokens[None])     # [1, T, E]
    if cfg.scale_embeddings:
        x = x * jnp.sqrt(jnp.float32(cfg.embed_dim)).astype(x.dtype)
    pos2 = positions[None]

    quant = scales is not None
    new_pools = []
    new_scales = []
    walk = (tables, seq_of_block, block_qstart, query_offsets, kv_valid)
    for li, (layer, (k_pool, v_pool)) in enumerate(
            zip(params["layers"], pools)):
        k_sc, v_sc = scales[li] if quant else (None, None)
        x, new_cache = _ragged_block(
            x, layer, (k_pool, v_pool, k_sc, v_sc), positions, walk,
            token_pages, token_offs, token_seq, copy_src, copy_dst,
            cfg=cfg, attn_path=attn_path, quant_spec=quant_spec)
        new_pools.append(new_cache[:2])
        if quant:
            new_scales.append(new_cache[2:])

    x = rms_norm(x, params["final_norm"], cfg.norm_eps,
                 cfg.rmsnorm_unit_offset)
    if sample_rows is not None:
        s, r = sample_rows.shape
        sel = x[0, sample_rows.reshape(-1)][None]           # [1, S*R, E]
    else:
        sel = x[0, last_rows][None]                         # [1, S, E]
    head = params["embedding"] if cfg.tie_embeddings else params["lm_head"]
    logits = _einsum("bte,ve->btv", sel, head, tp="col")
    logits = _softcap(logits, cfg.final_logit_softcap)
    if sample_rows is not None:
        return logits[0].reshape(s, r, -1), new_pools + new_scales
    return logits[0], new_pools + new_scales


# --- hybrid decoders: one mixer a layer, state beside the pools -------------
#
# The loops below walk `ModelConfig.layer_runs`, not `layer_kinds`: a run
# of Mamba-1 blocks is one entry of `params["layers"]` (leaves stacked
# along a layer axis) and ONE `lax.scan` (`_scan_run`), so a program
# holds one body a run whatever the depth; every other layer is a run of
# one and goes through ONE jitted body a step-program family
# (`_paged_hybrid_layer`, `_ragged_hybrid_layer`: models/common.
# layer_body), its kind and the config it reads static — so the layers
# of one signature are traced once and lowered once a program.
#
# What a program carries from layer to layer is a TUPLE, `carry`: the
# residual stream x and whatever rides beside it. Width one is every
# model but one kind's: a model with gated memory units (`gmu`) carries
# `m`, the scan output of its `ModelConfig.memory_layer`, from that
# layer up — recomputed every step, kept nowhere. A body reads
# `carry[0]` and hands the rest on untouched; a `gmu` layer reads
# `carry[1]`.
#
# THE SEAM (`ModelConfig.last_token_from`): the layers from that index on
# keep nothing — no pages, no state (`hybrid.STATELESS`) — so of a
# join's tokens only each row's last one needs them. In a prologue chunk
# and a ragged step the carry is gathered to those rows there (`_seam_run`),
# and the layers above run `[rows, 1, E]` through `_paged_hybrid_layer`
# at one token a row — the decode program's upper half; a cross layer
# then reads its pages through the decode walk. Decode itself, one token
# a row from the start, has nothing to gather.
#
# The rule a new block kind follows (models/common.py has it in full):
# a branch of the body reads only its arguments — the carry, that
# layer's leaves, `own` (that layer's pools — a cross layer: the pools
# it reads — or its parts of the state in
# `_STATE_PARTS`' order), `held` (where its capture is written) and the
# dispatch's arrays `d` — and what is static about it is `kind`, `cfg`
# (for an attention layer `cfg.attention_layer(i)`, a frozen view equal
# where two layers' geometry is) and `page_size`. It closes over nothing
# a layer owns; a layer that differs in a static field is a second
# trace, and nothing here tests a model's name.


def _hybrid_head(params, cfg, x):
    head = params["embedding"] if cfg.tie_embeddings else params["lm_head"]
    logits = _einsum("bte,ve->btv", x, head, tp="col")
    return _softcap(logits, cfg.final_logit_softcap)


def _state_lists(state: dict) -> dict:
    """Every part the layers may advance, as a list they assign into
    (a part the model's layers do not keep: empty)."""
    return {p: list(state.get(p, ()))
            for p in ("ssm", "conv", "ret", "retn", "ssm1", "conv1",
                      "sconv")}


def _scan_run(x, run, kinds, cfg: ModelConfig, ssm, conv, held, mixer,
              emit: bool = False):
    """One run of Mamba-1 blocks (`ModelConfig.layer_runs`) as ONE
    `lax.scan` over its stacked parameters: whatever the run's length
    the program holds one body — a mixer and, where the block has one,
    the MLP behind it. `ssm` / `conv` (every slot's state of the run,
    [rows, L, ...]) and `held` (the store's two arrays of the run, or
    None) ride in the carry and are updated in place at layer `l`;
    `mixer(h, layer, ssm, conv, l, held) -> (out, ssm, conv, held)`.
    With `emit` the mixer returns its scan output `m` last, and the
    run's LAST layer's is returned after `held`."""
    from .models import hybrid

    def block(carry, xs):
        x, ssm, conv, held = carry
        l, layers = xs
        layer = layers[hybrid.MAMBA1]
        out, ssm, conv, held, *m = mixer(
            hybrid.layer_norm_in(x, layer, cfg), layer, ssm, conv, l, held)
        x = x + out
        for kind in kinds[1:]:                  # (the MLP behind it)
            x = x + mlp(hybrid.layer_norm_in(x, layers[kind], cfg),
                        layers[kind], cfg)
        return (x, ssm, conv, held), (m[0] if emit else None)

    (x, ssm, conv, held), ms = jax.lax.scan(
        block, (x, ssm, conv, held), (jnp.arange(ssm.shape[1]), run))
    if emit:
        return x, ssm, conv, held, ms[-1]
    return x, ssm, conv, held


@functools.lru_cache(maxsize=None)
def _seam_run(cfg: ModelConfig) -> Optional[int]:
    """The index into `cfg.layer_runs` of the first run above the seam
    (`ModelConfig.last_token_from`; None: the model has none). What lies
    above keeps nothing, or the model cannot be served."""
    from .models import hybrid
    if cfg.last_token_from is None:
        return None
    kept = [k for k in cfg.layer_kinds[cfg.last_token_from:]
            if k not in hybrid.STATELESS]
    at, starts = 0, []
    for kinds, n in cfg.layer_runs:
        starts.append(at)
        at += len(kinds) * n
    if kept or cfg.last_token_from not in starts:
        raise ValueError(
            f"{cfg.name}: last_token_from {cfg.last_token_from} must "
            f"begin a run of layers that keep nothing (found {kept})")
    return starts.index(cfg.last_token_from)


@functools.lru_cache(maxsize=None)
def _memory_run(cfg: ModelConfig) -> Optional[int]:
    """The index into `cfg.layer_runs` of the scanned run whose last
    layer is `cfg.memory_layer` (None: no layer reads a memory)."""
    if cfg.memory_layer is None:
        return None
    at = 0
    for r, (kinds, n) in enumerate(cfg.layer_runs):
        at += len(kinds) * n
        if at - len(kinds) == cfg.memory_layer:
            return r
    raise ValueError(f"{cfg.name}: memory_layer {cfg.memory_layer} does "
                     "not end a run of Mamba-1 blocks")


def _attention_io(h, layer, cfg: ModelConfig, positions, dtype):
    """What an attention layer of a model with `layer_kinds` asks of its
    pages and writes to them: (q [B,T,H,D], entries — one array a pool
    of the layer, each [B,T,...] — and the kernels' extra keywords).
    Grouped-query: keys and values, two pools. Latent (models/mla.py,
    absorbed form): ONE entry a position and no value pool."""
    if cfg.latent:
        q, entry = mla.latents(h, layer, cfg, positions)
        return (q.astype(dtype), (entry.astype(dtype),),
                {"v_dim": cfg.kv_lora_rank})
    q, k, v = (a.astype(dtype) for a in
               project_qkv(h, layer, cfg, positions))
    if cfg.diff_attn:
        # (a kv pair is one lane row of a page: models/diffattn.py)
        q = diffattn.pack_queries(q)
    return q, (k, v), {}


def _attention_out(out, h, layer, cfg: ModelConfig, dtype):
    """The kernels' result [B,T,H,*] -> the layer's output [B,T,E];
    `h` the layer's normed input, which a gated layer's gate reads."""
    if cfg.diff_attn:
        return diffattn.output(out, layer, cfg, dtype)
    if cfg.latent:
        out = mla.values_of(out, layer, cfg)
    return _einsum("bthd,hde->bte", gate_heads(out, h, layer, cfg),
                   layer["o_proj"], tp="row").astype(dtype)


# The parts of the state a layer of each kind advances, in the order its
# body takes and returns them (a scanned Mamba-1 run: `_scan_run`).
_STATE_PARTS = {"retention": ("ret", "retn"), "mamba2": ("ssm", "conv"),
                "shortconv": ("sconv",)}


@layer_body(static=("kind", "cfg", "page_size"))
def _paged_hybrid_layer(carry, layer, own, held, d, *, kind: str,
                        cfg: ModelConfig, page_size: Optional[int]):
    """forward_paged_hybrid's layer as a body (models/common.layer_body):
    norm, ONE mixer of `kind`, residual. `carry`: the residual stream
    and what rides beside it (a `gmu` layer's memory), `own`: that
    layer's pools (attention; a cross layer: the pools it reads) or its
    parts of the state (`_STATE_PARTS`), `held`: the
    store's arrays a retention layer's capture goes into (else None),
    `d`: the dispatch's arrays (None where the program has none:
    `active` says decode, `cap_len` that a capture is wanted). -> (the
    carry, `own` as the layer leaves it — () where it wrote nothing —
    what it captured — a part each, () for
    none — and an expert layer's `hybrid.MOE_COUNTS`, else None)."""
    from .models import hybrid, retention, shortconv
    positions, lengths, cap_len, active = (
        d["positions"], d["lengths"], d["cap_len"], d["active"])
    decode = active is not None
    t = positions.shape[1]
    x, *beside = carry
    h = hybrid.layer_norm_in(x, layer, cfg)
    captured, counts = (), None
    if kind == hybrid.RETENTION:
        if decode:
            out, *own = retention.retention_step(
                h, layer, cfg, positions, *own, d["rows"], active)
        else:
            out, *own, snaps = retention.retention_prefill(
                h, layer, cfg, positions, *own, d["rows"], lengths,
                page_size, held, cap_len, d["snap_idx"])
            captured = snaps or ()
    elif kind == hybrid.MAMBA2:
        if decode:
            out, *own = hybrid.mamba2_step(h, layer, cfg, *own, active)
        else:
            # (with `cap_len`, also the state after that many tokens)
            out, *own = hybrid.mamba2_prefill(h, layer, cfg, *own,
                                              lengths, cap_len)
            own, captured = own[:2], own[2:]
    elif kind == hybrid.SHORTCONV:
        if decode:
            out, *own = shortconv.shortconv_step(h, layer, cfg, *own,
                                                 active)
        else:
            # (with `cap_len`, also the tail after that many tokens)
            out, *own = shortconv.shortconv_prefill(
                h, layer, cfg, *own, lengths, cap_len)
            own, captured = own[:1], own[1:]
    elif kind == hybrid.EXPERTS:
        out, c = hybrid.experts_mlp(h, layer, cfg, d["counted"])
        counts = hybrid.step_counts(c, jnp.any(d["counted"]))
    elif kind == hybrid.MLP:
        out = mlp(h, layer, cfg)
    elif kind == hybrid.GMU:
        out = hybrid.gmu(h, beside[0], layer, h.dtype)
    else:
        if kind == hybrid.CROSS:
            # (reads what another layer wrote, under the MODEL's window,
            # which is no layer's own: causal and unbounded)
            q, kw = diffattn.queries(h, layer, cfg).astype(own[0].dtype), {}
        else:
            q, entries, kw = _attention_io(h, layer, cfg, positions,
                                           own[0].dtype)
            own = tuple(_write_cells(p, d["pages"], d["offs"], e)
                        for p, e in zip(own, entries))
        k_pool, v_pool = (own + (None,))[:2]
        if t == 1:
            out = pattn.paged_decode_attention(
                q, k_pool, v_pool, d["table"], d["kv_valid_len"],
                sliding_window=cfg.sliding_window,
                softcap=cfg.attn_logit_softcap, **kw)
        else:
            out = pattn.paged_prefill_attention(
                q, k_pool, v_pool, d["table"], positions[:, 0],
                d["kv_valid_len"], sliding_window=cfg.sliding_window,
                softcap=cfg.attn_logit_softcap, **kw)
        if out is None:
            raise ValueError(
                "paged pool-direct kernels declined this shape "
                f"(T={t}, ps={page_size}); the engine gates hybrid "
                "models on paged_direct at build time")
        out = _attention_out(out, h, layer, cfg, h.dtype)
        if kind == hybrid.CROSS:
            own = ()
    return (x + out, *beside), tuple(own), tuple(captured), counts


@layer_body(static=("kind", "cfg", "page_size", "attn_path"))
def _ragged_hybrid_layer(carry, layer, own, held, d, *, kind: str,
                         cfg: ModelConfig, page_size: Optional[int],
                         attn_path: str):
    """forward_ragged_hybrid's layer as a body, `_paged_hybrid_layer`'s
    twin over the flat buffer [1, T, E]: `d` the ragged dispatch's
    arrays, `d["rg"]` `hybrid.ragged_meta`'s without its static block
    size. A state layer always captures (`cap_n` 0: nothing taken)."""
    from .models import hybrid, retention, shortconv
    from .serving_loop import RAGGED_BLOCK_Q
    rg = dict(d["rg"], block=RAGGED_BLOCK_Q)
    positions = d["positions"]
    pos2 = positions[None]
    x, *beside = carry
    h = hybrid.layer_norm_in(x, layer, cfg)
    captured, counts = (), None
    if kind == hybrid.RETENTION:
        out, *own, captured = retention.retention_ragged(
            h, layer, cfg, pos2, *own, rg, page_size, held,
            d["snap_idx"])
    elif kind == hybrid.MAMBA2:
        out, *own = hybrid.mamba2_ragged(h, layer, cfg, *own, rg)
        own, captured = own[:2], own[2:]
    elif kind == hybrid.SHORTCONV:
        out, *own = shortconv.shortconv_ragged(h, layer, cfg, *own, rg)
        own, captured = own[:1], own[1:]
    elif kind == hybrid.EXPERTS:
        out, c = hybrid.experts_mlp(h, layer, cfg, d["counted"])
        counts = hybrid.step_counts(c, 1)
    elif kind == hybrid.MLP:
        out = mlp(h, layer, cfg)
    elif kind == hybrid.GMU:
        out = hybrid.gmu(h, beside[0], layer, h.dtype)
    else:
        if kind == hybrid.CROSS:
            q, kw = diffattn.queries(h, layer, cfg).astype(own[0].dtype), {}
        else:
            q, entries, kw = _attention_io(h, layer, cfg, pos2,
                                           own[0].dtype)
            own = tuple(
                _write_cells(p, d["token_pages"], d["token_offs"], e[0])
                for p, e in zip(own, entries))              # e [1,T,...]
        k_pool, v_pool = (own + (None,))[:2]
        if attn_path == "kernel":
            out = pattn.ragged_paged_attention(
                q[0], k_pool, v_pool, d["tables"], rg["seq_of_block"],
                rg["block_qstart"], d["query_offsets"], d["kv_valid"],
                sliding_window=cfg.sliding_window,
                softcap=cfg.attn_logit_softcap, **kw)
        else:
            out = _ragged_xla_attention(
                q[0], k_pool, v_pool, d["tables"], rg["token_seq"],
                positions, d["kv_valid"], cfg)
        out = _attention_out(out[None], h, layer, cfg, h.dtype)
        if kind == hybrid.CROSS:
            own = ()
    return (x + out, *beside), tuple(own), tuple(captured), counts


def forward_paged_hybrid(
    params: Params, cfg: ModelConfig,
    tokens: jax.Array,            # [B, T] (T==1 with `active`: decode)
    positions: jax.Array,         # [B, T]
    pools: list,                  # (k_pool, v_pool) per ATTENTION layer
    table: jax.Array,             # [B, pages_per_seq]
    kv_valid_len: jax.Array,      # [B] valid entries AFTER this call
    state: dict,                  # {"ssm": [[B,H,P,N]..], "conv": [..]}
    *,
    lengths: Optional[jax.Array] = None,   # [B] prefill: valid tokens
    cap_len: Optional[jax.Array] = None,   # [B] prefill: snapshot after
    active: Optional[jax.Array] = None,    # [B] decode: rows that advance
    last_pos: Optional[jax.Array] = None,
    page_size: Optional[int] = None,       # a model with no pool to ask
    rows: Optional[jax.Array] = None,      # [B] state rows (SLOT_PARTS)
    snaps: Optional[dict] = None,          # the store's SLOT_PARTS
    snap_idx: Optional[jax.Array] = None,  # [B] where a capture goes
):
    """forward_paged for a model with `layer_kinds` (models/hybrid.py):
    every layer is one mixer behind one norm and a residual. Attention
    layers scatter into their own pools and attend through the same
    page-table kernels; Mamba-2 layers advance the rows' recurrent
    `state` (batch-row order: the program gathers and scatters the
    slot rows), as gated short-convolution layers (models/shortconv.py)
    advance their tails; retention layers (models/retention.py) and the scanned
    runs of Mamba-1 layers (models/mamba1.py) advance theirs IN PLACE on
    every slot's array (`state["ret"]`, `["retn"]`; `["ssm1"]`,
    `["conv1"]`, a leaf a run: whole, addressed by `rows`) and write a
    capture straight into `snaps` at `snap_idx`; expert layers count
    what they touched.

    -> (logits, new_pools, new_state, captured, counts): `captured` is
    the state after `cap_len` tokens (prefill with `cap_len`; for the
    retention parts the store's arrays with it written), else
    None; counts int32[5] (`hybrid.MOE_COUNTS`) = experts hit and
    assignments to held experts over the counted tokens, rows the
    grouped products multiplied and rows a loop over every held expert
    would have, expert-layer steps."""
    from .models import hybrid, mamba1
    if pools:
        page_size = pools[0][0].shape[1]
    b, t = tokens.shape
    decode = active is not None
    x = embed_tokens(params["embedding"], tokens)
    d = {"positions": positions, "table": table,
         "kv_valid_len": kv_valid_len, "lengths": lengths,
         "cap_len": cap_len, "active": active, "rows": rows,
         "snap_idx": snap_idx,
         "pages": table[jnp.arange(b)[:, None], positions // page_size],
         "offs": positions % page_size,
         "counted": (active[:, None] if decode else
                     jnp.arange(t)[None, :] < lengths[:, None])}
    st = _state_lists(state)
    cap = {p: [] for p in state} if cap_len is not None else None
    counts = jnp.zeros((len(hybrid.MOE_COUNTS),), jnp.int32)
    new_pools = []
    seen = collections.Counter()             # layers met, by kind
    carry = (x,)
    # The seam: a join's rows, gathered where the layers keep nothing.
    seam = _seam_run(cfg) if t > 1 and last_pos is not None else None
    memory = _memory_run(cfg)
    for r, ((kinds, _n), layer) in enumerate(zip(cfg.layer_runs,
                                                 params["layers"])):
        if r == seam:
            carry = tuple(gather_rows(a, last_pos) for a in carry)
            d = _above_seam(
                jnp.take_along_axis(positions, last_pos[:, None], axis=1),
                table, kv_valid_len)
        kind = kinds[0]
        i = seen[kind]
        seen[kind] += 1
        if kind == hybrid.MAMBA1:
            emit = r == memory
            if decode:
                def mixer(h, layer, s, c, l, held):
                    out = mamba1.mamba1_step(h, layer, cfg, s, c, l, rows,
                                             active, emit)
                    return out[:3] + (held,) + out[3:]
            else:
                def mixer(h, layer, s, c, l, held):
                    return mamba1.mamba1_prefill(
                        h, layer, cfg, s, c, l, rows, lengths, held,
                        cap_len, snap_idx, emit)
            held = None if cap is None else (snaps["ssm1"][i],
                                             snaps["conv1"][i])
            x, st["ssm1"][i], st["conv1"][i], held, *m = _scan_run(
                carry[0], layer, kinds, cfg, st["ssm1"][i], st["conv1"][i],
                held, mixer, emit)
            carry = (x, *carry[1:], *m)
            if cap is not None:
                cap["ssm1"].append(held[0])
                cap["conv1"].append(held[1])
            continue
        parts = _STATE_PARTS.get(kind, ())
        attends = kind == hybrid.ATTENTION
        held = None
        if kind == hybrid.RETENTION and cap is not None:
            held = (snaps["ret"][i], snaps["retn"][i])
        # (an attention layer: its own heads, window and rotary table,
        # where the attention layers differ — ModelConfig.attn_layers;
        # a cross layer: the pools of the attention layer below it)
        carry, own, captured, c = _paged_hybrid_layer(
            carry, layer,
            pools[i] if attends else new_pools[-1]
            if kind == hybrid.CROSS else tuple(st[p][i] for p in parts),
            held, d, kind=kind,
            cfg=cfg.attention_layer(i) if attends else cfg,
            page_size=page_size)
        if attends:
            new_pools.append(own)
        for p, a in zip(parts, own):
            st[p][i] = a
        if cap is not None:
            for p, a in zip(parts, captured):
                cap[p].append(a)
        if c is not None:
            counts = counts + c
    x = hybrid.final_norm(carry[0], params, cfg)
    if last_pos is not None and seam is None:
        x = gather_rows(x, last_pos)
    new = {p: st[p] for p in state}
    return _hybrid_head(params, cfg, x), new_pools, new, cap, counts


def _above_seam(positions, table, kv_valid_len) -> dict:
    """The dispatch's arrays as the layers above the seam see them
    (`_paged_hybrid_layer` at one token a row): positions [rows, 1] each
    row's last, its page table and its valid length. Those layers keep
    nothing, so nothing else is theirs to read."""
    return {"positions": positions, "table": table,
            "kv_valid_len": kv_valid_len, "lengths": None,
            "cap_len": None, "active": None}


def forward_ragged_hybrid(
    params: Params, cfg: ModelConfig, tokens, positions, pools, tables,
    seq_of_block, block_qstart, query_offsets, kv_valid, token_pages,
    token_offs, token_seq, last_rows,
    state: dict,                  # EVERY slot: {"ssm": [[R,...]..], ..}
    seq_slot: jax.Array,          # [S] state row of each sequence
    cap_n: jax.Array,             # [S] snapshot after this many tokens (0: none)
    attn_path: str = "kernel",
    page_size: Optional[int] = None,       # a model with no pool to ask
    snaps: Optional[dict] = None,          # the store's SLOT_PARTS
    snap_idx: Optional[jax.Array] = None,  # [S] where a capture goes
):
    """forward_ragged for a model with `layer_kinds`: the flat buffer's
    Mamba-2 layers run the block-chunked scan straight on the slot
    array (each block reads its sequence's state and writes it back),
    retention layers every run a page's chunk at a time, also straight
    on the slot array and with a capture written into `snaps` at
    `snap_idx`, a scanned run of Mamba-1 layers one selective-scan
    kernel a layer over the whole buffer, likewise in place; attention
    layers the ragged page-table kernel. ->
    (logits [S, V], new_pools, new_state, captured {"ssm": [[S,...]..],
    "conv": .., "ret" / "retn": the store's arrays}, counts)."""
    from .models import hybrid, mamba1
    from .serving_loop import RAGGED_BLOCK_Q
    if pools:
        page_size = pools[0][0].shape[1]
    s_max = tables.shape[0]
    x = embed_tokens(params["embedding"], tokens[None])  # [1, T, E]
    rg = hybrid.ragged_meta(positions, token_seq, query_offsets, kv_valid,
                            last_rows, seq_of_block, block_qstart,
                            seq_slot, cap_n, RAGGED_BLOCK_Q)
    # (the block's rows are static: a body takes the arrays, and puts
    # the number back)
    d = {"positions": positions, "tables": tables,
         "query_offsets": query_offsets, "kv_valid": kv_valid,
         "token_pages": token_pages, "token_offs": token_offs,
         "snap_idx": snap_idx,
         "rg": {k: v for k, v in rg.items() if k != "block"},
         "counted": (rg["token_valid"] & (token_seq != s_max - 1))[None]}
    st = _state_lists(state)
    cap = {p: [] for p in state}
    counts = jnp.zeros((len(hybrid.MOE_COUNTS),), jnp.int32)
    new_pools = []
    seen = collections.Counter()             # layers met, by kind
    carry = (x,)
    seam, memory = _seam_run(cfg), _memory_run(cfg)
    d_up = None                 # (set: the layers above the seam)
    for r, ((kinds, _n), layer) in enumerate(zip(cfg.layer_runs,
                                                 params["layers"])):
        if r == seam:
            # Each sequence's last token, a row: [1, T, .] -> [S, 1, .].
            carry = tuple(a[0, last_rows][:, None] for a in carry)
            d_up = _above_seam((kv_valid - 1)[:, None], tables, kv_valid)
        kind = kinds[0]
        i = seen[kind]
        seen[kind] += 1
        if kind == hybrid.MAMBA1:
            emit = r == memory

            def mixer(h, layer, s, c, l, held):
                return mamba1.mamba1_ragged(h, layer, cfg, s, c, l, rg,
                                            held, snap_idx, emit)
            x, st["ssm1"][i], st["conv1"][i], held, *m = _scan_run(
                carry[0], layer, kinds, cfg, st["ssm1"][i], st["conv1"][i],
                (snaps["ssm1"][i], snaps["conv1"][i]), mixer, emit)
            carry = (x, *carry[1:], *m)
            cap["ssm1"].append(held[0])
            cap["conv1"].append(held[1])
            continue
        parts = _STATE_PARTS.get(kind, ())
        attends = kind == hybrid.ATTENTION
        own = (pools[i] if attends else new_pools[-1]
               if kind == hybrid.CROSS else tuple(st[p][i] for p in parts))
        if d_up is not None:
            carry, own, captured, c = _paged_hybrid_layer(
                carry, layer, own, None, d_up, kind=kind, cfg=cfg,
                page_size=page_size)
        else:
            carry, own, captured, c = _ragged_hybrid_layer(
                carry, layer, own,
                ((snaps["ret"][i], snaps["retn"][i])
                 if kind == hybrid.RETENTION else None),
                d, kind=kind,
                cfg=cfg.attention_layer(i) if attends else cfg,
                page_size=page_size, attn_path=attn_path)
        if attends:
            new_pools.append(own)
        for p, a, held in zip(parts, own, captured):
            st[p][i] = a
            cap[p].append(held)
        if c is not None:
            counts = counts + c
    x = hybrid.final_norm(carry[0], params, cfg)
    # ([S, 1, E] from the seam, else the buffer's rows gathered here)
    sel = x[:, 0][None] if d_up is not None else x[0, last_rows][None]
    logits = _hybrid_head(params, cfg, sel)
    new = {p: st[p] for p in state}
    return logits[0], new_pools, new, cap, counts


# ---------------------------------------------------------------------------
# static-analysis program registration (ISSUE 15)
# ---------------------------------------------------------------------------

from ..analysis.jaxpr_audit import (ProgramSpec, Variant,  # noqa: E402
                                    analysis_register)


def trace_ragged_batch(engine, batch: dict):
    """Trace one ragged dispatch's program (`engine._ragged_step`) to a
    ClosedJaxpr without dispatching — the device-free twin of
    `InferenceEngine._ragged_dispatch.run`. Argument mapping mirrors
    that seam one-to-one (the same layout, the same static kwargs); if the
    twins drift, the audit's trace step fails loudly, which is the
    contract — an unauditable serving program must never be skipped
    silently. Shared by the ragged provider here and the spec-decode
    provider (verify/propose variants)."""
    score_width = int(batch.get("score_width", 0) or 0)
    propose_width = int(batch.get("propose_width", 0) or 0)
    from .engine import _audit_sds
    params = _audit_sds(engine.params)
    pools = _audit_sds(engine.kv.combined_pools())
    attn_path = ("kernel" if engine.ragged_path == "pallas_ragged"
                 else "xla")
    # One packed buffer and the key (engine/dispatch_pack.py): the
    # layout follows from the batch's shapes, as at the seam.
    layout = engine._ragged_layout(batch)
    buf = jax.ShapeDtypeStruct((layout.size,), jnp.int32)
    lora = (_audit_sds(engine.lora.stacked)
            if engine.lora is not None else None)

    def call(p, pl, b, k, lo):
        return engine._ragged_step(
            p, pl, b, k, layout=layout, greedy=batch["greedy"],
            attn_path=attn_path, score_width=score_width, lora=lo,
            propose_width=propose_width)

    return jax.make_jaxpr(call)(params, pools, buf,
                                jax.random.split(jax.random.PRNGKey(0)),
                                lora)


def analysis_warm_seqs(engine, n_seqs: int = 2):
    """Toy RaggedSeq compositions over scratch-page tables (shape-only
    — the audit traces, never dispatches, so no page is ever really
    read or allocated). Mirrors _warm_ragged's two-seq mixed batch."""
    import numpy as np
    from .serving_loop import RaggedSeq
    kv = engine.kv
    scratch = kv.scratch_page(0)
    table = np.full((kv.pages_per_seq,), scratch, np.int32)
    bos = engine.tokenizer.bos_id
    seqs = [RaggedSeq([bos] + [5] * 23, 0, table)]
    if n_seqs > 1:
        seqs.append(RaggedSeq([7], 8, table))
    return seqs[:n_seqs]


@analysis_register("ragged")
def _analysis_ragged_programs(engine) -> list:
    """The plain ragged mixed-dispatch program across the warmed shape
    grid. Two compositions (one-seq, two-seq) trace under EVERY shape
    label: composition is values, so both must produce the one jaxpr
    that shape warmed — a leak of composition into a static argument
    fails RT-JAXPR-VARIANTS."""
    if not getattr(engine, "ragged_enabled", False) \
            or getattr(engine, "hybrid", None) is not None:
        return []       # (a hybrid engine: engine._analysis_hybrid_programs)
    from .serving_loop import build_ragged_batch
    kv = engine.kv

    def variant(shape: int, n_seqs: int) -> Variant:
        def thunk():
            batch = build_ragged_batch(
                analysis_warm_seqs(engine, n_seqs), t_budget=shape,
                s_max=kv.num_slots + 1, pages_per_seq=kv.pages_per_seq,
                scratch_page=kv.scratch_page(0),
                pad_id=engine.tokenizer.pad_id,
                page_size=kv.page_size)
            return trace_ragged_batch(engine, batch)
        return Variant(label=f"t{shape}", thunk=thunk,
                       situation=f"{n_seqs} seq(s) in shape {shape}")

    return [ProgramSpec(
        name="ragged", phase="ragged",
        variants=[variant(shape, n)
                  for shape in engine.ragged_shapes for n in (1, 2)])]
