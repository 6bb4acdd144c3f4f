"""Long-context sequence parallelism — ring attention + Ulysses all-to-all.

The reference scales sequence length DOWN: its context-budgeting subsystem
truncates sources to a min-over-knights char budget and slices git diffs to
3000 chars (reference src/orchestrator.ts:281-292, :406; SURVEY.md §5.7).
This module inverts that into genuine long-context serving for the TPU
build (SURVEY.md §2.3 "SP/CP/ring-attention", §7 Phase 6): prefill with the
sequence axis sharded over a "seq" mesh axis so activation memory and
attention FLOPs split across chips.

Two schemes, chosen per topology at mesh-build time:

- **Ring attention** (`ring_attention`): K/V shards rotate hop-by-hop over
  the ICI ring (`jax.lax.ppermute`) while each chip keeps an online-softmax
  accumulator (m, l, o) over its resident queries — attention memory stays
  O(T²/n²) per chip and the per-hop transfer is the K/V shard, which XLA
  overlaps with the block matmuls. Works for any head count.
- **Ulysses** (`ulysses_attention`): `jax.lax.all_to_all` swaps the
  sequence axis for the head axis so each chip runs full-sequence attention
  on H/n heads; two big collectives instead of n-1 small ones. The local
  core is blockwise (same online-softmax update) so memory stays bounded.

Both cores consume the q/k/v produced by `models.common.project_qkv` and
plug into `transformer_block`'s `attn_fn` hook, so family flags (GQA,
sliding window, logit softcap, Gemma norms) behave identically to the dense
path.

Integration: `InferenceEngine` uses `make_ring_prefill` for fresh long
prompts (slot offset 0) past a length threshold; the returned full-sequence
K/V is scattered into the per-knight slot cache, so decode and later
delta-prefills proceed on the normal path. Weights are replicated over the
seq axis (for long-context prefill, activations — not weights — are the
memory bound; TP×SP composition is a future mesh axis).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .models.common import (
    ModelConfig,
    Params,
    _einsum,
    _softcap,
    embed_tokens,
    layer_body,
    project_qkv,
    rms_norm,
    transformer_block,
)

SEQ_AXIS = "seq"
BIG_NEG = -2.3819763e38


def _shard_map(f, mesh, in_specs, out_specs):
    from .compat import shard_map
    return shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs)


def build_seq_mesh(n_seq: int, devices: Optional[list] = None) -> Mesh:
    """A 1-axis ("seq",) mesh over the first n_seq devices."""
    devices = devices if devices is not None else jax.devices()
    if len(devices) < n_seq:
        raise ValueError(
            f"seq mesh needs {n_seq} devices, have {len(devices)}")
    return Mesh(np.array(devices[:n_seq]), (SEQ_AXIS,))


def _gqa_expand(x: jax.Array, repeat: int) -> jax.Array:
    return jnp.repeat(x, repeat, axis=2) if repeat > 1 else x


def _online_update(m, l, o, q, k_blk, v_blk, q_pos, kv_pos, kv_valid,
                   cfg: ModelConfig, kv_repeat: int):
    """One flash-attention-style accumulation step against a K/V block.

    State (m=max, l=normalizer, o=unnormalized output) is [B,H,T] / [B,H,T]
    / [B,H,T,D] in f32. q is pre-scaled+roped [B,T,H,D]; k_blk/v_blk are
    roped KV-head blocks [B,S,K,D] with absolute positions kv_pos [B,S].
    """
    k_att = _gqa_expand(k_blk, kv_repeat)
    v_att = _gqa_expand(v_blk, kv_repeat)
    logits = jnp.einsum("bthd,bshd->bhts", q, k_att,
                        preferred_element_type=jnp.float32)
    logits = _softcap(logits, cfg.attn_logit_softcap)
    mask = kv_pos[:, None, :] <= q_pos[:, :, None]        # causal
    mask &= kv_pos[:, None, :] < kv_valid[:, None, None]  # padded rows
    if cfg.sliding_window is not None:
        mask &= kv_pos[:, None, :] > q_pos[:, :, None] - cfg.sliding_window
    mask = mask[:, None, :, :]                            # [B,1,T,S]
    logits = jnp.where(mask, logits, BIG_NEG)
    m_new = jnp.maximum(m, logits.max(axis=-1))
    # `* mask` matters: an all-masked block has logits == m_new == BIG_NEG
    # and exp(0) would otherwise contribute a spurious 1 per key.
    p = jnp.exp(logits - m_new[..., None]) * mask
    corr = jnp.exp(m - m_new)
    l = l * corr + p.sum(axis=-1)
    o = o * corr[..., None] + jnp.einsum(
        "bhts,bshd->bhtd", p, v_att.astype(jnp.float32))
    return m_new, l, o


def _finalize(l, o, dtype) -> jax.Array:
    """[B,H,T,D] accumulator → [B,T,H,D] output; fully-masked (pad) query
    rows have l == 0 and are defined as 0."""
    out = o / jnp.where(l == 0.0, 1.0, l)[..., None]
    return out.transpose(0, 2, 1, 3).astype(dtype)


def ring_attention(q, k, v, q_pos, kv_pos, kv_valid, cfg: ModelConfig,
                   axis_name: str = SEQ_AXIS,
                   axis_size: Optional[int] = None) -> jax.Array:
    """Sequence-parallel causal attention; call INSIDE shard_map.

    q: local query shard [B,Tl,H,D] (pre-scaled+roped), k/v: local KV shard
    [B,Sl,K,D] (roped), q_pos/kv_pos: absolute positions [B,Tl]/[B,Sl],
    kv_valid: [B] total valid length. Returns [B,Tl,H,D].

    The K/V shard (and its positions) makes axis_size-1 ppermute hops
    around the ring; masks are computed from absolute positions, so no
    shard-index arithmetic is needed and ragged tails just mask out.
    """
    n = axis_size if axis_size is not None else jax.lax.psum(1, axis_name)
    b, t, h, _ = q.shape
    d = q.shape[-1]
    m = jnp.full((b, cfg.num_heads, t), -jnp.inf, jnp.float32)
    l = jnp.zeros((b, cfg.num_heads, t), jnp.float32)
    o = jnp.zeros((b, cfg.num_heads, t, d), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]
    for step in range(n):
        m, l, o = _online_update(m, l, o, q, k, v, q_pos, kv_pos, kv_valid,
                                 cfg, cfg.kv_repeat)
        if step < n - 1:
            k = jax.lax.ppermute(k, axis_name, perm)
            v = jax.lax.ppermute(v, axis_name, perm)
            kv_pos = jax.lax.ppermute(kv_pos, axis_name, perm)
    return _finalize(l, o, q.dtype)


def blockwise_sdpa(q, k, v, q_pos, kv_pos, kv_valid, cfg: ModelConfig,
                   block: int = 512) -> jax.Array:
    """Single-device blockwise attention (online softmax over KV chunks) —
    bounded memory for full-sequence attention; the local core of Ulysses.
    q [B,T,H,D], k/v [B,S,K',D] where H % K' == 0."""
    b, t, h, d = q.shape
    s = k.shape[1]
    repeat = h // k.shape[2]
    m = jnp.full((b, h, t), -jnp.inf, jnp.float32)
    l = jnp.zeros((b, h, t), jnp.float32)
    o = jnp.zeros((b, h, t, d), jnp.float32)
    for start in range(0, s, block):
        end = min(start + block, s)
        m, l, o = _online_update(
            m, l, o, q, k[:, start:end], v[:, start:end], q_pos,
            kv_pos[:, start:end], kv_valid, cfg, repeat)
    return _finalize(l, o, q.dtype)


def ulysses_attention(q, k, v, q_pos, kv_valid, cfg: ModelConfig,
                      axis_name: str = SEQ_AXIS, axis_size: int = 1,
                      block: int = 512) -> jax.Array:
    """All-to-all sequence parallelism; call INSIDE shard_map.

    Swap seq↔heads so each chip attends over the FULL sequence with H/n
    heads (two all-to-alls instead of a ring). Needs num_heads % n == 0;
    when kv heads don't divide n, they are GQA-expanded first (more bytes
    on the wire — the topology tradeoff vs ring_attention).
    """
    n = axis_size
    if cfg.num_heads % n != 0:
        raise ValueError(f"Ulysses needs heads ({cfg.num_heads}) % n ({n}) == 0")
    if k.shape[2] % n != 0:
        k = _gqa_expand(k, cfg.kv_repeat)
        v = _gqa_expand(v, cfg.kv_repeat)
    # [B,Tl,H,D] -> [B,T,H/n,D]: split heads, concat sequence.
    q_g = jax.lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1,
                             tiled=True)
    k_g = jax.lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1,
                             tiled=True)
    v_g = jax.lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1,
                             tiled=True)
    pos_g = jax.lax.all_gather(q_pos, axis_name, axis=1, tiled=True)  # [B,T]
    out = blockwise_sdpa(q_g, k_g, v_g, pos_g, pos_g, kv_valid, cfg, block)
    # [B,T,H/n,D] -> [B,Tl,H,D]
    return jax.lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)


@layer_body(static=("cfg", "scheme", "n"))
def _ring_block(x, layer, q_pos, lengths, *, cfg: ModelConfig, scheme: str,
                n: int):
    """The sequence-parallel prefill's layer as a body (models/common.
    layer_body), traced inside the shard_map: one block over this
    shard's rows, its attention core the ring's or Ulysses' over the
    `seq` axis of size `n`. -> (x, this shard's (k, v))."""

    def attn_fn(h, layer):
        q, k, v = project_qkv(h, layer, cfg, q_pos)
        if scheme == "ulysses":
            core = ulysses_attention(q, k, v, q_pos, lengths, cfg,
                                     SEQ_AXIS, n)
        else:
            core = ring_attention(q, k, v, q_pos, q_pos, lengths, cfg,
                                  SEQ_AXIS, n)
        out = _einsum("bthd,hde->bte", core, layer["o_proj"],
                      tp="row").astype(h.dtype)
        return out, (k, v)

    return transformer_block(x, layer, cfg, q_pos, None, None, None,
                             attn_fn=attn_fn)


def make_ring_prefill(cfg: ModelConfig, mesh: Mesh, scheme: str = "ring"):
    """Build the jitted sequence-parallel prefill program.

    Returns fn(params, tokens [B,Tp], positions [B,Tp], lengths [B]) ->
    (last-token logits f32 [B,V], [(k, v)] per layer, each [B,Tp,K,D]).
    Tp must divide by the seq-axis size; pad with any token id and let
    `lengths` mask the tail. Full [B,T,V] logits are never materialized —
    only the (valid-1)-position hidden state crosses the psum.
    """
    n = mesh.shape[SEQ_AXIS]

    def shard_fn(params, tokens, positions, lengths):
        # follows the param dtype (bf16 serving, f32 parity tests) — same
        # rule as models/common.py forward; embed_tokens/_einsum handle
        # int8 {"q","s"} leaves, so quant composes with seq parallelism
        x = embed_tokens(params["embedding"], tokens)
        if cfg.scale_embeddings:
            x = x * jnp.sqrt(jnp.float32(cfg.embed_dim)).astype(x.dtype)
        caches = []
        for layer in params["layers"]:
            x, kv = _ring_block(x, layer, positions, lengths, cfg=cfg,
                                scheme=scheme, n=n)
            caches.append(kv)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps,
                     cfg.rmsnorm_unit_offset)
        hit = (positions == (lengths - 1)[:, None]).astype(jnp.float32)
        last_h = jnp.einsum("bt,bte->be", hit, x.astype(jnp.float32))
        last_h = jax.lax.psum(last_h, SEQ_AXIS)
        head = params["embedding"] if cfg.tie_embeddings else params["lm_head"]
        logits = _einsum("be,ve->bv", last_h, head, tp="col")
        logits = _softcap(logits, cfg.final_logit_softcap)
        return logits, caches

    kv_spec = (P(None, SEQ_AXIS), P(None, SEQ_AXIS))
    mapped = _shard_map(
        shard_fn, mesh,
        in_specs=(P(), P(None, SEQ_AXIS), P(None, SEQ_AXIS), P(None)),
        out_specs=(P(None), [kv_spec] * cfg.num_layers))
    return jax.jit(mapped)


def pad_to_ring(lengths_max: int, n_seq: int, cache_len: int) -> int:
    """Bucketed padded length for ring prefill: next power-of-two multiple
    of n_seq ≥ lengths_max (recompile guard as prompts grow), capped at the
    largest n_seq-multiple that fits the cache. Returns 0 when the prompt
    cannot fit — caller falls back to chunked prefill."""
    cap = (cache_len // n_seq) * n_seq
    if lengths_max > cap:
        return 0
    tp = n_seq
    while tp < lengths_max:
        tp *= 2
    return min(tp, cap)


__all__ = [
    "SEQ_AXIS",
    "build_seq_mesh",
    "ring_attention",
    "ulysses_attention",
    "blockwise_sdpa",
    "make_ring_prefill",
    "pad_to_ring",
]
