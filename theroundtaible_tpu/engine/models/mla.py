"""Multi-head latent attention (MLA; DeepSeek-V2/V3, `axk1`).

Per layer, with h the normed input [T, E] and H heads:

    c_q = RMSNorm(h W_DQ)                         (q_lora_rank)
    [q_nope_i ; q_rope_i] = c_q W_UQ,i            (qk_nope_dim + qk_rope_dim)
    [c ; k_r] = h W_DKV                           (kv_lora_rank + qk_rope_dim)
    c_kv = RMSNorm(c);  k_rope = R(k_r);  q_rope_i <- R(q_rope_i)

R is the rotary embedding over the qk_rope_dim dimensions at YaRN-blended
frequencies (`yarn_inv_freq`), pairs taken as `common.rope` takes them.
THE CACHE HOLDS (c_kv, k_rope) AND NOTHING ELSE.

Published (expanded) form — `expanded_attention`, the whole-sequence
forward:

    [k_nope_i ; v_i] = c_kv W_UKV,i;  k_i = [k_nope_i ; k_rope]
    p = softmax(s q_i . k_i) causal;  o_i = sum p v_i;  out = concat(o_i) W_O

Absorbed form — `latents` / `values_of`, every path that reads pages:

    q~_i = q_nope_i W_UK,i^T                      (kv_lora_rank)
    score = s (q~_i . c_kv + q_rope_i . k_rope);  o-_i = sum p c_kv
    o_i = o-_i W_UV,i

which is multi-query attention with ONE kv head of key width
kv_lora_rank + qk_rope_dim whose values are the first kv_lora_rank
columns of its keys: the paged kernels' latent mode
(pallas/attention.py: `v_pool=None`, `v_dim`). A page entry is
[c_kv ; k_rope ; 0...] padded to `cfg.page_width` (whole lane rows), the
query [s q~_i ; s q_rope_i ; 0...] likewise, so the padding adds nothing
to a score. The two forms are equal in exact arithmetic.

The softmax scale s = (qk_nope_dim + qk_rope_dim)^-0.5 m^2 with
m = 0.1 mscale_all_dim ln(factor) + 1 (YaRN's attention temperature),
and the cos/sin multiplier mscale(factor, mscale) / mscale(factor,
mscale_all_dim) is 1 wherever the two are equal (`axk1`: both 1).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .common import (MASK_VALUE, ModelConfig, Params, _einsum, rms_norm,
                     rope, yarn_inv_freq, yarn_mscale)


def rope_frequencies(cfg: ModelConfig) -> tuple[np.ndarray, float]:
    """(inv_freq [qk_rope_dim/2], the cos/sin multiplier)."""
    d = cfg.qk_rope_dim
    if cfg.rope_yarn is None:
        return (1.0 / cfg.rope_theta ** (
            np.arange(0, d, 2, dtype=np.float64) / d)).astype(np.float32), 1.0
    factor, original_max, fast, slow, mscale, mscale_all = cfg.rope_yarn
    return (yarn_inv_freq(d, cfg.rope_theta, factor, original_max, fast,
                          slow),
            yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all))


def softmax_scale(cfg: ModelConfig) -> float:
    s = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    if cfg.rope_yarn is not None:
        factor, *_rest, mscale_all = cfg.rope_yarn
        s *= yarn_mscale(factor, mscale_all) ** 2
    return s


def _rope(x: jax.Array, positions: jax.Array, cfg: ModelConfig):
    inv_freq, mult = rope_frequencies(cfg)
    if mult != 1.0:
        raise NotImplementedError(
            f"{cfg.name}: a rotary cos/sin multiplier of {mult} (mscale "
            "!= mscale_all_dim) is not written")
    return rope(x, positions, cfg.rope_theta, inv_freq=jnp.asarray(inv_freq))


def _queries(h: jax.Array, layer: Params, cfg: ModelConfig,
             positions: jax.Array):
    """-> (q_nope [B,T,H,nope], q_rope [B,T,H,rope] roped), h's dtype."""
    c_q = rms_norm(_einsum("bte,er->btr", h, layer["q_a"]).astype(h.dtype),
                   layer["q_norm"], cfg.norm_eps, False)
    q = _einsum("btr,rhd->bthd", c_q, layer["q_b"]).astype(h.dtype)
    return (q[..., :cfg.qk_nope_dim],
            _rope(q[..., cfg.qk_nope_dim:], positions, cfg))


def _latent(h: jax.Array, layer: Params, cfg: ModelConfig,
            positions: jax.Array):
    """-> (c_kv [B,T,R] normed, k_rope [B,T,rope] roped), h's dtype."""
    ckr = _einsum("bte,er->btr", h, layer["kv_a"]).astype(h.dtype)
    c_kv = rms_norm(ckr[..., :cfg.kv_lora_rank], layer["kv_norm"],
                    cfg.norm_eps, False)
    k_rope = _rope(ckr[..., None, cfg.kv_lora_rank:], positions, cfg)
    return c_kv, k_rope[:, :, 0]


def _pad_last(x: jax.Array, width: int) -> jax.Array:
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1)
                   + [(0, width - x.shape[-1])])


def latents(h: jax.Array, layer: Params, cfg: ModelConfig,
            positions: jax.Array):
    """Absorbed form. h [B,T,E] (normed) -> (q [B,T,H,W] scaled, what the
    kernels multiply against pages; entry [B,T,W], what this position
    writes to its page), W = cfg.page_width."""
    q_nope, q_rope = _queries(h, layer, cfg, positions)
    c_kv, k_rope = _latent(h, layer, cfg, positions)
    w_uk = layer["kv_b"][..., :cfg.qk_nope_dim]            # [R,H,nope]
    q_lat = _einsum("bthn,rhn->bthr", q_nope, w_uk).astype(h.dtype)
    q = jnp.concatenate([q_lat, q_rope], axis=-1) \
        * jnp.asarray(softmax_scale(cfg), h.dtype)
    entry = jnp.concatenate([c_kv, k_rope], axis=-1)
    return _pad_last(q, cfg.page_width), _pad_last(entry, cfg.page_width)


def values_of(o_lat: jax.Array, layer: Params, cfg: ModelConfig):
    """o-_i [.., H, R] (the kernels' result) -> o_i = o-_i W_UV,i
    [.., H, v_head_dim]."""
    w_uv = layer["kv_b"][..., cfg.qk_nope_dim:]            # [R,H,v]
    return _einsum("...hr,rhv->...hv", o_lat, w_uv).astype(o_lat.dtype)


def expanded_attention(h: jax.Array, layer: Params, cfg: ModelConfig,
                       positions: jax.Array, attn_mask: jax.Array):
    """Published form over whole sequences, no pages: per-head keys and
    values from c_kv. h [B,T,E], attn_mask [B,T,T] -> (out [B,T,E],
    (c_kv, k_rope)): what a cache would hold."""
    q_nope, q_rope = _queries(h, layer, cfg, positions)
    c_kv, k_rope = _latent(h, layer, cfg, positions)
    kv = _einsum("bsr,rhd->bshd", c_kv, layer["kv_b"]).astype(h.dtype)
    k_nope, v = kv[..., :cfg.qk_nope_dim], kv[..., cfg.qk_nope_dim:]
    logits = (_einsum("bthd,bshd->bhts", q_nope, k_nope)
              + _einsum("bthd,bsd->bhts", q_rope, k_rope)) \
        * softmax_scale(cfg)
    logits = jnp.where(attn_mask[:, None, :, :], logits, MASK_VALUE)
    probs = jax.nn.softmax(logits, axis=-1).astype(h.dtype)
    o = _einsum("bhts,bshd->bthd", probs, v).astype(h.dtype)
    out = _einsum("bthd,hde->bte", o, layer["o_proj"],
                  tp="row").astype(h.dtype)
    return out, (c_kv, k_rope)
