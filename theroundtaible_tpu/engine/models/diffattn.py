"""Differential attention (`ModelConfig.diff_attn`; arXiv:2410.05258): the
"attention" and "cross" layers of a model whose heads come in pairs. With
H query heads over K kv heads of D (H = 2 K' for K' = K / 2 kv PAIRS a
whole number of times), differential head j = 0 .. H/2 - 1 takes query
heads (2j, 2j+1) and kv pair p = j // (H / K) = kv heads (2p, 2p+1):

    A1  = softmax(q_{2j}   k_{2p}^T   / sqrt(D) + mask)
    A2  = softmax(q_{2j+1} k_{2p+1}^T / sqrt(D) + mask)
    V_p = [v_{2p} ; v_{2p+1}]                                   (2 D wide)
    o_j = (1 - l0) RMSNorm_{2D}(A1 V_p - l A2 V_p; g, eps)
    l   = exp(lq1 . lk1) - exp(lq2 . lk2) + l0     (four D-vectors a layer)
    out = W_o [o_0 ... o_{H/2-1}] + b_o

`l0` is the layer's `lambda_init` leaf (`lambda_init(i)` of its published
depth i: a constant the model's code derives, stored so that layers of
one geometry share one trace).

**On the paged kernels, unedited.** A kv pair lies in ONE 128-lane row of
a page — K row p = [k_{2p} ; k_{2p+1}], V row p = V_p: the cell
`ModelConfig.lane_pack` already gives 64-wide heads, `[pages, page, K/2,
2 D]`. Query head 2j goes in as [q_{2j} ; 0], head 2j+1 as [0 ; q_{2j+1}]
(`pack_queries`): to every kernel that IS grouped-query attention of H
heads over K/2 of 2 D, a score is the product with the own key alone,
the softmax is the own head's, and the weighted sum is over the whole
V_p — A1 V_p and A2 V_p as they stand, no page byte read twice. The
queries arrive 2 D wide, so the wrappers' own packing (`pallas/
attention._on_packed`, which pairs heads the grouped-query way and keeps
half a row) is not engaged. The subtraction, the norm and (1 - l0) are
XLA between the kernel and W_o (`combine`).

A cross layer (`KIND`) has `q_proj`, the l-vectors, `sub_norm` and
`o_proj` only: it reads the pages of the nearest attention layer below
it and writes nothing.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import MASK_VALUE, ModelConfig, Params, _einsum, rms_norm

KIND = "cross"
LAMBDA_STD = 0.1


def lambda_init(depth: int) -> float:
    """l0 of the published layer `depth` (0-based)."""
    return 0.8 - 0.6 * math.exp(-0.3 * depth)


def init_mixer(cfg: ModelConfig, ks, dense, out, dtype, *, depth: int,
               cross: bool) -> Params:
    """The leaves of one differential layer: `dense` / `out` are
    `hybrid.init_layer`'s; biases at 0.02, the l-vectors N(0,
    LAMBDA_STD), the pair norm ones."""
    e, h, k, d = cfg.embed_dim, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    lam = jax.random.normal(ks[4], (4, d), jnp.float32) * LAMBDA_STD

    def bias(key, shape):
        return (jax.random.normal(key, shape, jnp.float32)
                * 0.02).astype(dtype)

    layer = {
        "q_proj": dense(ks[0], (e, h, d), e),
        "o_proj": out(ks[3], (h // 2, 2 * d, e), h * d),
        "lambda_q1": lam[0], "lambda_k1": lam[1],
        "lambda_q2": lam[2], "lambda_k2": lam[3],
        "lambda_init": jnp.asarray(lambda_init(depth), jnp.float32),
        "sub_norm": jnp.ones((2 * d,), dtype),
    }
    if not cross:
        layer["k_proj"] = dense(ks[1], (e, k, d), e)
        layer["v_proj"] = dense(ks[2], (e, k, d), e)
    if cfg.attn_bias:
        bk = jax.random.split(ks[5], 4)
        layer["q_bias"] = bias(bk[0], (h, d))
        layer["o_bias"] = bias(bk[3], (e,))
        if not cross:
            layer["k_bias"] = bias(bk[1], (k, d))
            layer["v_bias"] = bias(bk[2], (k, d))
    return layer


def pack_queries(q: jax.Array) -> jax.Array:
    """q [..., H, D] -> [..., H, 2 D]: an even head in the first half of
    its row, an odd head in the second, zeros in the other."""
    odd = (jnp.arange(q.shape[-2]) % 2 == 1)[:, None]
    zero = jnp.zeros((), q.dtype)
    return jnp.concatenate([jnp.where(odd, zero, q),
                            jnp.where(odd, q, zero)], axis=-1)


def queries(h: jax.Array, layer: Params, cfg: ModelConfig) -> jax.Array:
    """The packed, scaled queries [B, T, H, 2 D] of a layer that projects
    no keys (a cross layer; no position embedding: `cfg.rope` False)."""
    q = _einsum("bte,ehd->bthd", h, layer["q_proj"], tp="col")
    if cfg.attn_bias:
        q = q + layer["q_bias"].astype(jnp.float32)
    scale = (cfg.query_pre_attn_scalar
             if cfg.query_pre_attn_scalar is not None
             else cfg.head_dim ** -0.5)
    return pack_queries(q.astype(h.dtype) * scale)


def combine(out: jax.Array, layer: Params, cfg: ModelConfig) -> jax.Array:
    """The kernels' result [B, T, H, 2 D] (A1 V_p at even heads, A2 V_p
    at odd) -> o [B, T, H/2, 2 D] float32."""
    b, t, h, w = out.shape
    o = out.astype(jnp.float32).reshape(b, t, h // 2, 2, w)
    l0 = layer["lambda_init"].astype(jnp.float32)
    lam = (jnp.exp(jnp.sum(layer["lambda_q1"] * layer["lambda_k1"]))
           - jnp.exp(jnp.sum(layer["lambda_q2"] * layer["lambda_k2"]))
           + l0)
    diff = o[..., 0, :] - lam * o[..., 1, :]
    return rms_norm(diff, layer["sub_norm"], cfg.norm_eps, False) \
        * (1.0 - l0)


def output(out: jax.Array, layer: Params, cfg: ModelConfig,
           dtype) -> jax.Array:
    """The kernels' result -> the layer's output [B, T, E]."""
    y = _einsum("btjd,jde->bte", combine(out, layer, cfg).astype(dtype),
                layer["o_proj"], tp="row")
    if cfg.attn_bias:
        y = y + layer["o_bias"].astype(jnp.float32)
    return y.astype(dtype)


def dense_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    mask: jax.Array) -> jax.Array:
    """The packed form without pages, for the whole-sequence forward:
    q [B, T, H, 2 D] packed, k / v [B, S, K, D], mask [B, T, S] ->
    [B, T, H, 2 D] (A1 V_p at even heads, A2 V_p at odd)."""
    b, s, kh, d = k.shape
    rows = kh // 2
    rep = q.shape[2] // rows
    kp = jnp.repeat(k.reshape(b, s, rows, 2 * d), rep, axis=2)
    vp = jnp.repeat(v.reshape(b, s, rows, 2 * d), rep, axis=2)
    logits = jnp.einsum("bthd,bshd->bhts", q, kp,
                        preferred_element_type=jnp.float32)
    logits = jnp.where(mask[:, None], logits, MASK_VALUE)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, vp,
                      preferred_element_type=jnp.float32).astype(q.dtype)
