"""The plain reference of a `brumby` decoder (Brumby-14B-Base): Qwen3-14B's
block with the attention product replaced by POWER RETENTION. Every
published layer is pre-norm and residual,

    x' = x + W_o . Ret(RMSNorm(x));   x'' = x' + MLP(RMSNorm(x'))

with `rms_norm_eps` 1e-6, no biases, a final RMSNorm and an untied head.
The engine's parameter tree holds each half as a layer of its own (one
mixer behind one norm), so `params["layers"][2 l]` is layer l's
retention and `[2 l + 1]` its MLP.

    Ret   h = RMSNorm(x); H query heads over K kv heads of D (40 over 8
          of 128: query head n reads kv head m = n // (H / K));
          q_t = rope_t(RMSNorm_head(W_q h_t)), k_t = rope_t(
          RMSNorm_head(W_k h_t)), v_t = W_v h_t, and one gate a kv head,
          log g_t = log sigmoid(W_g h_t) in float32. Causal, degree p:

              a_tj = exp(sum_{l=j+1..t} log g_l) (q_t . k_j / sqrt D)^p
              y_t  = sum_{j<=t} a_tj v_j / sum_{j<=t} a_tj

          (p even: every weight is >= 0 and the row sum normalises; the
          scale cancels in the quotient and is kept for range.) Rotary
          plain, frequencies theta^(-2 m / D) over all D dimensions,
          dimension m paired with m + D / 2.
    MLP   W_down (silu(W_gate h) * W_up h).

Which lines are the paper's (Manifest AI, "Scaling Context Requires
Rethinking Attention", arXiv:2507.04239) and which are this
configuration's `assumed`, one key each: the paper's are the weight
(q . k)^p with even p, the gated (decayed) causal sum and its
normalisation by the row sum. Assumed, because the config does not fix
them: p = 2; the gate as log sigmoid of a linear map of the layer's
normed input, ONE A KV HEAD (one a query head is the other reading); no
epsilon in the normaliser; per-head RMS norm of q and k ahead of the
rotary embedding and full rotary, both kept from the Qwen3 lineage. The
published kernels' switch to the attention form under a length
threshold is an implementation choice with the same mathematics: this
file IS that form, at every length.

Plain `jax.numpy` in float32 under `default_matmul_precision("highest")`:
the quadratic form above, a dense mask, a head at a time, the QUERY rows
in blocks of QUERY_BLOCK (one head's weights are [512, T]), no state, no
feature map, no chunks, no kernels, no batch. It is fed the engine's own
parameter tree and reads it a leaf at a time through `read` (float32; a
control may round a matrix on the way); `gate=False` sets every g to 1
(a control: the decay left out). It shares no code with the program.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512
POWER = 2


def as_float32(leaf):
    return jnp.asarray(leaf, jnp.float32)


def _normed(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * weight


def sizes_of(config: dict) -> dict:
    """What the equations need, from the published keys alone."""
    if config.get("attention_bias") or config.get("rope_scaling"):
        raise ValueError("this reference knows no bias and plain rotary")
    return {"depth": int(config["num_hidden_layers"]),
            "eps": float(config["rms_norm_eps"]),
            "heads": int(config["num_attention_heads"]),
            "kv_heads": int(config["num_key_value_heads"]),
            "head_dim": int(config["head_dim"]),
            "theta": float(config["rope_theta"])}


def rotary_frequencies(theta: float, head_dim: int) -> np.ndarray:
    m = np.arange(head_dim // 2, dtype=np.float64)
    return theta ** (-2.0 * m / head_dim)


def _turn(x, positions, frequencies):
    """x [..., T, D] at `positions` [T]: dimension m with m + D/2."""
    angle = positions.astype(jnp.float32)[:, None] * frequencies[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    half = frequencies.shape[0]
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


# --- power retention ---------------------------------------------------------


@partial(jax.jit, static_argnames=("eps", "gate", "read"))
def _keys_values_decay(layer, x, frequencies, *, eps, gate, read):
    """-> k [K, T, D] (normed a head, turned), v [K, T, D] and the
    cumulative log-decay b [K, T]: b_t = sum_{l <= t} log g_l."""
    h = _normed(x, read(layer["norm"]), eps)
    k = jnp.einsum("te,ekd->ktd", h, read(layer["k_proj"]))
    k = _normed(k, read(layer["k_norm"]), eps)
    v = jnp.einsum("te,ekd->ktd", h, read(layer["v_proj"]))
    log_g = jax.nn.log_sigmoid(h @ read(layer["g_proj"]))       # [T, K]
    if not gate:
        log_g = jnp.zeros_like(log_g)
    return (_turn(k, jnp.arange(x.shape[0]), frequencies), v,
            jnp.cumsum(log_g, axis=0).T)


@partial(jax.jit, static_argnames=("block", "eps", "read"))
def _retain_block(layer, x, k, v, b, first_row, frequencies, *, block,
                  eps, read):
    """Rows first_row .. first_row + block of x + W_o Ret(norm x): every
    head against every key under a dense causal mask."""
    rows = first_row + jnp.arange(block)
    xb = jax.lax.dynamic_slice_in_dim(x, first_row, block, 0)
    h = _normed(xb, read(layer["norm"]), eps)
    w_q, w_o = read(layer["q_proj"]), read(layer["o_proj"])
    q_norm = read(layer["q_norm"])
    heads, d = w_q.shape[1], w_q.shape[2]
    group = heads // k.shape[0]
    causal = rows[:, None] >= jnp.arange(k.shape[1])[None, :]

    def head(n):
        m = n // group
        q = _turn(_normed(h @ w_q[:, n, :], q_norm, eps), rows,
                  frequencies)
        decay = jax.lax.dynamic_slice_in_dim(b[m], first_row, block, 0)[
            :, None] - b[m][None, :]
        a = jnp.where(causal, jnp.exp(jnp.where(causal, decay, 0.0))
                      * (q @ k[m].T * d ** -0.5) ** POWER, 0.0)
        y = (a @ v[m]) / jnp.sum(a, axis=-1, keepdims=True)
        return y @ w_o[n]                                   # [B, E]

    return xb + jnp.sum(jax.lax.map(head, jnp.arange(heads)), axis=0)


def retention_layer(layer, x, frequencies, *, gate=True, **how):
    t = x.shape[0]
    block = math.gcd(t, QUERY_BLOCK)       # 512 for the harness's padding
    k, v, b = _keys_values_decay(layer, x, frequencies, gate=gate, **how)
    return jnp.concatenate([
        _retain_block(layer, x, k, v, b, jnp.int32(r), frequencies,
                      block=block, **how)
        for r in range(0, t, block)], axis=0)


@partial(jax.jit, static_argnames=("eps", "read"))
def mlp_layer(layer, x, *, eps, read):
    h = _normed(x, read(layer["norm"]), eps)
    return x + (jax.nn.silu(h @ read(layer["gate_proj"]))
                * (h @ read(layer["up_proj"]))) @ read(layer["down_proj"])


# --- the model ---------------------------------------------------------------


@partial(jax.jit, static_argnames=("eps", "read"))
def _logits(norm, head, x, rows, *, eps, read):
    return _normed(x[rows], read(norm), eps) @ read(head).T


def hidden_after(params, config: dict, tokens, n_blocks=None,
                 read=as_float32, gate=True) -> jax.Array:
    """The residual stream [T, E] after the first `n_blocks` published
    layers (both halves); `tokens` one-dimensional."""
    sizes = sizes_of(config)
    frequencies = jnp.asarray(
        rotary_frequencies(sizes["theta"], sizes["head_dim"]), jnp.float32)
    layers = params["layers"]
    with jax.default_matmul_precision("highest"):
        x = as_float32(params["embedding"][jnp.asarray(tokens)])
        for l in range(sizes["depth"] if n_blocks is None else n_blocks):
            assert layers[2 * l]["q_proj"].shape[1:] == (
                sizes["heads"], sizes["head_dim"]), l
            assert layers[2 * l]["g_proj"].shape[1] == sizes["kv_heads"], l
            x = retention_layer(layers[2 * l], x, frequencies, gate=gate,
                                eps=sizes["eps"], read=read)
            x = mlp_layer(layers[2 * l + 1], x, eps=sizes["eps"],
                          read=read)
        return x


def logits_at(params, config: dict, tokens, rows, read=as_float32,
              gate=True) -> jax.Array:
    """Float32 logits [len(rows), vocab] over the whole sequence
    `tokens` (one-dimensional, padded as the caller likes to a multiple
    of 512: every layer is causal, so what follows a row never reaches
    it)."""
    x = hidden_after(params, config, tokens, read=read, gate=gate)
    with jax.default_matmul_precision("highest"):
        return _logits(params["final_norm"], params["lm_head"], x,
                       jnp.asarray(rows), eps=sizes_of(config)["eps"],
                       read=read)
