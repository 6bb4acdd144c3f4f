"""The plain reference of an `lfm2_moe` decoder (LFM2-24B-A2B), written
from the layer equations. Every published layer l is pre-norm and
residual twice,

    x' = x + Operator_l(RMSNorm(x));   x'' = x' + FeedForward_l(RMSNorm(x'))

with `norm_eps` 1e-5, a final RMSNorm (the published code calls it
`embedding_norm`) and logits on the embedding (tied). The engine's
parameter tree holds each half as a layer of its own (one mixer behind
one norm), so `params["layers"][2 l]` is layer l's operator and
`[2 l + 1]` its feed-forward.

    conv   `layer_types[l]` "conv": the gated short convolution, K =
           `conv_L_cache` taps, no bias, NO activation. h = RMSNorm(x):
           [B_t, C_t, u_t] = W_in h_t           (E -> 3 E, in that order)
           g_t             = B_t * u_t
           c_t             = sum_{j<K} w[j] * g_{t-K+1+j}   (g before the
                             sequence's start is 0; one filter a channel)
           out_t           = W_out (C_t * c_t)
    attn   "full_attention": H query heads over K_v kv heads of D = E / H
           (32 over 8 of 64: query head i reads kv head i // (H / K_v)),
           no bias; every q head and every k head through an RMSNorm of
           its own over D (weights `q_norm`, `k_norm`) BEFORE rotary;
           plain rotary at `rope_parameters.rope_theta` over all D,
           dimension m paired with m + D / 2; causal softmax at D^-0.5,
           no window.
    dense  l < `num_dense_layers`: W_down (silu(W_gate h) * W_up h).
    moe    else: s = sigmoid(h W_r) over ALL `num_experts` in float32;
           the `num_experts_per_tok` largest of s + b (`use_expert_bias`:
           b is used for the choice alone); `norm_topk_prob`: w_e = s_e /
           sum of the chosen s, times `routed_scaling_factor`; no shared
           expert; y = sum_e w_e W_down^e (silu(W_gate^e h) * W_up^e h).

Assumed, because the config does not say (the configuration file lists
each under `assumed`): head_dim = hidden / heads (the config names
none); the head tied to the embedding (the family's convention; no key);
the order B, C, u of W_in's thirds (the lfm2 modelling code's
`chunk(3)`); rotary pairs (m, m + D/2) where modelling code may
interleave — with seeded random weights a permutation of W_Q / W_K
columns.

Plain `jax.numpy` in float32 under `default_matmul_precision("highest")`:
the convolution as a sum of K shifted rows over the whole sequence,
attention a head at a time as a full causal softmax under a dense mask,
the experts as a dense loop over all of them, each over every token with
the router's weight (0: not chosen); no cache, no chunk, no kernel, no
batch. The QUERY rows of an attention layer go through it in blocks of
QUERY_BLOCK so that one head's scores are [512, T] and the harness's
longest sequence fits beside the engine this checks. It is fed the
engine's own parameter tree and reads it a leaf (an expert) at a time
through `read` (float32; a control may round a matrix on the way). It
shares no code with the program.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512
LAYER_TYPES = ("conv", "full_attention")


def as_float32(leaf):
    return jnp.asarray(leaf, jnp.float32)


def _normed(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * weight


def sizes_of(config: dict) -> dict:
    """What the equations need, from the published keys alone."""
    depth = int(config["num_hidden_layers"])
    types = list(config["layer_types"])
    if len(types) != depth:
        raise ValueError(f"layer_types has {len(types)} entries, "
                         f"num_hidden_layers says {depth}")
    if set(types) - set(LAYER_TYPES):
        raise ValueError("this reference knows conv and full_attention "
                         f"layers, not {sorted(set(types) - set(LAYER_TYPES))}")
    if config.get("conv_bias") or config.get("norm_topk_prob") is not True \
            or config.get("use_expert_bias") is not True:
        raise ValueError("this reference is written for conv_bias false, "
                         "norm_topk_prob true and use_expert_bias true")
    heads = int(config["num_attention_heads"])
    return {"depth": depth, "types": types,
            "eps": float(config["norm_eps"]),
            "heads": heads,
            "kv_heads": int(config["num_key_value_heads"]),
            "head_dim": int(config.get("head_dim")
                            or int(config["hidden_size"]) // heads),
            "theta": float(config["rope_parameters"]["rope_theta"]),
            "taps": int(config["conv_L_cache"]),
            "dense": int(config["num_dense_layers"]),
            "experts": int(config["num_experts"]),
            "top_k": int(config["num_experts_per_tok"]),
            "scale": float(config["routed_scaling_factor"])}


# --- the short convolution ---------------------------------------------------


@partial(jax.jit, static_argnames=("taps", "eps", "read"))
def conv_layer(layer, x, *, taps, eps, read):
    """x [T, E] -> x + W_out (C * conv(B * u)), the convolution a sum of
    `taps` shifted rows over the whole sequence."""
    t, e = x.shape
    h = _normed(x, read(layer["norm"]), eps)
    bcu = h @ read(layer["in_proj"])                        # [T, 3E]
    b, c, u = bcu[:, :e], bcu[:, e:2 * e], bcu[:, 2 * e:]
    g = b * u
    w = read(layer["conv_w"])                               # [K, E]
    padded = jnp.concatenate([jnp.zeros((taps - 1, e), jnp.float32), g], 0)
    conv = sum(w[j] * padded[j:j + t] for j in range(taps))
    return x + (c * conv) @ read(layer["out_proj"])


# --- attention ---------------------------------------------------------------


def _turn(x, positions, frequencies):
    """x [..., T, D] at `positions` [T]: dimension m with m + D/2."""
    angle = positions.astype(jnp.float32)[:, None] * frequencies[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    half = frequencies.shape[0]
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


@partial(jax.jit, static_argnames=("eps", "read"))
def _keys_values(layer, x, frequencies, *, eps, read):
    h = _normed(x, read(layer["norm"]), eps)
    k = jnp.einsum("te,ekd->ktd", h, read(layer["k_proj"]))
    v = jnp.einsum("te,ekd->ktd", h, read(layer["v_proj"]))
    k = _normed(k, read(layer["k_norm"]), eps)
    return _turn(k, jnp.arange(x.shape[0]), frequencies), v


@partial(jax.jit, static_argnames=("block", "eps", "read"))
def _attend_block(layer, x, k, v, first_row, frequencies, *, block, eps,
                  read):
    """Rows first_row .. first_row + block of x + W_o Attn(norm x):
    every head against every key under a dense causal mask."""
    rows = first_row + jnp.arange(block)
    xb = jax.lax.dynamic_slice_in_dim(x, first_row, block, 0)
    h = _normed(xb, read(layer["norm"]), eps)
    w_q, w_o = read(layer["q_proj"]), read(layer["o_proj"])
    heads, d = w_q.shape[1], w_q.shape[2]
    group = heads // k.shape[0]
    visible = rows[:, None] >= jnp.arange(k.shape[1])[None, :]
    q_norm = read(layer["q_norm"])

    def head(i):
        q = _turn(_normed(h @ w_q[:, i, :], q_norm, eps), rows, frequencies)
        scores = q @ k[i // group].T * d ** -0.5
        p = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
        return (p @ v[i // group]) @ w_o[i]                # [B, E]

    return xb + jnp.sum(jax.lax.map(head, jnp.arange(heads)), axis=0)


def attention_layer(layer, x, frequencies, **how):
    t = x.shape[0]
    block = math.gcd(t, QUERY_BLOCK)       # 512 for the harness's padding
    k, v = _keys_values(layer, x, frequencies, **how)
    return jnp.concatenate([
        _attend_block(layer, x, k, v, jnp.int32(r), frequencies,
                      block=block, **how)
        for r in range(0, t, block)], axis=0)


# --- feed-forward ------------------------------------------------------------


@partial(jax.jit, static_argnames=("eps", "read"))
def dense_layer(layer, x, *, eps, read):
    h = _normed(x, read(layer["norm"]), eps)
    return x + (jax.nn.silu(h @ read(layer["gate_proj"]))
                * (h @ read(layer["up_proj"]))) @ read(layer["down_proj"])


def router_weights(h, router, bias, top_k: int, scale: float):
    """[T, E] x [E, X] -> dense weights [T, X]: s = sigmoid over all X,
    the top_k largest of s + bias kept at s, renormalised to sum to one
    and scaled; the rest 0."""
    s = jax.nn.sigmoid(h @ router)
    ranked = jnp.argsort(-(s + bias), axis=-1)             # stable
    rank = jnp.argsort(ranked, axis=-1)
    kept = jnp.where(rank < top_k, s, 0.0)
    return kept / jnp.sum(kept, axis=-1, keepdims=True) * scale


@partial(jax.jit, static_argnames=("top_k", "scale", "eps", "read"))
def experts_layer(layer, x, *, top_k, scale, eps, read):
    """x [T, E] -> x + sum_e w_e Expert_e(norm x): the experts one at a
    time, each over every token with its weight (0: not chosen)."""
    h = _normed(x, read(layer["norm"]), eps)
    w = router_weights(h, as_float32(layer["router"]),
                       as_float32(layer["router_bias"]), top_k, scale)

    def add_expert(total, one):
        gate, up, down, w_e = one
        a = jax.nn.silu(h @ read(gate)) * (h @ read(up))
        return total + (a @ read(down)) * w_e[:, None], None

    stack = layer["experts"]
    out, _ = jax.lax.scan(add_expert, x, (
        stack["gate"], stack["up"], stack["down"], w.T))
    return out


# --- the model ---------------------------------------------------------------


@partial(jax.jit, static_argnames=("eps", "read"))
def _logits(norm, head, x, rows, *, eps, read):
    return _normed(x[rows], read(norm), eps) @ read(head).T


def hidden_after(params, config: dict, tokens, n_blocks=None,
                 read=as_float32) -> jax.Array:
    """The residual stream [T, E] after the first `n_blocks` published
    layers (both halves); `tokens` one-dimensional."""
    sizes = sizes_of(config)
    d = sizes["head_dim"]
    frequencies = jnp.asarray(
        sizes["theta"] ** (-2.0 * np.arange(d // 2, dtype=np.float64) / d),
        jnp.float32)
    layers, eps = params["layers"], sizes["eps"]
    with jax.default_matmul_precision("highest"):
        x = as_float32(params["embedding"][jnp.asarray(tokens)])
        for l in range(sizes["depth"] if n_blocks is None else n_blocks):
            operator, forward = layers[2 * l], layers[2 * l + 1]
            if sizes["types"][l] == "conv":
                assert operator["conv_w"].shape[0] == sizes["taps"], l
                x = conv_layer(operator, x, taps=sizes["taps"], eps=eps,
                               read=read)
            else:
                assert operator["q_proj"].shape[1:] == (sizes["heads"], d), l
                assert operator["k_proj"].shape[1] == sizes["kv_heads"], l
                x = attention_layer(operator, x, frequencies, eps=eps,
                                    read=read)
            if l < sizes["dense"]:
                x = dense_layer(forward, x, eps=eps, read=read)
            else:
                assert forward["router"].shape[1] == sizes["experts"], l
                assert "shared" not in forward, l
                x = experts_layer(forward, x, top_k=sizes["top_k"],
                                  scale=sizes["scale"], eps=eps, read=read)
        return x


def logits_at(params, config: dict, tokens, rows,
              read=as_float32) -> jax.Array:
    """Float32 logits [len(rows), vocab] over the whole sequence
    `tokens` (one-dimensional, padded as the caller likes to a multiple
    of 512: every layer is causal, so what follows a row never reaches
    it)."""
    x = hidden_after(params, config, tokens, read=read)
    with jax.default_matmul_precision("highest"):
        return _logits(params["final_norm"], params["embedding"], x,
                       jnp.asarray(rows), eps=sizes_of(config)["eps"],
                       read=read)
