"""The plain reference of a `mellum` decoder (Mellum2-12B-A2.5B): every
published layer is pre-norm and residual,

    x' = x + W_o . Attn_l(RMSNorm(x));   x'' = x' + MoE(RMSNorm(x'))

with `rms_norm_eps` 1e-6, no biases, a final RMSNorm and an untied head.
The engine's parameter tree holds each half as a layer of its own (one
mixer behind one norm), so `params["layers"][2 l]` is layer l's
attention and `[2 l + 1]` its experts.

    Attn_l   h = RMSNorm(x); H query heads over K kv heads of D (32 over
             4 of 128: query head i reads kv head i // (H / K)), the
             same in every layer; scores q_i . k_j * D^-0.5, causal.
             `layer_types[l]` "sliding_attention": key j is visible to
             query i iff 0 <= i - j < sliding_window; rotary plain,
             frequencies theta^(-2 m / D) over all D dimensions.
             "full_attention": unbounded; rotary YaRN — each frequency
             f_m blended with f_m / factor by a linear ramp over m
             between the pair indices whose wavelengths fit the
             original context beta_fast and beta_slow times — and cos
             and sin times the GIVEN `attention_factor`.
             Dimension m pairs with m + D / 2.
    MoE      p = softmax(h W_r) over ALL `num_experts` in float32; the
             `num_experts_per_tok` largest; `norm_topk_prob: true`:
             w_e = p_e / sum of the chosen p; no scale, no bias, no
             groups, NO shared expert;
             y = sum_e w_e W_down^e (silu(W_gate^e h) * W_up^e h).

Departures from the published description, each one key of the
configuration's `assumed`: no q/k norm (no key declares one); the
router's rule (the config names no scoring function: `norm_topk_prob`
beside no scaling factor is the Mixtral / Qwen-MoE convention, softmax
first, then top-k, then renormalise); the "MTP head" the description
mentions has no key in the config and is LEFT OUT; `intermediate_size`
is unused (no layer is `dense`, and one that says so is an error here);
rotary pairs (m, m + D/2) where modelling code may interleave — with
seeded random weights a permutation of W_Q / W_K columns.

Plain `jax.numpy` in float32 under `default_matmul_precision("highest")`:
a dense mask, a head at a time, an expert at a time, no cache, no
kernels, no batch. The QUERY rows of a layer go through attention in
blocks of QUERY_BLOCK, so that one head's scores are [512, T] and 6.5 k
positions at the published widths fit beside the engine this checks.
It is fed the engine's own parameter tree and reads it a leaf (an
expert) at a time through `read` (float32; a control may round a matrix
on the way). It shares no code with the program.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512
LAYER_TYPES = ("sliding_attention", "full_attention")


def as_float32(leaf):
    return jnp.asarray(leaf, jnp.float32)


def _normed(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * weight


def sizes_of(config: dict) -> dict:
    """What the equations need, from the published keys alone."""
    depth = int(config["num_hidden_layers"])
    types, mlps = list(config["layer_types"]), list(
        config["mlp_layer_types"])
    if len(types) != depth or len(mlps) != depth:
        raise ValueError(f"layer_types ({len(types)}) and mlp_layer_types "
                         f"({len(mlps)}) must have num_hidden_layers "
                         f"({depth}) entries")
    if set(types) - set(LAYER_TYPES) or set(mlps) != {"sparse"}:
        raise ValueError("this reference knows sliding_attention / "
                         "full_attention layers, every one sparse")
    if config.get("norm_topk_prob") is not True:
        raise ValueError("this reference renormalises the chosen "
                         "probabilities (norm_topk_prob: true)")
    return {"depth": depth, "types": types,
            "eps": float(config["rms_norm_eps"]),
            "heads": int(config["num_attention_heads"]),
            "kv_heads": int(config["num_key_value_heads"]),
            "head_dim": int(config["head_dim"]),
            "window": int(config["sliding_window"]),
            "rope": config["rope_parameters"],
            "experts": int(config["num_experts"]),
            "top_k": int(config["num_experts_per_tok"])}


# --- rotary tables -----------------------------------------------------------


def rotary_frequencies(entry: dict, head_dim: int):
    """One layer type's entry of `rope_parameters` -> (float64 angular
    frequencies [head_dim / 2], the multiplier on cos and sin)."""
    theta = float(entry["rope_theta"])
    m = np.arange(head_dim // 2, dtype=np.float64)
    plain = theta ** (-2.0 * m / head_dim)
    kind = entry.get("rope_type", "default")
    if kind == "default":
        return plain, 1.0
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r}: default or yarn")
    factor = float(entry["factor"])
    context = float(entry["original_max_position_embeddings"])

    def pair_that_turns(times: float) -> float:
        """The (fractional) pair index whose wavelength goes into the
        original context `times` times."""
        return (head_dim * math.log(context / (times * 2.0 * math.pi))
                / (2.0 * math.log(theta)))

    first = max(math.floor(pair_that_turns(float(entry["beta_fast"]))), 0)
    last = min(math.ceil(pair_that_turns(float(entry["beta_slow"]))),
               head_dim - 1)
    if first == last:
        last += 0.001
    stretched = np.clip((m - first) / (last - first), 0.0, 1.0)
    blended = plain * (1.0 - stretched) + plain / factor * stretched
    given = entry.get("attention_factor")
    return blended, (0.1 * math.log(factor) + 1.0 if given is None
                     else float(given))


def _turn(x, positions, frequencies, multiplier):
    """x [..., T, D] at `positions` [T]: dimension m with m + D/2."""
    angle = positions.astype(jnp.float32)[:, None] * frequencies[None, :]
    cos, sin = jnp.cos(angle) * multiplier, jnp.sin(angle) * multiplier
    half = frequencies.shape[0]
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


# --- attention ---------------------------------------------------------------


@partial(jax.jit, static_argnames=("eps", "multiplier", "read"))
def _keys_values(layer, x, frequencies, *, eps, multiplier, read):
    h = _normed(x, read(layer["norm"]), eps)
    k = jnp.einsum("te,ekd->ktd", h, read(layer["k_proj"]))
    v = jnp.einsum("te,ekd->ktd", h, read(layer["v_proj"]))
    return _turn(k, jnp.arange(x.shape[0]), frequencies, multiplier), v


@partial(jax.jit, static_argnames=("block", "window", "eps", "multiplier",
                                   "read"))
def _attend_block(layer, x, k, v, first_row, frequencies, *, block, window,
                  eps, multiplier, read):
    """Rows first_row .. first_row + block of x + W_o Attn(norm x):
    every head against every key under a dense mask."""
    rows = first_row + jnp.arange(block)
    xb = jax.lax.dynamic_slice_in_dim(x, first_row, block, 0)
    h = _normed(xb, read(layer["norm"]), eps)
    w_q, w_o = read(layer["q_proj"]), read(layer["o_proj"])
    heads, d = w_q.shape[1], w_q.shape[2]
    group = heads // k.shape[0]
    keys = jnp.arange(k.shape[1])
    distance = rows[:, None] - keys[None, :]
    visible = distance >= 0
    if window is not None:
        visible &= distance < window

    def head(i):
        q = _turn(h @ w_q[:, i, :], rows, frequencies, multiplier)
        scores = q @ k[i // group].T * d ** -0.5
        p = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
        return (p @ v[i // group]) @ w_o[i]                # [B, E]

    return xb + jnp.sum(jax.lax.map(head, jnp.arange(heads)), axis=0)


def attention_layer(layer, x, frequencies, *, window, **how):
    t = x.shape[0]
    block = math.gcd(t, QUERY_BLOCK)       # 512 for the harness's padding
    k, v = _keys_values(layer, x, frequencies, **how)
    return jnp.concatenate([
        _attend_block(layer, x, k, v, jnp.int32(r), frequencies,
                      block=block, window=window, **how)
        for r in range(0, t, block)], axis=0)


# --- experts -----------------------------------------------------------------


def router_weights(h, router, top_k: int):
    """[T, E] x [E, X] -> dense weights [T, X]: softmax over all X, the
    top_k largest kept and renormalised to sum to one, the rest 0."""
    p = jax.nn.softmax(h @ router, axis=-1)
    ranked = jnp.argsort(-p, axis=-1)                      # stable
    rank = jnp.argsort(ranked, axis=-1)
    kept = jnp.where(rank < top_k, p, 0.0)
    return kept / jnp.sum(kept, axis=-1, keepdims=True)


@partial(jax.jit, static_argnames=("top_k", "eps", "read"))
def experts_layer(layer, x, *, top_k, eps, read):
    """x [T, E] -> x + sum_e w_e Expert_e(norm x): the experts one at a
    time, each over every token with its weight (0: not chosen)."""
    h = _normed(x, read(layer["norm"]), eps)
    w = router_weights(h, as_float32(layer["router"]), top_k)

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
    tables = {kind: rotary_frequencies(sizes["rope"][kind],
                                       sizes["head_dim"])
              for kind in set(sizes["types"])}
    layers = params["layers"]
    with jax.default_matmul_precision("highest"):
        x = as_float32(params["embedding"][jnp.asarray(tokens)])
        for l in range(sizes["depth"] if n_blocks is None else n_blocks):
            kind = sizes["types"][l]
            frequencies, multiplier = tables[kind]
            assert layers[2 * l]["q_proj"].shape[1:] == (
                sizes["heads"], sizes["head_dim"]), l
            assert "shared" not in layers[2 * l + 1], l
            x = attention_layer(
                layers[2 * l], x, jnp.asarray(frequencies, jnp.float32),
                window=(sizes["window"] if kind == "sliding_attention"
                        else None),
                eps=sizes["eps"], multiplier=multiplier, read=read)
            x = experts_layer(layers[2 * l + 1], x, top_k=sizes["top_k"],
                              eps=sizes["eps"], read=read)
        return x


def logits_at(params, config: dict, tokens, rows,
              read=as_float32) -> jax.Array:
    """Float32 logits [len(rows), vocab] over the whole sequence
    `tokens` (one-dimensional, padded as the caller likes to a multiple
    of 512: every layer is causal, so what follows a row never reaches
    it)."""
    x = hidden_after(params, config, tokens, read=read)
    with jax.default_matmul_precision("highest"):
        return _logits(params["final_norm"], params["lm_head"], x,
                       jnp.asarray(rows), eps=sizes_of(config)["eps"],
                       read=read)
