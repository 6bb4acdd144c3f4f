"""The plain reference of a `laguna` decoder (Laguna-XS.2): attention
layers whose geometry is their own — full or sliding, 48 or 64 query
heads over 8 kv heads, a rotary table a layer type, a sigmoid gate on
the attention output — then a dense gated MLP (`mlp_layer_types`
"dense") or routed + shared gated experts ("sparse"). Every published
layer is pre-norm and residual,

    x <- x + Attn(norm(x));   x <- x + MLP(norm(x))

and the engine's parameter tree holds each half as a layer of its own
(one mixer behind one norm), so `params["layers"]` is read in pairs.

    Attn     h = norm(x); q_i = h W_Q,i (H_l heads of this layer);
             k_j, v_j = h W_K,j, h W_V,j (K kv heads, the same in every
             layer); q head i reads kv head i // (H_l / K);
             rotary embedding R_l of the layer's TYPE over the first
             `partial_rotary_factor` x head_dim dimensions of q and k,
             dimension j paired with j + half of that part, the rest
             passed through; "default": theta^(-2j/rot); "yarn":
             frequencies blended between theta's own and theta's /
             factor (beta_fast, beta_slow over the original context),
             cos and sin times `attention_factor` (given, else 0.1
             ln(factor) + 1), as `transformers` computes it: the passed-
             through part is not scaled;
             p = softmax(q_i . k_j / sqrt(head_dim)), causal, and on
             sliding layers only the last `sliding_window` positions
             (the position itself among them);
             gate: g = sigmoid(h W_G), one logit a head (`gating` true /
             "per-head": W_G [E, H_l]);
             out = concat_i(g_i * sum p v) W_O
    dense    (silu(h W_gate) * h W_up) W_down
    experts  sc = sigmoid(h W_r) over ALL published experts; the k
             largest, no groups, no bias; w = sc[chosen] / (sum + 1e-20)
             * moe_routed_scaling_factor, applied to the experts' OUTPUT
             (`moe_apply_router_weight_on_input: false`); out = sum over
             the chosen experts of w_e E_e(h), + Shared(h),
             E_e and Shared gated SiLU MLPs

— then a final RMSNorm and an untied head.

Departures from the published description, each one key of the
configuration's `assumed`: the gate's width and input (the config says
`gating: true` and no more: one logit a head on the normed input — the
parameter count decides, and the sibling config writes "per-head"); the
router's rule (the config names none: sigmoid scores, top-k, renormalised
— what a scale of 2.5 beside a shared expert goes with); no q/k norm (no
key declares one); rotary pairs (j, j + half) where the modelling code
may interleave — with seeded random weights a permutation of W_Q / W_K
columns.

Straightforward `jax.numpy` in float32 under
`default_matmul_precision("highest")`: a dense mask, a head at a time,
an expert at a time, no kernels, no cache, no batch. It is fed the
engine's own parameter tree and casts it to float32 a leaf (an expert)
at a time, so that it fits beside the engine it checks. It shares no
code with the program.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def sizes_of(config: dict) -> dict:
    """The numbers the equations need, from the published keys."""
    if config.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError("this reference scores experts with a sigmoid; "
                         f"scoring_func={config['scoring_func']!r}")
    if config["gating"] is not True and config["gating"] != "per-head":
        raise ValueError("this reference gates a head with one logit; "
                         f"gating={config['gating']!r}")
    blocks = int(config["num_hidden_layers"])
    lists = {k: list(config[k]) for k in (
        "layer_types", "mlp_layer_types", "num_attention_heads_per_layer")}
    assert all(len(v) == blocks for v in lists.values()), lists
    return {
        "blocks": blocks, **lists,
        "eps": float(config["rms_norm_eps"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "window": int(config["sliding_window"]),
        "rotary": config["rope_parameters"],
        "top_k": int(config["num_experts_per_tok"]),
        "scale": float(config["moe_routed_scaling_factor"]),
        "held": int(config["num_experts"]),
    }


# --- the rotary tables -------------------------------------------------------


def rotary_table(params: dict, head_dim: int):
    """One layer type's entry of `rope_parameters` -> (angular
    frequencies [rot/2], the multiplier on cos and sin)."""
    rot = int(head_dim * float(params.get("partial_rotary_factor", 1)))
    theta = float(params["rope_theta"])
    plain = [theta ** (-2.0 * j / rot) for j in range(rot // 2)]
    if params.get("rope_type", "default") == "default":
        return np.asarray(plain, np.float32), 1.0
    assert params["rope_type"] == "yarn", params
    factor = float(params["factor"])
    original = float(params["original_max_position_embeddings"])

    def dimension_turning(rotations: float) -> float:
        """The (fractional) pair index whose wavelength fits the
        original context `rotations` times."""
        return (rot * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dimension_turning(float(params["beta_fast"]))), 0)
    high = min(math.ceil(dimension_turning(float(params["beta_slow"]))),
               rot - 1)
    if low == high:
        high += 0.001
    out = []
    for j, f in enumerate(plain):
        ramp = min(max((j - low) / (high - low), 0.0), 1.0)
        out.append(f * (1.0 - ramp) + f / factor * ramp)
    given = params.get("attention_factor")
    multiplier = (float(given) if given is not None
                  else 0.1 * math.log(factor) + 1.0)
    return np.asarray(out, np.float32), multiplier


def _rotate(x, freqs, multiplier):
    """x [T, D] at positions 0..T-1: the first 2 * len(freqs) dimensions
    turn, dimension j paired with j + len(freqs); the rest pass."""
    t, half = x.shape[0], freqs.shape[0]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang) * multiplier, jnp.sin(ang) * multiplier
    a, b, rest = x[:, :half], x[:, half:2 * half], x[:, 2 * half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


# --- attention ---------------------------------------------------------------


@partial(jax.jit, static_argnames=("window", "eps", "multiplier"))
def attention_layer(layer, x, freqs, *, window, eps, multiplier):
    """x [T, E] -> x + Attn(norm(x)): a dense mask, a head at a time.
    `window` None: causal and unbounded."""
    w = {k: _f32(v) for k, v in layer.items()}
    t = x.shape[0]
    h = _rms_norm(x, w["norm"], eps)
    heads, d = w["q_proj"].shape[1], w["q_proj"].shape[2]
    group = heads // w["k_proj"].shape[1]
    q = jnp.einsum("te,ehd->htd", h, w["q_proj"])          # [H,T,D]
    k = jnp.einsum("te,ekd->ktd", h, w["k_proj"])          # [K,T,D]
    v = jnp.einsum("te,ekd->ktd", h, w["v_proj"])
    k = jax.vmap(lambda a: _rotate(a, freqs, multiplier))(k)
    pos = jnp.arange(t)
    seen = pos[None, :] <= pos[:, None]
    if window is not None:
        seen &= pos[None, :] > pos[:, None] - window
    g = jax.nn.sigmoid(h @ w["g_proj"]).T[:, :, None]      # [H,T,1]

    def one_head(args):
        i, q_i, g_i = args
        k_i, v_i = k[i // group], v[i // group]
        scores = _rotate(q_i, freqs, multiplier) @ k_i.T * d ** -0.5
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return g_i * (probs @ v_i)                         # [T,D]

    o = jax.lax.map(one_head, (jnp.arange(heads), q,
                               jnp.broadcast_to(g, (heads,) + g.shape[1:])))
    return x + jnp.einsum("htd,hde->te", o, w["o_proj"])


# --- dense MLP ---------------------------------------------------------------


@partial(jax.jit, static_argnames=("eps",))
def dense_layer(layer, x, *, eps):
    h = _rms_norm(x, _f32(layer["norm"]), eps)
    a = jax.nn.silu(h @ _f32(layer["gate_proj"])) \
        * (h @ _f32(layer["up_proj"]))
    return x + a @ _f32(layer["down_proj"])


# --- experts -----------------------------------------------------------------


def _gated(gate, up, down, h):
    return (jax.nn.silu(h @ _f32(gate)) * (h @ _f32(up))) @ _f32(down)


@partial(jax.jit, static_argnames=("top_k", "scale", "eps"))
def experts_layer(layer, x, *, top_k, scale, eps):
    """x [T, E] -> x + experts(norm(x)): the experts (all of them, in
    the rows of the layer's stacks) one at a time, each over every token
    with its weight (0: not chosen), and the shared expert."""
    h = _rms_norm(x, _f32(layer["norm"]), eps)
    sc = jax.nn.sigmoid(h @ _f32(layer["router"]))         # [T, X]
    order = jnp.argsort(-sc, axis=-1)[:, :top_k]
    chosen = jnp.take_along_axis(sc, order, axis=-1)
    w = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20) * scale
    stack = layer["experts"]
    held = stack["up"].shape[0]

    def one_expert(acc, xs):
        e, gate, up, down = xs
        weight = jnp.sum(jnp.where(order == e, w, 0.0), axis=-1)
        return acc + _gated(gate, up, down, h) * weight[:, None], None

    shared = layer["shared"]
    out, _ = jax.lax.scan(
        one_expert, _gated(shared["gate"], shared["up"], shared["down"], h),
        (jnp.arange(held), stack["gate"], stack["up"],
         stack["down"]))
    return x + out


# --- the model ---------------------------------------------------------------


@partial(jax.jit, static_argnames=("eps",))
def _head(norm, head, x, rows, *, eps):
    return _rms_norm(x[rows], _f32(norm), eps) @ _f32(head).T


def hidden_after(params, config: dict, tokens, n_blocks=None) -> jax.Array:
    """The residual stream [T, E] after the first `n_blocks` published
    layers (attention and MLP halves both)."""
    sizes = sizes_of(config)
    tables = {kind: rotary_table(sizes["rotary"][kind], sizes["head_dim"])
              for kind in ("full_attention", "sliding_attention")}
    layers = params["layers"]
    blocks = sizes["blocks"] if n_blocks is None else n_blocks
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embedding"][jnp.asarray(tokens)])
        for b in range(blocks):
            kind = sizes["layer_types"][b]
            freqs, multiplier = tables[kind]
            attn = layers[2 * b]
            assert attn["q_proj"].shape[1] == \
                sizes["num_attention_heads_per_layer"][b], b
            x = attention_layer(
                attn, x, jnp.asarray(freqs), eps=sizes["eps"],
                window=(sizes["window"] if kind == "sliding_attention"
                        else None),
                multiplier=multiplier)
            if sizes["mlp_layer_types"][b] == "dense":
                x = dense_layer(layers[2 * b + 1], x, eps=sizes["eps"])
            else:
                x = experts_layer(
                    layers[2 * b + 1], x, top_k=sizes["top_k"],
                    scale=sizes["scale"], eps=sizes["eps"])
        return x


def logits_at(params, config: dict, tokens, rows) -> jax.Array:
    """Float32 logits [len(rows), vocab] over the whole sequence
    `tokens` (one-dimensional, padded as the caller likes: every layer
    is causal, so what follows a row never reaches it)."""
    x = hidden_after(params, config, tokens)
    with jax.default_matmul_precision("highest"):
        return _head(params["final_norm"], params["lm_head"], x,
                     jnp.asarray(rows), eps=sizes_of(config)["eps"])
