"""The plain reference of an `axk1` decoder (A.X-K1; DeepSeek-V3's block):
multi-head latent attention, then a dense MLP in the first
`first_k_dense_replace` layers and routed + shared experts after. Every
published layer is pre-norm and residual,

    x <- x + MLA(norm(x));   x <- x + MLP(norm(x))

and the engine's parameter tree holds each half as a layer of its own
(one mixer behind one norm), so `params["layers"]` is read in pairs.

    MLA      c_q = RMSNorm(h W_DQ); [q_nope_i ; q_rope_i] = c_q W_UQ,i;
             [c ; k_r] = h W_DKV; c_kv = RMSNorm(c);
             k_rope = R(k_r), q_rope_i <- R(q_rope_i): rotary embedding
             over the qk_rope_head_dim dimensions at YaRN-blended
             frequencies, dimension j paired with j + half;
             [k_nope_i ; v_i] = c_kv W_UKV,i; k_i = [k_nope_i ; k_rope];
             p = softmax(s q_i . k_i), causal;
             s = (nope + rope)^-0.5 m^2, m = 0.1 mscale_all_dim
             ln(factor) + 1; out = concat_i(sum p v_i) W_O
    dense    (silu(h W_gate) * h W_up) W_down
    experts  sc = sigmoid(h W_r) over ALL published experts; the k
             largest (`topk_method: "none"` read literally: no groups, no
             bias); w = sc[chosen] / (sum + 1e-20) * scale; out = sum over
             the chosen experts HELD HERE of w_e E_e(h), + Shared(h),
             E_e and Shared gated SiLU MLPs

— then a final RMSNorm and an untied head. THE PUBLISHED, EXPANDED FORM
of the attention: per-head keys and values are built from c_kv for the
whole sequence, and no cache exists. The program computes the absorbed
form against latent pages; that the two agree is what `correct` tests.

Straightforward `jax.numpy` in float32 under
`default_matmul_precision("highest")`: a head at a time, an expert at a
time, no kernels, no cache, no batch. It is fed the engine's own
parameter tree and casts it to float32 a leaf at a time, so that it fits
beside the engine it checks (at the published widths and 2048 positions
its largest temporaries are one dense-MLP matrix, 0.53 GB, and the
[T, 18432] activations, 0.15 GB). It shares no code with the program.

What the absent experts of the deployment would add is left out here as
in the program (`ep_size` / `ep_rank` say which are held);
`expert_ids` gives `experts_layer` any other set, for the test that adds
the shares up.
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
    held = int(config["n_routed_experts"])
    ep_size = int(config.get("ep_size", 1))
    yarn = config["rope_scaling"]
    return {
        "blocks": int(config["num_hidden_layers"]),
        "dense_blocks": int(config["first_k_dense_replace"]),
        "eps": float(config["rms_norm_eps"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "rank": int(config["kv_lora_rank"]),
        "theta": float(config["rope_theta"]),
        "factor": float(yarn["factor"]),
        "original_max": float(yarn["original_max_position_embeddings"]),
        "beta_fast": float(yarn["beta_fast"]),
        "beta_slow": float(yarn["beta_slow"]),
        "mscale": float(yarn["mscale"]),
        "mscale_all_dim": float(yarn["mscale_all_dim"]),
        "top_k": int(config["num_experts_per_tok"]),
        "scale": float(config["routed_scaling_factor"]),
        "held": held, "published": held * ep_size,
        "offset": held * int(config.get("ep_rank", 0)),
    }


# --- YaRN -------------------------------------------------------------------


def _mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(sizes: dict) -> np.ndarray:
    """[rope/2] angular frequencies. A dimension that turns more than
    beta_fast times over the original context keeps theta's frequency;
    one that turns less than beta_slow times has it divided by `factor`;
    between them a linear ramp over the dimension index."""
    dim, theta = sizes["rope"], sizes["theta"]

    def dimension_turning(rotations: float) -> float:
        return (dim * math.log(sizes["original_max"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dimension_turning(sizes["beta_fast"])), 0)
    high = min(math.ceil(dimension_turning(sizes["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    out = np.zeros((dim // 2,), np.float64)
    for j in range(dim // 2):
        plain = theta ** (-2.0 * j / dim)
        ramp = min(max((j - low) / (high - low), 0.0), 1.0)
        out[j] = plain * (1.0 - ramp) + plain / sizes["factor"] * ramp
    return out.astype(np.float32)


def softmax_scale(sizes: dict) -> float:
    m = _mscale(sizes["factor"], sizes["mscale_all_dim"])
    return (sizes["nope"] + sizes["rope"]) ** -0.5 * m * m


def _rotate(x, freqs, multiplier):
    """x [T, ..., rope] at positions 0..T-1; dimension j pairs with
    j + rope/2."""
    t, half = x.shape[0], x.shape[-1] // 2
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    ang = ang.reshape((t,) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang) * multiplier, jnp.sin(ang) * multiplier
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


# --- MLA --------------------------------------------------------------------


@partial(jax.jit, static_argnames=("nope", "rank", "eps", "scale",
                                   "multiplier"))
def mla_layer(layer, x, freqs, *, nope, rank, eps, scale, multiplier):
    """x [T, E] -> x + MLA(norm(x)), expanded form, a head at a time."""
    w = {k: _f32(v) for k, v in layer.items()}
    t = x.shape[0]
    h = _rms_norm(x, w["norm"], eps)
    c_q = _rms_norm(h @ w["q_a"], w["q_norm"], eps)
    q = jnp.einsum("tr,rhd->htd", c_q, w["q_b"])          # [H,T,nope+rope]
    ckr = h @ w["kv_a"]
    c_kv = _rms_norm(ckr[:, :rank], w["kv_norm"], eps)
    k_rope = _rotate(ckr[:, rank:], freqs, multiplier)    # [T,rope]
    kv = jnp.einsum("tr,rhd->htd", c_kv, w["kv_b"])       # [H,T,nope+v]
    pos = jnp.arange(t)
    seen = pos[None, :] <= pos[:, None]

    def one_head(args):
        q_i, kv_i = args
        q_rope = _rotate(q_i[:, nope:], freqs, multiplier)
        scores = (q_i[:, :nope] @ kv_i[:, :nope].T
                  + q_rope @ k_rope.T) * scale
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return probs @ kv_i[:, nope:]                     # [T,v]

    o = jax.lax.map(one_head, (q, kv))                    # [H,T,v]
    return x + jnp.einsum("htd,hde->te", o, w["o_proj"])


# --- dense MLP --------------------------------------------------------------


@partial(jax.jit, static_argnames=("eps",))
def dense_layer(layer, x, *, eps):
    h = _rms_norm(x, _f32(layer["norm"]), eps)
    a = jax.nn.silu(h @ _f32(layer["gate_proj"])) \
        * (h @ _f32(layer["up_proj"]))
    return x + a @ _f32(layer["down_proj"])


# --- experts ----------------------------------------------------------------


@partial(jax.jit, static_argnames=("top_k", "scale", "eps"))
def _route(norm, router, x, *, top_k, scale, eps):
    h = _rms_norm(x, _f32(norm), eps)
    sc = jax.nn.sigmoid(h @ _f32(router))                 # [T, X]
    order = jnp.argsort(-sc, axis=-1)[:, :top_k]
    chosen = jnp.take_along_axis(sc, order, axis=-1)
    w = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20) * scale
    return h, order, w


@jax.jit
def _expert(gate, up, down, h, weight):
    """weight [T]: this expert's share of each token (0: not chosen)."""
    a = jax.nn.silu(h @ _f32(gate)) * (h @ _f32(up))
    return (a @ _f32(down)) * weight[:, None]


def experts_layer(layer, x, sizes: dict, expert_ids=None):
    """x [T, E] -> x + experts(norm(x)) for the experts whose published
    ids are `expert_ids` (default: the ones held here, whose weights
    are rows 0.. of the layer's stacks), and the shared expert."""
    h, order, w = _route(layer["norm"], layer["router"], x,
                         top_k=sizes["top_k"], scale=sizes["scale"],
                         eps=sizes["eps"])
    if expert_ids is None:
        expert_ids = range(sizes["offset"], sizes["offset"] + sizes["held"])
    shared = layer["shared"]
    out = _expert(shared["gate"], shared["up"], shared["down"], h,
                  jnp.ones((x.shape[0],), jnp.float32))
    stack = layer["experts"]
    for row, e in enumerate(expert_ids):
        weight = jnp.sum(jnp.where(order == e, w, 0.0), axis=-1)
        out = out + _expert(stack["gate"][row], stack["up"][row],
                            stack["down"][row], h, weight)
    return x + out


# --- the model --------------------------------------------------------------


@partial(jax.jit, static_argnames=("eps",))
def _head(norm, head, x, rows, *, eps):
    return _rms_norm(x[rows], _f32(norm), eps) @ _f32(head).T


def hidden_after(params, config: dict, tokens, n_blocks=None) -> jax.Array:
    """The residual stream [T, E] after the first `n_blocks` published
    layers (attention and MLP halves both)."""
    sizes = sizes_of(config)
    freqs = jnp.asarray(yarn_frequencies(sizes))
    multiplier = (_mscale(sizes["factor"], sizes["mscale"])
                  / _mscale(sizes["factor"], sizes["mscale_all_dim"]))
    layers = params["layers"]
    blocks = sizes["blocks"] if n_blocks is None else n_blocks
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embedding"][jnp.asarray(tokens)])
        for b in range(blocks):
            x = mla_layer(layers[2 * b], x, freqs, nope=sizes["nope"],
                          rank=sizes["rank"], eps=sizes["eps"],
                          scale=softmax_scale(sizes),
                          multiplier=multiplier)
            if b < sizes["dense_blocks"]:
                x = dense_layer(layers[2 * b + 1], x, eps=sizes["eps"])
            else:
                x = experts_layer(layers[2 * b + 1], x, sizes)
        return x


def logits_at(params, config: dict, tokens, rows) -> jax.Array:
    """Float32 logits [len(rows), vocab] over the whole sequence
    `tokens` (one-dimensional, padded as the caller likes: every layer
    is causal, so what follows a row never reaches it)."""
    x = hidden_after(params, config, tokens)
    with jax.default_matmul_precision("highest"):
        return _head(params["final_norm"], params["lm_head"], x,
                     jnp.asarray(rows), eps=sizes_of(config)["eps"])
