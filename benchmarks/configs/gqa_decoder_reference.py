"""The plain reference of the grouped-query decoder that both Mistral-7B
and Qwen2.5 are (Llama-style block: RMSNorm, rotary embeddings applied
to split halves, grouped-query causal attention, SwiGLU, untied or tied
head; Qwen2.5 adds a bias on the q/k/v projections).

Straightforward `jax.numpy` in float32 under
`default_matmul_precision("highest")`: no kernels, no cache, no batch.
It is fed the engine's own parameters one layer at a time — int8 leaves
are dequantised here, as q times its per-output-channel scale — so the
served path is compared with the same weights computed the plain way.
It shares no code with the program: the equations are written out.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

# Where an int8 leaf's scale sits: the projections keep their trailing
# (output) axes, the embedding and the head keep their rows.
_ROW_SCALED = ("embedding", "lm_head")


def dequant(key: str, leaf) -> jax.Array:
    """A weight leaf in float32, whatever type it is served in."""
    if isinstance(leaf, dict):
        q, s = leaf["q"].astype(jnp.float32), leaf["s"].astype(jnp.float32)
        if key in _ROW_SCALED:
            return q * s[:, None]
        return q * s          # scale axes are q's trailing axes
    return jnp.asarray(leaf, jnp.float32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [T, heads, D]; rotate the two halves of D by position."""
    t, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d // 2, dtype=jnp.float32) / (d // 2))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


@partial(jax.jit, static_argnames=("theta", "eps", "window"))
def _layer(layer, x, *, theta, eps, window):
    w = {k: dequant(k, v) for k, v in layer.items()}
    h = _rms_norm(x, w["input_norm"], eps)
    q = jnp.einsum("te,ehd->thd", h, w["q_proj"])
    k = jnp.einsum("te,ekd->tkd", h, w["k_proj"])
    v = jnp.einsum("te,ekd->tkd", h, w["v_proj"])
    if "q_bias" in w:
        q, k, v = q + w["q_bias"], k + w["k_bias"], v + w["v_bias"]
    q, k = _rope(q, theta), _rope(k, theta)
    t, heads, d = q.shape
    kv_heads = k.shape[1]
    groups = heads // kv_heads     # query heads h*groups..+groups-1 share kv head h
    pos = jnp.arange(t)
    seen = pos[None, :] <= pos[:, None]
    if window:
        seen &= pos[None, :] > pos[:, None] - window

    def one_kv_head(args):
        qh, kh, vh = args             # [T, G, D], [T, D], [T, D]
        scores = jnp.einsum("tgd,sd->gts", qh, kh) * d ** -0.5
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("gts,sd->tgd", probs, vh)

    # One kv head at a time: the same sums, a [G, T, T] score block at
    # a time instead of [H, T, T], so a long prompt fits beside the
    # engine it is checking.
    attn = jax.lax.map(one_kv_head, (
        q.reshape(t, kv_heads, groups, d).transpose(1, 0, 2, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    attn = attn.transpose(1, 0, 2, 3).reshape(t, heads, d)
    x = x + jnp.einsum("thd,hde->te", attn, w["o_proj"])
    h = _rms_norm(x, w["pre_mlp_norm"], eps)
    gate = jax.nn.silu(h @ w["gate_proj"]) * (h @ w["up_proj"])
    return x + gate @ w["down_proj"]


@partial(jax.jit, static_argnames=("eps",))
def _head(norm, head, x, rows, *, eps):
    x = _rms_norm(x[rows], jnp.asarray(norm, jnp.float32), eps)
    return x @ head.T


def logits_at(params, sizes: dict, tokens, rows) -> jax.Array:
    """Float32 logits [len(rows), vocab] of the decoder over the whole
    sequence `tokens` (one-dimensional, padded as the caller likes: the
    mask is causal, so what follows a row never reaches it).

    `sizes`: rope_theta, rms_norm_eps, sliding_window (or None),
    tie_word_embeddings — the published configuration's own keys."""
    with jax.default_matmul_precision("highest"):
        x = dequant("embedding", _take(params["embedding"], tokens))
        for layer in params["layers"]:
            x = _layer(layer, x, theta=float(sizes["rope_theta"]),
                       eps=float(sizes["rms_norm_eps"]),
                       window=sizes.get("sliding_window") or 0)
        head_key = ("embedding" if sizes["tie_word_embeddings"]
                    else "lm_head")
        return _head(params["final_norm"],
                     dequant(head_key, params[head_key]), x,
                     jnp.asarray(rows), eps=float(sizes["rms_norm_eps"]))


def _take(embedding, tokens):
    """The embedding's rows for `tokens`, still in their served type."""
    tokens = jnp.asarray(tokens)
    if isinstance(embedding, dict):
        return {"q": embedding["q"][tokens], "s": embedding["s"][tokens]}
    return embedding[tokens]
