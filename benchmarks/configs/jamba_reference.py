"""The plain reference of a `jamba` decoder (AI21-Jamba2-3B), written from
the layer equations. Every published layer i is pre-norm and residual,

    x' = x + Mixer_i(RMSNorm(x));   x'' = x' + MLP(RMSNorm(x'))

with `rms_norm_eps` 1e-6, a final RMSNorm and logits on the embedding
(tied). Mixer_i is attention where i % attn_layer_period ==
attn_layer_offset, else a Mamba-1 mixer; `num_experts` is 1, so every
feed-forward is one dense SwiGLU.

    Mamba  h = RMSNorm(x); d = mamba_expand * hidden, N = mamba_d_state,
           R = mamba_dt_rank, K = mamba_d_conv:
           [u_t, z_t]       = W_in h_t
           c_t              = silu(conv_b + sum_{j<K} conv_w[j] u_{t-K+1+j})
           [dl_t, B_t, C_t] = W_x c_t, EACH through an RMSNorm of its own
           dt_t             = softplus(W_dt dl_t + b_dt)          in R^d
           S_t[d, n]        = exp(dt_t[d] A[d, n]) S_{t-1}[d, n]
                              + dt_t[d] c_t[d] B_t[n]       A = -exp(A_log)
           y_t              = S_t C_t + D c_t
           out_t            = W_out (y_t silu(z_t))
    Attn   20 query heads of 128 over ONE kv head, no bias, causal
           softmax at 1 / sqrt(128), no window, NO position embedding.
    MLP    W_down (silu(W_gate h) * W_up h).

Assumed, because the config does not say (the configuration file lists
each under `assumed`): head_dim = hidden / heads; no rotary embedding
(the config has no rotary key and the `jamba` modelling code applies
none: the Mamba layers carry order).

Plain `jax.numpy` in float32 under `default_matmul_precision("highest")`:
THE RECURRENCE A TOKEN AT A TIME (`lax.scan` over positions, the state
[d, N]), the convolution as a sum of K shifted rows, attention as a full
causal softmax a head at a time with the one kv head used by every
query head; no chunks, no kernels, no cache, no batch, no scan over
layers. It is fed the engine's own parameter tree, in which a run of
consecutive (Mamba, MLP) layers is ONE entry of `params["layers"]` whose
leaves carry a leading layer axis (`{"mamba1": ..., "mlp": ...}`) and an
attention layer and the MLP behind it are two entries; it walks that
tree by the published pattern and indexes a run's leaves by layer. Every
leaf goes through `read` (float32; a control may round a matrix on the
way); `norms=False` leaves the three small norms out (a control). It
shares no code with the program.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def as_float32(leaf):
    return jnp.asarray(leaf, jnp.float32)


def _normed(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * weight


def sizes_of(config: dict) -> dict:
    """What the equations need, from the published keys alone."""
    if int(config["num_experts"]) != 1 or config.get("sliding_window"):
        raise ValueError("this reference knows one dense feed-forward a "
                         "layer and no window")
    hidden = int(config["hidden_size"])
    return {"depth": int(config["num_hidden_layers"]),
            "period": int(config["attn_layer_period"]),
            "offset": int(config["attn_layer_offset"]),
            "eps": float(config["rms_norm_eps"]),
            "heads": int(config["num_attention_heads"]),
            "kv_heads": int(config["num_key_value_heads"]),
            "d_inner": int(config["mamba_expand"]) * hidden,
            "d_state": int(config["mamba_d_state"]),
            "d_conv": int(config["mamba_d_conv"]),
            "dt_rank": int(config["mamba_dt_rank"])}


def published_layers(params, sizes: dict):
    """(is_attention, mixer leaves, MLP leaves) of every published layer,
    from the engine's tree: a run's leaves indexed by its layer."""
    entries, at, inside = params["layers"], 0, 0
    for i in range(sizes["depth"]):
        if i % sizes["period"] == sizes["offset"]:
            assert inside == 0, i
            yield True, entries[at], entries[at + 1]
            at += 2
            continue
        run = entries[at]
        yield False, *(jax.tree_util.tree_map(lambda a, j=inside: a[j],
                                              run[kind])
                       for kind in ("mamba1", "mlp"))
        inside += 1
        if inside == run["mamba1"]["in_proj"].shape[0]:
            at, inside = at + 1, 0


# --- the layers --------------------------------------------------------------


@partial(jax.jit, static_argnames=("eps", "norms", "read", "sizes"))
def mamba_layer(layer, x, *, eps, norms, read, sizes):
    d, n, k, r = sizes
    t = x.shape[0]
    h = _normed(x, read(layer["norm"]), eps)
    uz = h @ read(layer["in_proj"])
    u, z = uz[:, :d], uz[:, d:]
    # The causal depthwise convolution: K shifted rows, summed.
    w = read(layer["conv_w"])                              # [K, d]
    padded = jnp.concatenate([jnp.zeros((k - 1, d), jnp.float32), u], 0)
    c = jax.nn.silu(read(layer["conv_b"])
                    + sum(w[j] * padded[j:j + t] for j in range(k)))
    xp = c @ read(layer["x_proj"])
    dl, b, cm = xp[:, :r], xp[:, r:r + n], xp[:, r + n:]
    if norms:
        dl = _normed(dl, read(layer["dt_norm"]), eps)
        b = _normed(b, read(layer["b_norm"]), eps)
        cm = _normed(cm, read(layer["c_norm"]), eps)
    dt = jax.nn.softplus(dl @ read(layer["dt_proj"])
                         + read(layer["dt_bias"]))         # [T, d]
    a = -jnp.exp(read(layer["A_log"])).reshape(n, d).T     # [d, N]

    def token(s, ts):
        dt_t, c_t, b_t, c_state = ts
        s = jnp.exp(dt_t[:, None] * a) * s \
            + (dt_t * c_t)[:, None] * b_t[None, :]
        return s, s @ c_state

    _, y = jax.lax.scan(token, jnp.zeros((d, n), jnp.float32),
                        (dt, c, b, cm))
    y = (y + read(layer["D"]) * c) * jax.nn.silu(z)
    return x + y @ read(layer["out_proj"])


@partial(jax.jit, static_argnames=("eps", "read"))
def attention_layer(layer, x, *, eps, read):
    t = x.shape[0]
    h = _normed(x, read(layer["norm"]), eps)
    w_q, w_o = read(layer["q_proj"]), read(layer["o_proj"])   # [E,H,D] [H,D,E]
    w_k, w_v = read(layer["k_proj"]), read(layer["v_proj"])   # [E,K,D]
    heads, d = w_q.shape[1], w_q.shape[2]
    group = heads // w_k.shape[1]
    k = jnp.einsum("te,ekd->ktd", h, w_k)
    v = jnp.einsum("te,ekd->ktd", h, w_v)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]

    def head(i):
        m = i // group                  # the kv head every query head of
        q = h @ w_q[:, i, :]            # its group reads
        s = jnp.where(causal, q @ k[m].T * d ** -0.5, -jnp.inf)
        return (jax.nn.softmax(s, axis=-1) @ v[m]) @ w_o[i]

    return x + jnp.sum(jax.lax.map(head, jnp.arange(heads)), axis=0)


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
                 read=as_float32, norms=True) -> jax.Array:
    """The residual stream [T, E] after the first `n_blocks` published
    layers (both halves); `tokens` one-dimensional."""
    sizes = sizes_of(config)
    eps = sizes["eps"]
    mamba = (sizes["d_inner"], sizes["d_state"], sizes["d_conv"],
             sizes["dt_rank"])
    with jax.default_matmul_precision("highest"):
        x = as_float32(params["embedding"][jnp.asarray(tokens)])
        for i, (is_attention, mixer, mlp) in enumerate(
                published_layers(params, sizes)):
            if n_blocks is not None and i >= n_blocks:
                break
            if is_attention:
                assert mixer["q_proj"].shape[1] == sizes["heads"], i
                assert mixer["k_proj"].shape[1] == sizes["kv_heads"], i
                x = attention_layer(mixer, x, eps=eps, read=read)
            else:
                x = mamba_layer(mixer, x, eps=eps, norms=norms, read=read,
                                sizes=mamba)
            x = mlp_layer(mlp, x, eps=eps, read=read)
        return x


def logits_at(params, config: dict, tokens, rows, read=as_float32,
              norms=True) -> jax.Array:
    """Float32 logits [len(rows), vocab] over the whole sequence
    `tokens` (one-dimensional, padded as the caller likes: every layer
    is causal, so what follows a row never reaches it)."""
    x = hidden_after(params, config, tokens, read=read, norms=norms)
    with jax.default_matmul_precision("highest"):
        return _logits(params["final_norm"], params["embedding"], x,
                       jnp.asarray(rows), eps=sizes_of(config)["eps"],
                       read=read)
