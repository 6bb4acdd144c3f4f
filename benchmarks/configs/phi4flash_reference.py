"""The plain reference of a `phi4flash` decoder (Phi-4-mini-flash-reasoning:
the SambaY "decoder-hybrid-decoder" of arXiv:2507.06607 with differential
attention, arXiv:2410.05258), written from the layer equations. With L =
`num_hidden_layers` (a multiple of 4) every published layer i = 0 .. L-1 is

    x' = x + Mixer_i(LN(x));   x'' = x' + MLP(LN'(x'))

LN a LayerNorm (mean AND variance, weight and bias) at `layer_norm_eps`,
MLP(h) = W_down (silu(W_gate h) * W_up h) without bias; after layer L-1 a
LayerNorm and logits on the embedding (tied; no scaling of embeddings, no
position embedding anywhere). The mixer, by the model's own depth rule:

    i even, i <= L/2     Mamba-1 WITHOUT inner norms. d = 2 hidden, N 16,
                         R = ceil(hidden / 16), K 4:
                           [u_t, z_t]       = W_in h_t
                           c_t              = silu(conv_b + sum_j conv_w[j]
                                                   u_{t-K+1+j})
                           [dl_t, B_t, C_t] = W_x c_t
                           dt_t             = softplus(W_dt dl_t + b_dt)
                           S_t[d, n]        = exp(dt_t[d] A[d, n]) S_{t-1}
                                              + dt_t[d] c_t[d] B_t[n]
                           m_t              = S_t C_t + D c_t
                           out_t            = W_out (m_t silu(z_t))
                         Layer L/2's m is THE MEMORY the units above read.
    i odd, i < L/2       differential attention over a window W
                         (`sliding_window`: position s is seen from t where
                         t - W < s <= t); i = L/2 + 1: the same, causal and
                         unbounded. q = W_q h + b_q (H heads of D), k, v
                         likewise (K heads of D). Differential head j takes
                         query heads (2j, 2j+1) and kv pair p = j // (H/K) =
                         kv heads (2p, 2p+1):
                           A1  = softmax(q_{2j}   k_{2p}^T   / sqrt(D) + mask)
                           A2  = softmax(q_{2j+1} k_{2p+1}^T / sqrt(D) + mask)
                           V_p = [v_{2p} ; v_{2p+1}]
                           o_j = (1 - l0_i) RMSNorm(A1 V_p - l_i A2 V_p; g)
                           l_i = exp(lq1 . lk1) - exp(lq2 . lk2) + l0_i
                           l0_i = 0.8 - 0.6 exp(-0.3 i)
                           out = W_o [o_0 ... o_{H/2-1}] + b_o
    i even, i >= L/2+2   gated memory unit: out = W_2 (m * silu(W_1 h)),
                         m layer L/2's at the same position.
    i odd, i >= L/2+3    differential CROSS layer: q = W_q h + b_q only; k
                         and v are LAYER L/2+1's, causal and unbounded.

Plain `jax.numpy` in float32 under `default_matmul_precision("highest")`:
the recurrence A TOKEN AT A TIME (`lax.scan` over positions, the state
[d, N]), the convolution as a sum of K shifted rows, two explicit softmaxes
over explicit masks a differential head (a head at a time, summed as they
come, so a 4 k-token sequence holds two [T, T] score matrices and one
[T, E] sum and no more), no cache,
no pages, no seam: EVERY position runs every layer, and the caller reads
the rows it wants. It is fed the engine's own parameter tree — a Mamba layer
and its MLP are one entry of `params["layers"]` with a leading layer axis of
one (`{"mamba1": ..., "mlp": ...}`), every other mixer and every other MLP
an entry each — and walks it by the depth rule above, which it derives from
the published keys alone. l0 comes from the formula, not from the tree.
Every leaf goes through `read` (float32; a control may round a matrix on
the way). It shares no code with the program.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

MAMBA, WINDOW, FULL, MEMORY, CROSS = "mamba", "window", "full", "gmu", "cross"


def as_float32(leaf):
    return jnp.asarray(leaf, jnp.float32)


def sizes_of(config: dict) -> dict:
    """What the equations need, from the published keys; the sizes the
    published file lacks at the family's defaults (the configuration
    file lists each under `assumed`)."""
    if int(config.get("mb_per_layer", 2)) != 2:
        raise ValueError("this reference knows mb_per_layer 2: a Mamba "
                         "layer every second layer")
    hidden = int(config["hidden_size"])
    depth = int(config["num_hidden_layers"])
    if depth % 4:
        raise ValueError("the depth rule needs num_hidden_layers % 4 == 0")
    return {"depth": depth, "eps": float(config["layer_norm_eps"]),
            "heads": int(config["num_attention_heads"]),
            "kv_heads": int(config["num_key_value_heads"]),
            "window": int(config["sliding_window"]),
            "d_inner": int(config.get("mamba_expand", 2)) * hidden,
            "d_state": int(config.get("mamba_d_state", 16)),
            "d_conv": int(config.get("mamba_d_conv", 4)),
            "dt_rank": int(config.get("mamba_dt_rank",
                                      math.ceil(hidden / 16)))}


def mixer_of(i: int, depth: int) -> str:
    """The model's own depth rule."""
    half = depth // 2
    if i % 2 == 0:
        return MAMBA if i <= half else MEMORY
    if i < half:
        return WINDOW
    return FULL if i == half + 1 else CROSS


def lambda_init(i: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def published_layers(params, sizes: dict):
    """(mixer kind, mixer leaves, MLP leaves) of every published layer,
    from the engine's tree."""
    entries, at = params["layers"], 0
    for i in range(sizes["depth"]):
        kind = mixer_of(i, sizes["depth"])
        if kind == MAMBA:
            run = entries[at]
            assert run["mamba1"]["in_proj"].shape[0] == 1, i
            yield kind, *(jax.tree_util.tree_map(lambda a: a[0], run[k])
                          for k in ("mamba1", "mlp"))
            at += 1
        else:
            yield kind, entries[at], entries[at + 1]
            at += 2
    assert at == len(entries), (at, len(entries))


# --- the layers --------------------------------------------------------------


def _layer_norm(x, weight, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * weight + bias


def _normed(layer, x, eps, read):
    return _layer_norm(x, read(layer["norm"]), read(layer["norm_b"]), eps)


@partial(jax.jit, static_argnames=("eps", "read", "sizes"))
def mamba_layer(layer, x, *, eps, read, sizes):
    """-> (x + the mixer's output, m [T, d])."""
    d, n, k, r = sizes
    t = x.shape[0]
    h = _normed(layer, x, eps, read)
    uz = h @ read(layer["in_proj"])
    u, z = uz[:, :d], uz[:, d:]
    # The causal depthwise convolution: K shifted rows, summed.
    w = read(layer["conv_w"])                              # [K, d]
    padded = jnp.concatenate([jnp.zeros((k - 1, d), jnp.float32), u], 0)
    c = jax.nn.silu(read(layer["conv_b"])
                    + sum(w[j] * padded[j:j + t] for j in range(k)))
    xp = c @ read(layer["x_proj"])
    dl, b, cm = xp[:, :r], xp[:, r:r + n], xp[:, r + n:]
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
    m = y + read(layer["D"]) * c
    return x + (m * jax.nn.silu(z)) @ read(layer["out_proj"]), m


def _keys_values(layer, h, read):
    """k, v [K, T, D] of an attention layer that has them."""
    k = jnp.einsum("te,ekd->ktd", h, read(layer["k_proj"])) \
        + read(layer["k_bias"])[:, None, :]
    v = jnp.einsum("te,ekd->ktd", h, read(layer["v_proj"])) \
        + read(layer["v_bias"])[:, None, :]
    return k, v


@partial(jax.jit, static_argnames=("eps", "read", "window", "depth"))
def differential_layer(layer, x, k, v, *, eps, read, window, depth):
    """One differential attention layer over keys and values [K, T, D]
    (its own, or for a cross layer another layer's): a differential
    head at a time, each two softmaxes over the explicit mask."""
    t = x.shape[0]
    h = _normed(layer, x, eps, read)
    w_q, b_q = read(layer["q_proj"]), read(layer["q_bias"])   # [E,H,D] [H,D]
    w_o = read(layer["o_proj"])                               # [H/2,2D,E]
    heads, d = w_q.shape[1], w_q.shape[2]
    per_pair = (heads // 2) // (k.shape[0] // 2)
    q_pos, s_pos = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    mask = s_pos <= q_pos
    if window is not None:
        mask &= s_pos > q_pos - window
    l0 = lambda_init(depth)
    lam = (jnp.exp(jnp.sum(read(layer["lambda_q1"])
                           * read(layer["lambda_k1"])))
           - jnp.exp(jnp.sum(read(layer["lambda_q2"])
                             * read(layer["lambda_k2"]))) + l0)
    g = read(layer["sub_norm"])

    def softmax_of(q, keys):
        return jax.nn.softmax(
            jnp.where(mask, q @ keys.T / math.sqrt(d), -jnp.inf), axis=-1)

    def head(acc, j):
        p = j // per_pair
        q1 = h @ w_q[:, 2 * j, :] + b_q[2 * j]
        q2 = h @ w_q[:, 2 * j + 1, :] + b_q[2 * j + 1]
        v_p = jnp.concatenate([v[2 * p], v[2 * p + 1]], axis=-1)  # [T,2D]
        diff = softmax_of(q1, k[2 * p]) @ v_p \
            - lam * (softmax_of(q2, k[2 * p + 1]) @ v_p)
        o = diff * jax.lax.rsqrt(
            jnp.mean(jnp.square(diff), -1, keepdims=True) + eps) * g
        return acc + ((1.0 - l0) * o) @ w_o[j], None

    out, _ = jax.lax.scan(head, jnp.zeros_like(x), jnp.arange(heads // 2))
    return x + out + read(layer["o_bias"])


@partial(jax.jit, static_argnames=("eps", "read"))
def memory_layer(layer, x, m, *, eps, read):
    h = _normed(layer, x, eps, read)
    return x + (m * jax.nn.silu(h @ read(layer["in_proj"]))) \
        @ read(layer["out_proj"])


@partial(jax.jit, static_argnames=("eps", "read"))
def mlp_layer(layer, x, *, eps, read):
    h = _normed(layer, x, eps, read)
    return x + (jax.nn.silu(h @ read(layer["gate_proj"]))
                * (h @ read(layer["up_proj"]))) @ read(layer["down_proj"])


@partial(jax.jit, static_argnames=("eps", "read"))
def _kv_of(layer, x, *, eps, read):
    return _keys_values(layer, _normed(layer, x, eps, read), read)


# --- the model ---------------------------------------------------------------


@partial(jax.jit, static_argnames=("eps", "read"))
def _logits(norm, bias, head, x, rows, *, eps, read):
    return _layer_norm(x[rows], read(norm), read(bias), eps) @ read(head).T


def hidden_after(params, config: dict, tokens, n_blocks=None,
                 read=as_float32) -> jax.Array:
    """The residual stream [T, E] after the first `n_blocks` published
    layers (both halves); `tokens` one-dimensional."""
    sizes = sizes_of(config)
    eps = sizes["eps"]
    mamba = (sizes["d_inner"], sizes["d_state"], sizes["d_conv"],
             sizes["dt_rank"])
    memory = shared = None
    with jax.default_matmul_precision("highest"):
        x = as_float32(params["embedding"][jnp.asarray(tokens)])
        for i, (kind, mixer, mlp) in enumerate(
                published_layers(params, sizes)):
            if n_blocks is not None and i >= n_blocks:
                break
            if kind == MAMBA:
                x, m = mamba_layer(mixer, x, eps=eps, read=read,
                                   sizes=mamba)
                if i == sizes["depth"] // 2:
                    memory = m
            elif kind == MEMORY:
                x = memory_layer(mixer, x, memory, eps=eps, read=read)
            else:
                assert mixer["q_proj"].shape[1] == sizes["heads"], i
                if kind == CROSS:
                    k, v = shared
                else:
                    assert mixer["k_proj"].shape[1] == sizes["kv_heads"], i
                    k, v = _kv_of(mixer, x, eps=eps, read=read)
                    if kind == FULL:
                        shared = (k, v)
                x = differential_layer(
                    mixer, x, k, v, eps=eps, read=read, depth=i,
                    window=sizes["window"] if kind == WINDOW else None)
            x = mlp_layer(mlp, x, eps=eps, read=read)
        return x


def logits_at(params, config: dict, tokens, rows,
              read=as_float32) -> jax.Array:
    """Float32 logits [len(rows), vocab] over the whole sequence
    `tokens` (one-dimensional, padded as the caller likes: every layer
    is causal, so what follows a row never reaches it)."""
    x = hidden_after(params, config, tokens, read=read)
    with jax.default_matmul_precision("highest"):
        return _logits(params["final_norm"], params["final_norm_b"],
                       params["embedding"], x, jnp.asarray(rows),
                       eps=sizes_of(config)["eps"], read=read)
