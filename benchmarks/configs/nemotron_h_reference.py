"""The plain reference of a `nemotron_h` decoder (NVIDIA-Nemotron-3-Nano):
every layer is ONE mixer behind one RMSNorm and a residual —

    M  Mamba-2:   [z | xBC | dt] = h W_in; xBC <- silu(causal depthwise
                  conv, kernel K, + bias); x, B, C = split(xBC); head h
                  reads group h // (heads / groups);
                  dt <- softplus(dt + dt_bias); A = -exp(A_log);
                  S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t
                  y_t = S_t C_t + D x_t
                  y <- y * silu(z), RMS-normalised over each group of
                  d_in / groups channels, times its weight; out = y W_out
    E  experts:   s = sigmoid(h W_r) over ALL published experts; the
                  top k of s + bias; w = s[chosen] / (sum + 1e-20) *
                  scale; out = sum over the chosen experts HELD HERE of
                  w_e W2_e relu(W1_e h)^2, + W2_s relu(W1_s h)^2
    *  attention: grouped-query, causal, NO position embedding

— then a final RMSNorm and an untied head.

Straightforward `jax.numpy` in float32 under
`default_matmul_precision("highest")`: the sequential recurrence
(`lax.scan` over positions, where the program runs a chunked form), an
explicit loop over the held experts, no kernels, no cache, no batch. It
is fed the engine's own parameter tree and casts it to float32 a layer
(an expert) at a time, so that it fits beside the engine it checks. It
shares no code with the program: the equations are written out.

What the absent experts of the deployment would add is left out here as
in the program (the configuration's `ep_size` / `ep_rank` say which are
held); `uncut=True` gives the layer with every expert, for the test
that adds the shares up.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

LETTERS = {"M": "mamba2", "E": "experts", "*": "attention"}


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def sizes_of(config: dict) -> dict:
    """The numbers the equations need, from the published keys."""
    held = int(config["n_routed_experts"])
    ep_size = int(config.get("ep_size", 1))
    return {
        "pattern": config["hybrid_override_pattern"],
        "eps": float(config["norm_eps"]),
        "heads": int(config["mamba_num_heads"]),
        "head_dim": int(config["mamba_head_dim"]),
        "state": int(config["ssm_state_size"]),
        "groups": int(config["n_groups"]),
        "kernel": int(config["conv_kernel"]),
        "top_k": int(config["num_experts_per_tok"]),
        "scale": float(config["routed_scaling_factor"]),
        "held": held, "published": held * ep_size,
        "offset": held * int(config.get("ep_rank", 0)),
    }


# --- M ---------------------------------------------------------------------


@partial(jax.jit, static_argnames=("heads", "head_dim", "state", "groups",
                                   "kernel", "eps"))
def mamba2_layer(layer, x, *, heads, head_dim, state, groups, kernel, eps):
    """x [T, E] -> x + mixer(norm(x)), position by position."""
    w = {k: _f32(v) for k, v in layer.items()}
    t = x.shape[0]
    d_in = heads * head_dim
    gn = groups * state
    h = _rms_norm(x, w["norm"], eps)
    zxbcdt = h @ w["in_proj"]
    z = zxbcdt[:, :d_in]
    xbc = zxbcdt[:, d_in:d_in + d_in + 2 * gn]
    dt = jax.nn.softplus(zxbcdt[:, d_in + d_in + 2 * gn:] + w["dt_bias"])
    # Causal depthwise conv: tap k reads the input kernel-1-k back.
    padded = jnp.concatenate(
        [jnp.zeros((kernel - 1, xbc.shape[1]), jnp.float32), xbc])
    conv = w["conv_b"]
    for k in range(kernel):
        conv = conv + w["conv_w"][k] * padded[k:k + t]
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :d_in].reshape(t, heads, head_dim)
    bs = xbc[:, d_in:d_in + gn].reshape(t, groups, state)
    cs = xbc[:, d_in + gn:].reshape(t, groups, state)
    per = heads // groups
    a = -jnp.exp(w["A_log"])                              # [H]

    def step(s, inp):
        x_t, b_t, c_t, dt_t = inp       # [H,P] [G,N] [G,N] [H]
        b_h = jnp.repeat(b_t, per, axis=0)                # [H,N]
        c_h = jnp.repeat(c_t, per, axis=0)
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :])
        y = jnp.einsum("hpn,hn->hp", s, c_h) + w["D"][:, None] * x_t
        return s, y

    _, ys = jax.lax.scan(
        step, jnp.zeros((heads, head_dim, state), jnp.float32),
        (xs, bs, cs, dt))
    y = ys.reshape(t, d_in) * jax.nn.silu(z)
    yg = y.reshape(t, groups, d_in // groups)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True) + eps)
    y = yg.reshape(t, d_in) * w["gate_norm"]
    return x + y @ w["out_proj"]


# --- E ---------------------------------------------------------------------


@partial(jax.jit, static_argnames=("top_k", "scale", "eps"))
def _route(norm, router, bias, x, *, top_k, scale, eps):
    h = _rms_norm(x, _f32(norm), eps)
    s = jax.nn.sigmoid(h @ _f32(router))                  # [T, X]
    order = jnp.argsort(-(s + _f32(bias)), axis=-1)[:, :top_k]
    chosen = jnp.take_along_axis(s, order, axis=-1)
    w = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20) * scale
    return h, order, w


@jax.jit
def _expert(up, down, h, weight):
    """weight [T]: this expert's share of each token (0: not chosen)."""
    a = jnp.square(jax.nn.relu(h @ _f32(up)))
    return (a @ _f32(down)) * weight[:, None]


def experts_layer(layer, x, sizes: dict, expert_ids=None):
    """x [T, E] -> x + experts(norm(x)) for the experts whose published
    ids are `expert_ids` (default: the ones held here, whose weights
    are rows 0.. of the layer's stacks), and the shared expert."""
    h, order, w = _route(layer["norm"], layer["router"],
                         layer["router_bias"], x, top_k=sizes["top_k"],
                         scale=sizes["scale"], eps=sizes["eps"])
    if expert_ids is None:
        expert_ids = range(sizes["offset"], sizes["offset"] + sizes["held"])
    out = _expert(layer["shared"]["up"], layer["shared"]["down"], h,
                  jnp.ones((x.shape[0],), jnp.float32))
    for row, e in enumerate(expert_ids):
        weight = jnp.sum(jnp.where(order == e, w, 0.0), axis=-1)
        out = out + _expert(layer["experts"]["up"][row],
                            layer["experts"]["down"][row], h, weight)
    return x + out


# --- * ---------------------------------------------------------------------


@partial(jax.jit, static_argnames=("eps",))
def attention_layer(layer, x, *, eps):
    w = {k: _f32(v) for k, v in layer.items()}
    h = _rms_norm(x, w["norm"], eps)
    q = jnp.einsum("te,ehd->thd", h, w["q_proj"])
    k = jnp.einsum("te,ekd->tkd", h, w["k_proj"])
    v = jnp.einsum("te,ekd->tkd", h, w["v_proj"])
    t, heads, d = q.shape
    kv_heads = k.shape[1]
    per = heads // kv_heads       # query heads h*per..+per-1 share kv head h
    pos = jnp.arange(t)
    seen = pos[None, :] <= pos[:, None]

    def one_kv_head(args):
        qh, kh, vh = args             # [T, per, D], [T, D], [T, D]
        scores = jnp.einsum("tgd,sd->gts", qh, kh) * d ** -0.5
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("gts,sd->tgd", probs, vh)

    attn = jax.lax.map(one_kv_head, (
        q.reshape(t, kv_heads, per, d).transpose(1, 0, 2, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    attn = attn.transpose(1, 0, 2, 3).reshape(t, heads, d)
    return x + jnp.einsum("thd,hde->te", attn, w["o_proj"])


# --- the model -------------------------------------------------------------


@partial(jax.jit, static_argnames=("eps",))
def _head(norm, head, x, rows, *, eps):
    return _rms_norm(x[rows], _f32(norm), eps) @ _f32(head).T


def hidden_after(params, config: dict, tokens, n_layers=None) -> jax.Array:
    """The residual stream [T, E] after the first `n_layers` layers."""
    sizes = sizes_of(config)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embedding"][jnp.asarray(tokens)])
        for letter, layer in list(zip(sizes["pattern"],
                                      params["layers"]))[:n_layers]:
            kind = LETTERS[letter]
            if kind == "mamba2":
                x = mamba2_layer(
                    layer, x, heads=sizes["heads"],
                    head_dim=sizes["head_dim"], state=sizes["state"],
                    groups=sizes["groups"], kernel=sizes["kernel"],
                    eps=sizes["eps"])
            elif kind == "experts":
                x = experts_layer(layer, x, sizes)
            else:
                x = attention_layer(layer, x, eps=sizes["eps"])
        return x


def logits_at(params, config: dict, tokens, rows) -> jax.Array:
    """Float32 logits [len(rows), vocab] over the whole sequence
    `tokens` (one-dimensional, padded as the caller likes: every layer
    is causal, so what follows a row never reaches it)."""
    x = hidden_after(params, config, tokens)
    with jax.default_matmul_precision("highest"):
        return _head(params["final_norm"], params["lm_head"], x,
                     jnp.asarray(rows), eps=sizes_of(config)["eps"])
