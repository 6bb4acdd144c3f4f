"""The layers of a hybrid decoder (`ModelConfig.layer_kinds`): a Mamba-2
mixer, a layer of routed and shared experts, and the block wiring in
which every layer is ONE mixer behind one RMSNorm and a residual
(`nemotron_h`). Attention layers reuse `common.project_qkv` (or, with
latent pages, `models/mla.py`) and the paged kernels; a plain MLP layer
reuses `common.mlp`. A pre-norm block of attention and MLP, each behind
its own norm, is two such layers (`axk1`: attention + mlp, then
attention + experts): one wiring for both families. A power-retention
layer (`retention`, models/retention.py: a feature-map state and no keys
or values) is a fourth kind of mixer behind the same norm; a model whose
mixers are all of that kind has no attention layer and so no page pool.
A Mamba-1 layer (`mamba1`, models/mamba1.py: a decay for every (state
index, channel) pair, so a scan kernel and no product form) is a fifth,
a gated short convolution (`shortconv`, models/shortconv.py: three taps
between two elementwise gates, whose whole state is two rows) a sixth.
Two kinds keep NOTHING, so a join runs them on each row's last token
alone (`ModelConfig.last_token_from`; engine/paged_forward.py, "the
seam"): a differential cross layer (`cross`, models/diffattn.py: a
query and an out-projection over the pages of the nearest attention
layer below it) and a gated memory unit (`gmu`: `W_2 (m * silu(W_1 h))`,
`m` the scan output of the last Mamba-1 layer below it, which rides
beside the residual stream). Where `cfg.layer_norm`, the norm ahead of
every mixer is a LayerNorm with weight and bias (`layer_norm_in`).
`layer_kinds` keeps one entry a layer; the layers are stored and run as
`ModelConfig.layer_runs` derives them: consecutive (mamba1, mlp) blocks
are ONE entry of `params["layers"]` whose leaves carry a leading layer
axis and one `lax.scan` in every program, every other layer an entry and
a trace of its own (`layers_unrolled` gives either a layer at a time).

Mamba-2 (state-space duality form). Per head, with state S in
R^{P x N} kept in float32:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t
    y_t = S_t C_t + D x_t

Three programs compute it, all from `_ssd_chunk` (one chunk's products
on the MXU, one state hand-over a chunk):

- `mamba2_prefill`: [B, T] rows, chunk `cfg.mamba_chunk` (128), each
  row from its own state; also the state after `cap_len` tokens (a
  snapshot at a page boundary costs one more small product).
- `mamba2_ragged`: the scheduler's flat token buffer. Its blocks of
  RAGGED_BLOCK_Q rows belong to one sequence each, so the chunk is the
  block: the scan reads each block's sequence state from the slot
  array, advances it, and writes it back — a run restarts from its
  slot's state at every sequence boundary by construction.
- `mamba2_step`: one token a row, the recurrence itself.

A token with dt = 0 is the identity on the state (exp(0) = 1, no
input), which is how pad tokens and finished rows are masked.

Experts (`experts_mlp`): sigmoid scores over ALL published experts,
top-k of score (+ bias, where the router's rule has one), weights
renormalised and scaled; each expert `act(x W_up) W_down` or, gated,
`(act(x W_gate) * x W_up) W_down` (`cfg.expert_act`,
`cfg.expert_gated`: relu squared ungated for `nemotron_h`, gated SiLU
for `axk1`); the chip
computes the part of the result its own experts give
(`expert_offset <= id < offset + experts_held`) and the shared expert.
The routed part multiplies only the rows that chose an expert
(`routed_experts`): the T x top-k assignments are sorted by expert
(another chip's last), each projection is ONE grouped product over the
sorted rows (on the chip the Pallas kernel of `pallas/grouped.py`, which
reads an expert's matrix only for the row tiles that hold its rows;
elsewhere `lax.ragged_dot`), and the weighted rows are summed back to
their tokens. Static shapes, every assignment to a held expert computed,
none dropped or capped; a decode step reads only the experts it hit, and
a join multiplies T x top-k rows, not T x held.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..pallas import grouped
from . import diffattn, mamba1, retention, shortconv
from .common import ModelConfig, Params, _einsum, rms_norm

MAMBA2, EXPERTS, ATTENTION, MLP = "mamba2", "experts", "attention", "mlp"
RETENTION, MAMBA1, SHORTCONV = "retention", mamba1.KIND, shortconv.KIND
CROSS, GMU = diffattn.KIND, "gmu"
# The kinds that keep neither pages nor state: what may lie above
# `ModelConfig.last_token_from`.
STATELESS = (CROSS, GMU, MLP)
# State parts gathered to the batch's rows and scattered back by a step
# program (small), and parts a layer updates in place on the whole slot
# array (models/retention.py: 34 MB a row a layer; models/mamba1.py: one
# leaf a scanned run, [rows, layers, ...]).
ROW_PARTS = ("ssm", "conv", shortconv.PART)
SLOT_PARTS = ("ret", "retn") + mamba1.PARTS
# The chunk of a retention layer where no page size says it (the
# whole-sequence forward): the serving paths chunk by the page.
RETENTION_CHUNK = 128
PATTERN_LETTERS = {"M": MAMBA2, "E": EXPERTS, "*": ATTENTION}


def kinds_of_pattern(pattern: str) -> tuple[str, ...]:
    """`hybrid_override_pattern` -> layer kinds. A letter this module
    has no layer for ('-', a plain MLP layer) fails here."""
    try:
        return tuple(PATTERN_LETTERS[c] for c in pattern)
    except KeyError as e:
        raise ValueError(
            f"hybrid_override_pattern has a layer kind {e.args[0]!r} "
            f"this engine does not run (known: "
            f"{''.join(PATTERN_LETTERS)})") from None


# --- Mamba-2 ---------------------------------------------------------------


def _split_in_proj(zxbcdt: jax.Array, cfg: ModelConfig):
    d_in, conv = cfg.mamba_d_inner, cfg.mamba_conv_dim
    return (zxbcdt[..., :d_in], zxbcdt[..., d_in:d_in + conv],
            zxbcdt[..., d_in + conv:])


def _split_xbc(xbc: jax.Array, cfg: ModelConfig):
    """[..., conv_dim] -> x [..., H, P], B [..., G, N], C [..., G, N]."""
    d_in = cfg.mamba_d_inner
    gn = cfg.ssm_groups * cfg.ssm_state
    lead = xbc.shape[:-1]
    x = xbc[..., :d_in].reshape(*lead, cfg.mamba_heads, cfg.mamba_head_dim)
    b = xbc[..., d_in:d_in + gn].reshape(*lead, cfg.ssm_groups,
                                         cfg.ssm_state)
    c = xbc[..., d_in + gn:].reshape(*lead, cfg.ssm_groups, cfg.ssm_state)
    return x, b, c


def _conv_taps(rows: list, layer: Params) -> jax.Array:
    """silu(sum_k w[k] * rows[k] + b): rows[k] is the input K-1-k tokens
    back (rows[-1] the token itself), float32."""
    return jax.nn.silu(shortconv.taps_sum(
        rows, layer["conv_w"].astype(jnp.float32),       # [K, C]
        layer["conv_b"].astype(jnp.float32)))


def _dt_of(dt_raw: jax.Array, layer: Params) -> jax.Array:
    return jax.nn.softplus(dt_raw.astype(jnp.float32)
                           + layer["dt_bias"].astype(jnp.float32))


def _gated_out(y: jax.Array, z: jax.Array, layer: Params,
               cfg: ModelConfig, dtype) -> jax.Array:
    """y * silu(z), RMS-normalised over each group of d_in / groups
    channels, times the norm's weight, then the out-projection.
    y [..., H, P] float32, z [..., d_in]."""
    lead = y.shape[:-2]
    g = cfg.ssm_groups
    y = y.reshape(*lead, cfg.mamba_d_inner) \
        * jax.nn.silu(z.astype(jnp.float32))
    yg = y.reshape(*lead, g, cfg.mamba_d_inner // g)
    yg = yg * jax.lax.rsqrt(
        jnp.mean(jnp.square(yg), axis=-1, keepdims=True) + cfg.norm_eps)
    y = yg.reshape(*lead, cfg.mamba_d_inner) \
        * layer["gate_norm"].astype(jnp.float32)
    return _einsum("...f,fe->...e", y.astype(dtype),
                   layer["out_proj"]).astype(dtype)


def _ssd_chunk(x, dt, a_neg, bm, cm, d_skip, s_in, cap_idx=None):
    """One chunk of the scan for B rows.

    x [B,Q,H,P], dt [B,Q,H] (0 = identity token), a_neg [H] (< 0),
    bm/cm [B,Q,G,N], d_skip [H], s_in [B,H,P,N]; all float32.
    -> y [B,Q,H,P], s_out [B,H,P,N], and with `cap_idx` [B] (index in
    the chunk of the last token consumed) the state after that token.
    """
    b_, q, h, p = x.shape
    g = bm.shape[2]
    rep = h // g
    la = dt * a_neg                                       # [B,Q,H] <= 0
    cs = jnp.cumsum(la, axis=1)                           # inclusive
    xdt = x * dt[..., None]                               # [B,Q,H,P]
    # Heads as (group, head-in-group): B and C are per group.
    xdt_g = xdt.reshape(b_, q, g, rep, p)
    cs_g = cs.reshape(b_, q, g, rep)
    s_g = s_in.reshape(b_, g, rep, p, -1)
    cb = jnp.einsum("bign,bjgn->bgij", cm, bm,
                    preferred_element_type=jnp.float32)   # [B,G,Q,Q]
    seg = cs_g[:, :, None] - cs_g[:, None, :]             # [B,Qi,Qj,G,R]
    causal = jnp.tril(jnp.ones((q, q), bool))[None, :, :, None, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    m = cb.transpose(0, 2, 3, 1)[..., None] * decay       # [B,Qi,Qj,G,R]
    y = jnp.einsum("bijgr,bjgrp->bigrp", m, xdt_g,
                   preferred_element_type=jnp.float32)
    y = y + jnp.exp(cs_g)[..., None] * jnp.einsum(
        "bign,bgrpn->bigrp", cm, s_g,
        preferred_element_type=jnp.float32)
    y = y.reshape(b_, q, h, p) + d_skip[None, None, :, None] * x

    def state_after(cs_at, keep):
        # cs_at [B,G,R]: cumulative log-decay at the token the state is
        # taken after; keep [B,Q] bool: tokens at or before it.
        w = jnp.where(keep[:, :, None, None],
                      jnp.exp(cs_at[:, None] - cs_g), 0.0)  # [B,Q,G,R]
        new = jnp.einsum("bjgr,bjgrp,bjgn->bgrpn", w, xdt_g, bm,
                         preferred_element_type=jnp.float32)
        return (jnp.exp(cs_at)[..., None, None] * s_g + new) \
            .reshape(s_in.shape)

    s_out = state_after(cs_g[:, -1], jnp.ones((b_, q), bool))
    if cap_idx is None:
        return y, s_out
    idx = jnp.clip(cap_idx, 0, q - 1)
    cs_at = jnp.take_along_axis(
        cs_g, idx[:, None, None, None], axis=1)[:, 0]
    s_cap = state_after(cs_at, jnp.arange(q)[None, :] <= idx[:, None])
    return y, s_out, s_cap


def mamba2_prefill(h: jax.Array, layer: Params, cfg: ModelConfig,
                   ssm0: jax.Array, conv0: jax.Array,
                   lengths: jax.Array,
                   cap_len: Optional[jax.Array] = None):
    """A Mamba-2 mixer over [B, T] rows, each from its own state.

    h [B,T,E] (normed input), ssm0 [B,H,P,N] f32, conv0 [B,K-1,C] f32,
    lengths [B] valid tokens a row. -> (out [B,T,E], ssm [B,H,P,N],
    conv [B,K-1,C]) after `lengths` tokens, and with `cap_len` [B]
    (1..lengths; anything else: garbage the caller drops) also
    (ssm_cap, conv_cap) after `cap_len` tokens."""
    b_, t, _e = h.shape
    k1 = cfg.conv_kernel - 1
    z, xbc, dt_raw = _split_in_proj(
        _einsum("bte,ef->btf", h, layer["in_proj"]), cfg)
    ext = jnp.concatenate([conv0, xbc.astype(jnp.float32)], axis=1)
    xbc = _conv_taps([ext[:, k:k + t] for k in range(cfg.conv_kernel)],
                     layer)
    x, bm, cm = _split_xbc(xbc, cfg)
    valid = jnp.arange(t)[None, :] < lengths[:, None]
    dt = jnp.where(valid[..., None], _dt_of(dt_raw, layer), 0.0)
    a_neg = -jnp.exp(layer["A_log"].astype(jnp.float32))
    d_skip = layer["D"].astype(jnp.float32)

    q = min(cfg.mamba_chunk, t)
    n_c = -(-t // q)
    pad = n_c * q - t

    def chunks(a):
        if pad:
            a = jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        return jnp.moveaxis(a.reshape(b_, n_c, q, *a.shape[2:]), 1, 0)

    want_cap = cap_len is not None
    last = (cap_len if want_cap else lengths) - 1         # token index
    cap_chunk, cap_idx = last // q, last % q

    def body(carry, xs):
        s, s_cap = carry
        ci, xc, dtc, bc, cc = xs
        if want_cap:
            y, s_new, s_at = _ssd_chunk(xc, dtc, a_neg, bc, cc, d_skip,
                                        s, cap_idx)
            hit = (ci == cap_chunk)[:, None, None, None]
            s_cap = jnp.where(hit, s_at, s_cap)
        else:
            y, s_new = _ssd_chunk(xc, dtc, a_neg, bc, cc, d_skip, s)
        return (s_new, s_cap), y

    s_cap0 = ssm0 if want_cap else jnp.zeros((), jnp.float32)
    (ssm, ssm_cap), ys = jax.lax.scan(
        body, (ssm0, s_cap0),
        (jnp.arange(n_c), chunks(x), chunks(dt), chunks(bm), chunks(cm)))
    y = jnp.moveaxis(ys, 0, 1).reshape(b_, n_c * q, *ys.shape[3:])[:, :t]
    out = _gated_out(y, z, layer, cfg, h.dtype)
    conv = shortconv.tail_rows(ext, lengths, k1)
    if not want_cap:
        return out, ssm, conv
    return out, ssm, conv, ssm_cap, shortconv.tail_rows(ext, cap_len, k1)


def mamba2_step(h: jax.Array, layer: Params, cfg: ModelConfig,
                ssm: jax.Array, conv: jax.Array, active: jax.Array):
    """One decode token a row: the recurrence. h [B,1,E]; rows with
    `active` False keep their state (a finished row still rides the
    batch). -> (out [B,1,E], ssm, conv)."""
    z, xbc, dt_raw = _split_in_proj(
        _einsum("bte,ef->btf", h, layer["in_proj"]), cfg)
    cur = xbc[:, 0].astype(jnp.float32)                   # [B,C]
    k1 = cfg.conv_kernel - 1
    xc = _conv_taps([conv[:, k] for k in range(k1)] + [cur], layer)
    x, bm, cm = _split_xbc(xc, cfg)                       # [B,H,P] [B,G,N]
    dt = _dt_of(dt_raw[:, 0], layer)                      # [B,H]
    a_neg = -jnp.exp(layer["A_log"].astype(jnp.float32))
    b_, hh, p = x.shape
    g = bm.shape[1]
    rep = hh // g
    xg = x.reshape(b_, g, rep, p)
    dtg = dt.reshape(b_, g, rep)
    sg = ssm.reshape(b_, g, rep, p, -1)
    new = (jnp.exp(dtg * a_neg.reshape(g, rep))[..., None, None] * sg
           + (dtg[..., None] * xg)[..., None] * bm[:, :, None, None, :])
    y = jnp.einsum("bgrpn,bgn->bgrp", new, cm,
                   preferred_element_type=jnp.float32)
    y = y.reshape(b_, hh, p) \
        + layer["D"].astype(jnp.float32)[None, :, None] * x
    out = _gated_out(y[:, None], z, layer, cfg, h.dtype)
    keep = active[:, None, None, None]
    ssm = jnp.where(keep, new.reshape(ssm.shape), ssm)
    conv_new = jnp.concatenate([conv[:, 1:], cur[:, None]], axis=1)
    conv = jnp.where(active[:, None, None], conv_new, conv)
    return out, ssm, conv


def mamba2_ragged(h: jax.Array, layer: Params, cfg: ModelConfig,
                  ssm_all: jax.Array, conv_all: jax.Array, rg: dict):
    """A Mamba-2 mixer over the flat token buffer.

    h [1,T,E]; ssm_all [R,H,P,N] / conv_all [R,K-1,C]: EVERY slot's
    state (row R-1 is scratch: pads land there). `rg` (built once a
    dispatch by `ragged_meta`): token_seq [T], run_idx [T] (index of
    the token in its run), token_valid [T], seq_slot [S], seq_start
    [S], seq_len [S], block_slot / seq_of_block / block_qstart [T/Q],
    cap_n [S] (snapshot after this many tokens of the run; 0: none).
    -> (out [1,T,E], ssm_all, conv_all, cap_ssm [S,H,P,N], cap_conv
    [S,K-1,C]): every sequence's slot advanced by its run, and the
    state of each at its snapshot point (rows with cap_n 0: garbage)."""
    t = h.shape[1]
    k1 = cfg.conv_kernel - 1
    q = rg["block"]
    z, xbc, dt_raw = _split_in_proj(
        _einsum("bte,ef->btf", h, layer["in_proj"]), cfg)
    raw = xbc[0].astype(jnp.float32)                      # [T,C]
    tok_slot = rg["seq_slot"][rg["token_seq"]]            # [T]
    run_idx = rg["run_idx"]
    rows = []
    for back in range(k1, 0, -1):
        # The input `back` tokens ago: in the buffer while the run
        # reaches that far, else in the slot's tail.
        in_run = run_idx >= back
        prev = raw[jnp.clip(jnp.arange(t) - back, 0, t - 1)]
        tail = conv_all[tok_slot, jnp.clip(k1 + run_idx - back, 0,
                                           k1 - 1)]
        rows.append(jnp.where(in_run[:, None], prev, tail))
    xc = _conv_taps(rows + [raw], layer)
    x, bm, cm = _split_xbc(xc, cfg)                       # [T,H,P] ...
    dt = jnp.where(rg["token_valid"][:, None],
                   _dt_of(dt_raw[0], layer), 0.0)
    a_neg = -jnp.exp(layer["A_log"].astype(jnp.float32))
    d_skip = layer["D"].astype(jnp.float32)
    nb = t // q

    def blocks(a):
        return a.reshape(nb, 1, q, *a.shape[1:])

    # The block holding the last token before a sequence's snapshot
    # point, and that token's index in it (-1: no snapshot here).
    cap_n = rg["cap_n"]                                   # [S]
    seq_b = rg["seq_of_block"]
    last = cap_n[seq_b] - 1
    cap_idx = jnp.where((cap_n[seq_b] > 0)
                        & (rg["block_qstart"] == last // q * q),
                        last % q, -1)                     # [T/Q]

    def body(carry, xs):
        state, caps = carry
        slot, seq, cidx, xc_, dtc, bc, cc = xs
        s_in = jax.lax.dynamic_index_in_dim(state, slot, 0, keepdims=True)
        y, s_out, s_cap = _ssd_chunk(xc_, dtc, a_neg, bc, cc, d_skip, s_in,
                                     jnp.maximum(cidx, 0)[None])
        state = jax.lax.dynamic_update_index_in_dim(state, s_out[0],
                                                    slot, 0)
        caps = jax.lax.cond(
            cidx >= 0,
            lambda c: jax.lax.dynamic_update_index_in_dim(c, s_cap[0],
                                                          seq, 0),
            lambda c: c, caps)
        return (state, caps), y[0]

    caps0 = jnp.zeros((cap_n.shape[0],) + ssm_all.shape[1:], jnp.float32)
    (ssm_all, cap_ssm), ys = jax.lax.scan(
        body, (ssm_all, caps0),
        (rg["block_slot"], seq_b, cap_idx, blocks(x), blocks(dt),
         blocks(bm), blocks(cm)))
    y = ys.reshape(t, *ys.shape[2:])
    out = _gated_out(y[None], z, layer, cfg, h.dtype)

    def tails(n):
        # The last K-1 inputs of [old tail; the run's first n rows].
        j = jnp.arange(k1)[None, :]
        src = n[:, None] - k1 + j                         # index in run
        from_run = raw[jnp.clip(rg["seq_start"][:, None] + src, 0, t - 1)]
        old = conv_all[rg["seq_slot"][:, None],
                       jnp.clip(n[:, None] + j, 0, k1 - 1)]
        return jnp.where((src >= 0)[..., None], from_run, old)

    cap_conv = tails(cap_n)
    conv_all = conv_all.at[rg["seq_slot"]].set(tails(rg["seq_len"]))
    return out, ssm_all, conv_all, cap_ssm, cap_conv


def ragged_meta(positions, token_seq, query_offsets, kv_valid, last_rows,
                seq_of_block, block_qstart, seq_slot, cap_n,
                block: int) -> dict:
    """What the Mamba-2 layers of one ragged dispatch share, from the
    flat buffer's own arrays (serving_loop.build_ragged_batch) and the
    state slot of every sequence (pads: the scratch row)."""
    seq_len = kv_valid - query_offsets                    # tokens a run
    run_idx = positions - query_offsets[token_seq]
    return {
        "block": block, "token_seq": token_seq, "run_idx": run_idx,
        "token_valid": run_idx < seq_len[token_seq],
        "seq_slot": seq_slot, "seq_len": seq_len,
        "seq_start": last_rows - (seq_len - 1),
        "seq_pos0": query_offsets,
        "block_slot": seq_slot[seq_of_block],
        "seq_of_block": seq_of_block, "block_qstart": block_qstart,
        "cap_n": cap_n,
    }


# --- experts ---------------------------------------------------------------


def _relu2(x: jax.Array) -> jax.Array:
    return jnp.square(jax.nn.relu(x))


EXPERT_ACTS = {"relu2": _relu2, "silu": jax.nn.silu}
ROUTER_RULES = ("sigmoid_bias_topk", "sigmoid_topk", "softmax_topk")

# What a step program returns of its expert layers, in this order.
MOE_COUNTS = ("experts_hit", "local_assignments", "expert_layer_steps",
              "rows_multiplied", "rows_dense")


def step_counts(counts: jax.Array, stepped) -> jax.Array:
    """One expert layer's `experts_mlp` counts and whether the layer ran
    a live step (bool or 0/1), in MOE_COUNTS' order."""
    return jnp.concatenate([
        counts[:2], jnp.asarray(stepped, jnp.int32)[None], counts[2:]])


def route(h: jax.Array, layer: Params, cfg: ModelConfig):
    """The router's rule, scores in float32 over ALL published experts.
    DeepSeek-V3's with one group: s = sigmoid(h W_r); choose top-k of
    s + bias ("sigmoid_bias_topk": `nemotron_h`) or of s alone
    ("sigmoid_topk": `axk1` and `laguna`, which declare no bias).
    Mixtral's and Qwen-MoE's ("softmax_topk": `mellum`): s = softmax(h
    W_r) over all of them, top-k of s. Weights s[chosen] / (sum +
    1e-20) * scale (`routed_scaling` 1 where the config has none).
    h [T,E] -> (ids [T,k] int32, weights [T,k] f32)."""
    if cfg.router_rule not in ROUTER_RULES:
        raise ValueError(f"router_rule {cfg.router_rule!r}: known are "
                         f"{', '.join(ROUTER_RULES)}")
    score = (jax.nn.softmax if cfg.router_rule == "softmax_topk"
             else jax.nn.sigmoid)
    s = score(jnp.einsum(
        "te,ex->tx", h.astype(jnp.float32),
        layer["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    pick = s
    if cfg.router_rule == "sigmoid_bias_topk":
        pick = s + layer["router_bias"].astype(jnp.float32)
    _, ids = jax.lax.top_k(pick, cfg.moe_top_k)
    w = jnp.take_along_axis(s, ids, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) \
        * cfg.routed_scaling
    return ids.astype(jnp.int32), w


def _expert(rows: jax.Array, weights: Params, dot, act: str, gated: bool):
    """`act(rows W_up) W_down` or, gated, `(act(rows W_gate) * rows W_up)
    W_down`, each product through `dot(rows, matrix)` -> float32."""
    a = EXPERT_ACTS[act](dot(rows, weights["gate" if gated else "up"]))
    if gated:
        a = a * dot(rows, weights["up"])
    return dot(a.astype(rows.dtype), weights["down"])


@functools.partial(jax.jit, static_argnames=("held", "act", "gated"))
def routed_experts(x: jax.Array, experts: Params, local: jax.Array,
                   w: jax.Array, *, held: int, act: str, gated: bool):
    """What the held experts add. x [T,E]; local [T,k] = chosen id less
    `expert_offset` (outside [0, held): another chip's); w [T,k] f32.
    The T*k assignments are sorted by expert (another chip's last), each
    projection is ONE grouped product over the sorted rows — each row
    times its own expert's matrix — and the weighted rows are summed
    back to their tokens: every assignment to a held expert is computed,
    none dropped or capped, and no expert multiplies a row that did not
    choose it. The product is the Pallas kernel of `pallas/grouped.py`
    on the chip, its visits computed once here for all the projections;
    where that declines, `lax.ragged_dot`. -> ([T,E] f32, rows
    multiplied int32).

    A jit of its own, as `pallas.attention._ragged_walk` is: a model's
    expert layers call it with the same shapes, so the sort, the kernels
    and the sum back are traced once a process and lowered once a
    program, not once a layer."""
    t, k = local.shape
    m, mp = t * k, grouped.padded_rows(t * k)
    here = (local >= 0) & (local < held)
    group = jnp.pad(jnp.where(here, local, held).reshape(m), (0, mp - m),
                    constant_values=held)
    order = jnp.argsort(group)                 # stable: tokens in order
    sizes = jnp.sum(group[:, None] == jnp.arange(held, dtype=group.dtype),
                    axis=0, dtype=jnp.int32)
    rows = x[jnp.minimum(order // k, t - 1)]               # [mp,E]
    if grouped.decline_reason(x.shape[1], experts["up"].shape[2],
                              x.dtype) is None:
        dot = functools.partial(grouped.grouped_matmul,
                                visits=grouped.group_visits(sizes, mp))
    else:
        def dot(rows, weights):
            return jax.lax.ragged_dot(rows, weights, sizes,
                                      preferred_element_type=jnp.float32)
    y = _expert(rows, experts, dot, act, gated)
    multiplied = jnp.sum(sizes)
    # Another chip's rows and the padding: never multiplied, weighted 0
    # by `where` (they are undefined, not zero).
    weight = jnp.pad(w.reshape(m), (0, mp - m))[order]
    y = jnp.where((jnp.arange(mp) < multiplied)[:, None],
                  y * weight[:, None], 0.0)
    back = jnp.zeros((mp,), jnp.int32).at[order].set(
        jnp.arange(mp, dtype=jnp.int32))
    return jnp.sum(y[back[:m]].reshape(t, k, -1), axis=1), multiplied


def experts_mlp(h: jax.Array, layer: Params, cfg: ModelConfig,
                token_mask: Optional[jax.Array] = None):
    """Routed experts held here + the shared expert (a model without
    one, `shared_expert_dim` 0, has no such leaf and no such product).
    h [..., T, E] ->
    (out, counts int32[4]): counts = (held experts some counted token
    chose, assignments of counted tokens to held experts, rows the
    grouped product multiplied, rows a loop over every held expert
    would have: T x held) — what a step must read of the experts, and
    what it multiplied, for the `moe.*` metrics. `token_mask` [..., T]
    says which tokens count (pads and finished rows do not); every
    token is still computed."""
    lead = h.shape[:-1]
    x = h.reshape(-1, h.shape[-1])                        # [T,E]
    t = x.shape[0]
    ids, w = route(x, layer, cfg)
    held = cfg.experts_held
    local = ids - cfg.expert_offset
    here = (local >= 0) & (local < held)
    counted = (jnp.ones((t,), bool) if token_mask is None
               else token_mask.reshape(-1))
    chosen = here & counted[:, None]                      # [T,k]
    hit = jnp.zeros((held,), bool).at[
        jnp.where(chosen & (w > 0), local, held)].set(True, mode="drop")
    routed, multiplied = routed_experts(
        x, layer["experts"], local, w, held=held, act=cfg.expert_act,
        gated=cfg.expert_gated)
    counts = jnp.stack([jnp.sum(hit), jnp.sum(chosen), multiplied,
                        jnp.asarray(t * held)]).astype(jnp.int32)

    out = routed
    if cfg.shared_expert_dim:
        out = out + _expert(x, layer["shared"],
                            functools.partial(_einsum, "te,ef->tf"),
                            cfg.expert_act, cfg.expert_gated)
    return out.astype(h.dtype).reshape(*lead, -1), counts


# --- the block -------------------------------------------------------------


def _norm(x: jax.Array, weight, bias, cfg: ModelConfig) -> jax.Array:
    """The model's norm over the last axis, in float32: RMS, or where
    `cfg.layer_norm` a LayerNorm (mean and variance, weight and bias)."""
    if not cfg.layer_norm:
        return rms_norm(x, weight, cfg.norm_eps, False)
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    xf = xf * jax.lax.rsqrt(
        jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + cfg.norm_eps)
    return (xf * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def layer_norm_in(x: jax.Array, layer: Params, cfg: ModelConfig):
    return _norm(x, layer["norm"], layer.get("norm_b"), cfg)


def final_norm(x: jax.Array, params: Params, cfg: ModelConfig):
    return _norm(x, params["final_norm"], params.get("final_norm_b"), cfg)


def gmu(h: jax.Array, m: jax.Array, layer: Params, dtype) -> jax.Array:
    """A gated memory unit: W_2 (m * silu(W_1 h)); h [..., E] the
    layer's normed input, m [..., d_inner] float32 the memory at the
    same positions."""
    gate = jax.nn.silu(_einsum("...e,ef->...f", h, layer["in_proj"]))
    return _einsum("...f,fe->...e",
                   (m.astype(jnp.float32) * gate).astype(dtype),
                   layer["out_proj"]).astype(dtype)


# What a mixer's out-projection adds to a residual stream of unit rms
# (the embedding's, models/common.py: init_params), in random weights.
# With every mixer at unit scale the embedding is lost after one layer
# and one changed expert moves the logits by 0.17 sigma — and a top-k
# router changes experts on rounding noise: in bfloat16 a tenth of the
# served tokens then lay over 0.25 sigma from a float32 reference's
# maximum. A trained stack's layers each add a fraction. Measured at the
# benchmark's widths (PERF.md, PR 27): at 0.1 (the depth-scaled residual
# init, 1/sqrt(2 x 52)) the worst of 4608 positions lay 0.22 sigma off,
# at 0.07 the worst of 2304 lay 0.07 off.
RESIDUAL_SHARE = 0.07
# A model whose mixers are retention layers has no router for rounding
# to tip, and at RESIDUAL_SHARE its logits are the token's own
# embedding: six retention layers moved the served token by hundredths
# of a sigma, so no comparison of tokens could tell a wrong state, a
# gate left out or a lower precision from a sound run (PERF.md, PR 42).
# There EVERY out-projection (o_proj, down_proj) is at RETENTION_SHARE
# of unit scale: the mixers carry the stream (after six published layers
# 97 % of its mean square is theirs), whose mean square then grows by
# about RETENTION_GROWTH a published layer.
RETENTION_SHARE, RETENTION_GROWTH = 3.0, 4.5
# A model whose head IS its embedding (`tie_embeddings` beside
# `layer_kinds`): at unit rms a token's own row would decide its own
# logit (|e|^2 = E against sigma sqrt(E) for every other token) and the
# served token would be the last one read, whatever the layers hold. The
# table stands at the family's `initializer_range` instead, and every
# out-projection of a model with Mamba-1 layers at MAMBA1_SHARE of unit
# scale, so that the mixers carry the stream from the first one on and
# it stays of order one through the published 56 (about 1 / sqrt(56)).
TIED_EMBED_STD, MAMBA1_SHARE = 0.02, 0.134
# A model with gated short-convolution layers (`lfm2_moe`: tied head AND
# routed experts): the out-projections of its conv mixers, attention
# layers and dense MLPs at SHORTCONV_SHARE of unit scale — those mixers
# carry the stream, so the tied embedding's share of it is small (a
# token's own logit stands under a sigma up after a dozen of them) —
# while the experts' stay at RESIDUAL_SHARE: one expert changed by
# rounding in the router then moves the stream by a twentieth of its rms.
SHORTCONV_SHARE = 0.3
# The seeded bias of a LayerNorm (`cfg.layer_norm`): small and not zero,
# so that a comparison against a reference reads it.
NORM_BIAS_STD = 0.02
# The seeded gate of a retention layer (init_layer): the embedding
# channel held at 1.0 (no out-projection writes to it), W_g's row there
# over the kv heads, and the scale of its other rows (of unit scale).
GATE_CHANNEL, GATE_LEVELS, GATE_NOISE = 0, (4.0, 6.5), 0.25


def init_layer(cfg: ModelConfig, kind: str, key: jax.Array,
               dtype, depth: int = 0) -> Params:
    """Random weights of one layer, by kind: in-projections at the
    scale that keeps activations of order one, out-projections at
    RESIDUAL_SHARE of it (RETENTION_SHARE in a model with retention
    layers; `depth` counts the retention layers ahead of this one — or,
    in a model with differential attention, the PUBLISHED layers, mixer
    and MLP together, ahead of this one: `diffattn.lambda_init`). An
    attention layer is given ITS view of the config
    (ModelConfig.attention_layer): its own head count."""
    e = cfg.embed_dim
    ks = jax.random.split(key, 8)

    def dense(key, shape, fan_in, share=1.0):
        return (jax.random.normal(key, shape, jnp.float32)
                * (share * fan_in ** -0.5)).astype(dtype)

    def out(key, shape, fan_in):
        if cfg.mamba1_layers:
            return dense(key, shape, fan_in, MAMBA1_SHARE)
        if cfg.shortconv_layers:
            return dense(key, shape, fan_in, SHORTCONV_SHARE)
        if not cfg.retention_layers:
            return dense(key, shape, fan_in, RESIDUAL_SHARE)
        return dense(key, shape, fan_in, RETENTION_SHARE).at[
            ..., GATE_CHANNEL].set(0)

    layer: Params = {"norm": jnp.ones((e,), dtype)}
    if cfg.layer_norm:
        layer["norm_b"] = (jax.random.normal(
            jax.random.fold_in(key, 8), (e,), jnp.float32)
            * NORM_BIAS_STD).astype(dtype)
    if kind == MAMBA2:
        hh, d_in, conv = (cfg.mamba_heads, cfg.mamba_d_inner,
                          cfg.mamba_conv_dim)
        # dt from the published range [time_step_min, time_step_max],
        # log-uniform; A in [1, 16]: the reference implementation's init.
        dt = jnp.exp(jax.random.uniform(ks[2], (hh,), jnp.float32)
                     * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
        layer.update({
            "in_proj": dense(ks[0], (e, 2 * d_in + 2 * cfg.ssm_groups
                                     * cfg.ssm_state + hh), e),
            "conv_w": dense(ks[1], (cfg.conv_kernel, conv),
                            cfg.conv_kernel),
            "conv_b": jnp.zeros((conv,), dtype),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(jnp.float32),
            "A_log": jnp.log(jax.random.uniform(
                ks[3], (hh,), jnp.float32, 1.0, 16.0)),
            "D": jnp.ones((hh,), jnp.float32),
            "gate_norm": jnp.ones((d_in,), dtype),
            "out_proj": dense(ks[4], (d_in, e), d_in, RESIDUAL_SHARE),
        })
    elif kind == MAMBA1:
        layer.update(mamba1.init_mixer(cfg, ks, dense, out, dtype))
    elif kind == SHORTCONV:
        layer.update(shortconv.init_mixer(cfg, ks, dense, out))
    elif kind == EXPERTS:
        f, fs, held = cfg.expert_dim, cfg.shared_expert_dim, \
            cfg.experts_held
        layer.update({
            "router": dense(ks[0], (e, cfg.routed_experts), e)
            .astype(jnp.float32),
            "experts": {"up": dense(ks[2], (held, e, f), e),
                        "down": dense(ks[3], (held, f, e), f,
                                      RESIDUAL_SHARE)},
        })
        if fs:
            layer["shared"] = {"up": dense(ks[4], (e, fs), e),
                               "down": dense(ks[5], (fs, e), fs,
                                             RESIDUAL_SHARE)}
        if cfg.router_rule == "sigmoid_bias_topk":
            layer["router_bias"] = jax.random.normal(
                ks[1], (cfg.routed_experts,), jnp.float32) * 0.02
        if cfg.expert_gated:
            layer["experts"]["gate"] = dense(ks[6], (held, e, f), e)
            if fs:
                layer["shared"]["gate"] = dense(ks[7], (e, fs), e)
    elif kind == MLP:
        f = cfg.mlp_dim
        layer.update({
            "gate_proj": dense(ks[0], (e, f), e),
            "up_proj": dense(ks[1], (e, f), e),
            "down_proj": out(ks[2], (f, e), f),
        })
    elif kind == RETENTION:
        h_, k_, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        # A gate without a bias reads its level from the one channel
        # the embedding holds constant (common.init_params): g =
        # sigmoid(level + noise) with the levels GATE_LEVELS over the kv
        # heads, half-lives of tens to hundreds of tokens. A random W_g
        # on zero-mean inputs gives g near 0.5: a memory of a few
        # tokens, under which no comparison could see the state. The
        # layer's norm divides that channel by the stream's rms, so the
        # row is written times what the rms has grown to at this depth.
        g_proj = dense(ks[4], (e, k_), e, GATE_NOISE).astype(jnp.float32)
        g_proj = g_proj.at[GATE_CHANNEL].set(
            jnp.linspace(*GATE_LEVELS, k_)
            * (1.0 + RETENTION_GROWTH * depth) ** 0.5).astype(dtype)
        layer.update({
            "q_proj": dense(ks[0], (e, h_, d), e),
            "k_proj": dense(ks[1], (e, k_, d), e),
            "v_proj": dense(ks[2], (e, k_, d), e),
            "g_proj": g_proj,
            "q_norm": jnp.ones((d,), dtype),
            "k_norm": jnp.ones((d,), dtype),
            "o_proj": out(ks[3], (h_, d, e), h_ * d),
        })
    elif kind == ATTENTION and cfg.latent:
        h_, r_q, r_kv = cfg.num_heads, cfg.q_lora_rank, cfg.kv_lora_rank
        nope, rot, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
        layer.update({
            "q_a": dense(ks[0], (e, r_q), e),
            "q_norm": jnp.ones((r_q,), dtype),
            "q_b": dense(ks[1], (r_q, h_, nope + rot), r_q),
            "kv_a": dense(ks[2], (e, r_kv + rot), e),
            "kv_norm": jnp.ones((r_kv,), dtype),
            "kv_b": dense(ks[3], (r_kv, h_, nope + dv), r_kv),
            "o_proj": dense(ks[4], (h_, dv, e), h_ * dv, RESIDUAL_SHARE),
        })
    elif kind == GMU:
        d1 = cfg.mamba1_dim
        layer.update({"in_proj": dense(ks[0], (e, d1), e),
                      "out_proj": out(ks[1], (d1, e), d1)})
    elif kind in (ATTENTION, CROSS) and cfg.diff_attn:
        layer.update(diffattn.init_mixer(cfg, ks, dense, out, dtype,
                                         depth=depth, cross=kind == CROSS))
    elif kind == ATTENTION:
        h_, k_, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        layer.update({
            "q_proj": dense(ks[0], (e, h_, d), e),
            "k_proj": dense(ks[1], (e, k_, d), e),
            "v_proj": dense(ks[2], (e, k_, d), e),
            "o_proj": out(ks[3], (h_, d, e), h_ * d),
        })
        if cfg.attn_gate:
            layer["g_proj"] = dense(ks[4], (e, h_), e)
        if cfg.qk_norm:
            layer["q_norm"] = jnp.ones((d,), dtype)
            layer["k_norm"] = jnp.ones((d,), dtype)
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    return layer


def zero_state(cfg: ModelConfig, rows: int, dtype=jnp.bfloat16) -> dict:
    """The recurrent state of `rows` sequences, float32, one entry a
    layer that keeps one (Mamba-1: one a scanned RUN, models/mamba1.py:
    {"ssm1": [[rows,L,N,G,W]...], "conv1": [[rows,L,K-1,G,W]...]}).
    Mamba-2: {"ssm": [[rows,H,P,N]...], "conv":
    [[rows,K-1,C]...]}; retention (models/retention.py), where the
    model has such layers: {"ret": [[rows,K,D/2+1,D,D]...], "retn":
    [[rows,K,D/2+1,D]...]}; gated short convolution
    (models/shortconv.py): {"sconv": [[rows,K-1,E]...]} in `dtype`, the
    activations' (every other part is float32 whatever it is)."""
    n = len(cfg.mamba_layers)
    return {
        "ssm": [jnp.zeros((rows, cfg.mamba_heads, cfg.mamba_head_dim,
                           cfg.ssm_state), jnp.float32) for _ in range(n)],
        "conv": [jnp.zeros((rows, cfg.conv_kernel - 1,
                            cfg.mamba_conv_dim), jnp.float32)
                 for _ in range(n)],
        # (a part only where some layer keeps it)
        **(retention.zero_state(cfg, rows) if cfg.retention_layers
           else {}),
        **(mamba1.zero_state(cfg, rows) if cfg.mamba1_layers else {}),
        **(shortconv.zero_state(cfg, rows, dtype) if cfg.shortconv_layers
           else {}),
    }


def state_bytes_per_sequence(cfg: ModelConfig, dtype=jnp.bfloat16) -> int:
    per = (cfg.mamba_heads * cfg.mamba_head_dim * cfg.ssm_state
           + (cfg.conv_kernel - 1) * cfg.mamba_conv_dim) * 4
    return per * len(cfg.mamba_layers) \
        + retention.bytes_per_state(cfg) * len(cfg.retention_layers) \
        + mamba1.bytes_per_state(cfg) * len(cfg.mamba1_layers) \
        + shortconv.bytes_per_state(cfg, dtype) * len(cfg.shortconv_layers)


def layers_unrolled(cfg: ModelConfig, params: Params):
    """(kind, layer) of every layer in order, a scanned run's stacked
    leaves indexed by layer: for the whole-sequence forward and whoever
    reads the tree a layer at a time (a reference, a test)."""
    for (kinds, n), entry in zip(cfg.layer_runs, params["layers"]):
        if kinds[0] != MAMBA1:
            yield kinds[0], entry
            continue
        for j in range(n):
            for kind in kinds:
                yield kind, jax.tree_util.tree_map(lambda a, j=j: a[j],
                                                   entry[kind])
