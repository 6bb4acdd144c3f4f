"""The gated short convolution (`ModelConfig.layer_kinds`: "shortconv";
the `conv` operator of `lfm2_moe`): a depthwise causal convolution of K =
`cfg.conv_kernel` taps between two elementwise gates, no bias and no
activation. For token t, h the layer's normed input [E]:

    [B_t, C_t, u_t] = W_in h_t            (E -> 3 E, in that order)
    g_t             = B_t * u_t
    c_t             = sum_{j<K} w[j] * g_{t-K+1+j}     (one filter a channel)
    out_t           = W_out (C_t * c_t)

A sequence keeps the last K-1 rows of g — at K = 3 two rows of E values,
8 KB in bfloat16 at E = 2048 — and nothing else: no recurrence reaches
further back than the taps. `g` is rounded ONCE, where it is made, to the
dtype of the tail it is handed (the engine's own: `zero_state`), in all
three forms below, and the taps are summed in float32: a tail one form
leaves is bit for bit what another would have kept, so a join that starts
from a snapshot, a continuation and a scan from nothing give the same
rows.

Three forms, as the other state mixers have (`hybrid.mamba2_*`), the
state a ROW part (`hybrid.ROW_PARTS`: gathered to the batch's rows by a
step program and scattered back, as Mamba-2's conv tail is):

- `shortconv_step`: one token a row.
- `shortconv_prefill`: [B, T] rows, each from its own tail; also the
  tail after `cap_len` tokens (a snapshot at a page boundary).
- `shortconv_ragged`: the scheduler's flat buffer. A run's rows are
  consecutive, so a token's taps are the rows before it in the buffer
  while the run reaches that far and its slot's tail beyond; every
  sequence's slot leaves with the last K-1 rows of [tail; run].

Three elementwise stages beside two products: XLA fuses them, and no
kernel is asked for.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .common import ModelConfig, Params, _einsum

KIND = "shortconv"
PART = "sconv"


def bytes_per_state(cfg: ModelConfig, dtype=jnp.bfloat16) -> int:
    """One sequence, one layer: K-1 rows of E values of `dtype` (the
    engine's)."""
    return ((cfg.conv_kernel - 1) * cfg.embed_dim
            * jnp.dtype(dtype).itemsize)


def zero_state(cfg: ModelConfig, rows: int, dtype=jnp.bfloat16) -> dict:
    return {PART: [jnp.zeros((rows, cfg.conv_kernel - 1, cfg.embed_dim),
                             dtype) for _ in cfg.shortconv_layers]}


def init_mixer(cfg: ModelConfig, ks, dense, out) -> Params:
    """`dense` / `out` are `hybrid.init_layer`'s (unit scale in, the
    model's share out); the taps at K^-0.5 each, so that c is of g's
    scale."""
    e = cfg.embed_dim
    return {"in_proj": dense(ks[0], (e, 3 * e), e),
            "conv_w": dense(ks[1], (cfg.conv_kernel, e), cfg.conv_kernel),
            "out_proj": out(ks[2], (e, e), e)}


def taps_sum(rows: list, w: jax.Array, start=None) -> jax.Array:
    """start + sum_k w[k] * rows[k], float32: rows[k] is the input
    K-1-k tokens back (rows[-1] the token itself), w [K, C]. Mamba-2's
    conv (`hybrid._conv_taps`: a bias to start from, SiLU after) and
    this layer's (neither) are this sum."""
    acc = start
    for k, r in enumerate(rows):
        term = w[k] * r
        acc = term if acc is None else acc + term
    return acc


def _gates(h: jax.Array, layer: Params, cfg: ModelConfig, dtype):
    """-> (g in `dtype`, the tail's; C float32), both [..., E]."""
    e = cfg.embed_dim
    bcu = _einsum("...e,ef->...f", h, layer["in_proj"])
    g = (bcu[..., :e] * bcu[..., 2 * e:]).astype(dtype)
    return g, bcu[..., e:2 * e]


def _out(rows: list, c_gate: jax.Array, layer: Params, dtype) -> jax.Array:
    conv = taps_sum([r.astype(jnp.float32) for r in rows],
                    layer["conv_w"].astype(jnp.float32))
    return _einsum("...e,ef->...f", (c_gate * conv).astype(dtype),
                   layer["out_proj"]).astype(dtype)


def tail_rows(ext: jax.Array, lengths: jax.Array, k1: int) -> jax.Array:
    """Rows [len, len + K-1) of ext = [old tail (K-1 rows); the run's
    rows]: the last K-1 inputs of a run of `lengths` tokens.
    ext [B, K-1+T, C], lengths [B] -> [B, K-1, C]."""
    idx = jnp.clip(lengths[:, None] + jnp.arange(k1)[None, :], 0,
                   ext.shape[1] - 1)
    return jnp.take_along_axis(ext, idx[:, :, None], axis=1)


def shortconv_step(h: jax.Array, layer: Params, cfg: ModelConfig,
                   tail: jax.Array, active: jax.Array):
    """One decode token a row. h [B,1,E], tail [B,K-1,E]; rows with
    `active` False keep their tail (a finished row still rides the
    batch). -> (out [B,1,E], tail)."""
    k1 = cfg.conv_kernel - 1
    g, c_gate = _gates(h, layer, cfg, tail.dtype)
    cur = g[:, 0]                                         # [B,E]
    out = _out([tail[:, k] for k in range(k1)] + [cur], c_gate[:, 0],
               layer, h.dtype)
    new = jnp.concatenate([tail[:, 1:], cur[:, None]], axis=1)
    return out[:, None], jnp.where(active[:, None, None], new, tail)


def shortconv_prefill(h: jax.Array, layer: Params, cfg: ModelConfig,
                      tail0: jax.Array, lengths: jax.Array,
                      cap_len: Optional[jax.Array] = None):
    """The mixer over [B, T] rows, each from its own tail. h [B,T,E]
    (normed input), tail0 [B,K-1,E], lengths [B] valid tokens a row.
    -> (out [B,T,E], tail after `lengths` tokens) and, with `cap_len`
    [B] (1..lengths; anything else: garbage the caller drops), the tail
    after `cap_len` tokens."""
    t = h.shape[1]
    k1 = cfg.conv_kernel - 1
    g, c_gate = _gates(h, layer, cfg, tail0.dtype)
    ext = jnp.concatenate([tail0, g], axis=1)             # [B,K-1+T,E]
    out = _out([ext[:, k:k + t] for k in range(cfg.conv_kernel)], c_gate,
               layer, h.dtype)
    tail = tail_rows(ext, lengths, k1)
    if cap_len is None:
        return out, tail
    return out, tail, tail_rows(ext, cap_len, k1)


def shortconv_ragged(h: jax.Array, layer: Params, cfg: ModelConfig,
                     tail_all: jax.Array, rg: dict):
    """The mixer over the flat token buffer. h [1,T,E]; tail_all
    [R,K-1,E]: EVERY slot's tail (row R-1 is scratch: pads land there);
    `rg` as `hybrid.ragged_meta` builds it. -> (out [1,T,E], tail_all,
    cap [S,K-1,E]): every sequence's slot advanced by its run, and the
    tail of each at its snapshot point (rows with cap_n 0: garbage)."""
    t = h.shape[1]
    k1 = cfg.conv_kernel - 1
    g, c_gate = _gates(h, layer, cfg, tail_all.dtype)
    raw = g[0]                                            # [T,E]
    tok_slot = rg["seq_slot"][rg["token_seq"]]            # [T]
    run_idx = rg["run_idx"]
    rows = []
    for back in range(k1, 0, -1):
        # The input `back` tokens ago: in the buffer while the run
        # reaches that far, else in the slot's tail.
        prev = raw[jnp.clip(jnp.arange(t) - back, 0, t - 1)]
        old = tail_all[tok_slot, jnp.clip(k1 + run_idx - back, 0, k1 - 1)]
        rows.append(jnp.where((run_idx >= back)[:, None], prev, old))
    out = _out(rows + [raw], c_gate[0], layer, h.dtype)

    def tails(n):
        # The last K-1 inputs of [old tail; the run's first n rows].
        j = jnp.arange(k1)[None, :]
        src = n[:, None] - k1 + j                         # index in run
        from_run = raw[jnp.clip(rg["seq_start"][:, None] + src, 0, t - 1)]
        old = tail_all[rg["seq_slot"][:, None],
                       jnp.clip(n[:, None] + j, 0, k1 - 1)]
        return jnp.where((src >= 0)[..., None], from_run, old)

    cap = tails(rg["cap_n"])
    tail_all = tail_all.at[rg["seq_slot"]].set(tails(rg["seq_len"]))
    return out[None], tail_all, cap
