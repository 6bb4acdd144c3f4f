"""Mamba-1 (`ModelConfig.layer_kinds`: "mamba1"): a selective state-space
mixer whose decay is a value for EVERY (state index, channel) pair, so
its scan has no product form (Mamba-2's scalar decay a head has:
`hybrid._ssd_chunk`). For token t, h the layer's normed input,
d_inner = `cfg.mamba1_dim`, N = `cfg.ssm_state`, R = `cfg.dt_rank`:

    [u_t, z_t]        = W_in h_t                          (no bias)
    c_t               = silu(conv_b + sum_j conv_w[j] u_{t-K+1+j})
    [dl_t, B_t, C_t]  = W_x c_t                           (R, N, N)
    dl, B, C          each through an RMS norm of its own where
                      `cfg.mamba1_norms` (`jamba`: True; `phi4flash`:
                      False — straight to W_dt and the scan, and the
                      layer has no such leaves)
    dt_t              = softplus(W_dt dl_t + b_dt)        in R^d_inner
    S_t[n, d]         = exp(dt_t[d] A[n, d]) S_{t-1}[n, d]
                        + dt_t[d] c_t[d] B_t[n]           A = -exp(A_log)
    y_t               = S_t C_t + D c_t
    out_t             = W_out (y_t silu(z_t))             (no bias)

With `emit` an entry point also returns m_t = y_t (float32, before the
gate): what the gated memory units of the layers above read
(`ModelConfig.memory_layer`); the layer's own output is unchanged.

A sequence keeps S (float32) and the last K-1 rows of u (the conv's
tail); nothing reads a past position again.

**The layout.** Channels are folded onto whole lane rows (`pallas/
mamba1.fold`: d_inner = G x 128), N ahead of them: `[N, G, W]` a
sequence a layer. `[d_inner, N]` would pad 16 to 128 lanes, eight times
the bytes. A model's Mamba-1 layers come in RUNS (`cfg.layer_runs`) whose
parameters and state are stacked along a layer axis and scanned
(engine/paged_forward.py), so the state is ONE leaf a run,
`ssm1` [rows, L, N, G, W] and `conv1` [rows, L, K-1, G, W]: rows ahead of
layers, so that a slot's whole state is one index for the store
(engine/hybrid_state.py), and a layer of the scan addresses `[:, l]`.

Three entry points, as `hybrid.mamba2_*` has them, all on EVERY slot's
state in place (`hybrid.SLOT_PARTS`):

- `mamba1_step`: one token a row, in SLOT order — the batch's rows are
  scattered to their state rows (small), every other slot rides with
  dt = 0, and the update is one pass over `[:, l]`: on the chip the scan
  kernel at one token a block (`name="mamba1_step"`), each state byte
  read once and written once in place, nothing gathered (XLA's own
  fusions for the same step read the state twice: once for y, once for
  the update); elsewhere the same pass in `jax.numpy`.
- `mamba1_ragged` (the scheduler's flat buffer) and `mamba1_prefill`
  ([B, T] rows: the same buffer, a run a row): the scan of a dispatch
  is ONE call of `pallas/mamba1.mamba1_scan` a layer on the chip, and
  `scan_blocks` (the same recurrence a token at a time in `jax.numpy`)
  elsewhere. A run restarts from its slot's row; a snapshot is the
  state after the token a page ends on, written straight into the store.

A token with dt = 0 is the identity on the state (exp(0) = 1, no
input): pads, finished rows, slots not in the batch.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..pallas import mamba1 as kernel
from .common import ModelConfig, Params, _einsum, rms_norm

KIND = "mamba1"
PARTS = ("ssm1", "conv1")
# The seeded step size: log-uniform over the reference implementation's
# range, so that exp(dt A) at A = -1 has half-lives of 7 to 700 tokens.
DT_RANGE = (1e-3, 1e-1)


def dims(cfg: ModelConfig) -> tuple[int, int, int, int]:
    """(N, G, W, K-1): state indices, lane rows, lanes, tail rows."""
    g, w = kernel.fold(cfg.mamba1_dim)
    return cfg.ssm_state, g, w, cfg.conv_kernel - 1


def bytes_per_state(cfg: ModelConfig) -> int:
    """One sequence, one Mamba-1 layer: S and the conv tail, float32."""
    n, g, w, k1 = dims(cfg)
    return (n + k1) * g * w * 4


def zero_state(cfg: ModelConfig, rows: int) -> dict:
    n, g, w, k1 = dims(cfg)
    return {"ssm1": [jnp.zeros((rows, length, n, g, w), jnp.float32)
                     for length in cfg.scan_runs],
            "conv1": [jnp.zeros((rows, length, k1, g, w), jnp.float32)
                      for length in cfg.scan_runs]}


def init_mixer(cfg: ModelConfig, ks, dense, out, dtype) -> Params:
    """The reference implementation's initialisation: A = -(1..N) a
    channel, b_dt the inverse softplus of a log-uniform draw from
    DT_RANGE a channel, W_dt at R^-0.5, D and the three small norms
    (where the model has them: `cfg.mamba1_norms`) ones; `dense` / `out`
    are `hybrid.init_layer`'s (unit scale in, the model's share out)."""
    e, d, n, r = cfg.embed_dim, cfg.mamba1_dim, cfg.ssm_state, cfg.dt_rank
    lo, hi = DT_RANGE
    dt = jnp.exp(jax.random.uniform(ks[2], (d,), jnp.float32)
                 * (jnp.log(hi) - jnp.log(lo)) + jnp.log(lo))
    norms = {"dt_norm": jnp.ones((r,), dtype),
             "b_norm": jnp.ones((n,), dtype),
             "c_norm": jnp.ones((n,), dtype)} if cfg.mamba1_norms else {}
    return {
        "in_proj": dense(ks[0], (e, 2 * d), e),
        "conv_w": dense(ks[1], (cfg.conv_kernel, d), cfg.conv_kernel),
        "conv_b": jnp.zeros((d,), dtype),
        "x_proj": dense(ks[3], (d, r + 2 * n), d),
        **norms,
        "dt_proj": dense(ks[4], (r, d), r),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[:, None],
            (n, d)),
        "D": jnp.ones((d,), jnp.float32),
        "out_proj": out(ks[5], (d, e), d),
    }


# --- the mixer around the scan ----------------------------------------------


def _in(h: jax.Array, layer: Params, cfg: ModelConfig):
    uz = _einsum("...e,ef->...f", h, layer["in_proj"])
    return uz[..., :cfg.mamba1_dim], uz[..., cfg.mamba1_dim:]


def _conv(rows: list, layer: Params) -> jax.Array:
    """silu(b + sum_k w[k] rows[k]): rows[k] the input K-1-k tokens back
    (rows[-1] the token itself), float32."""
    w = layer["conv_w"].astype(jnp.float32)
    acc = layer["conv_b"].astype(jnp.float32)
    for k, r in enumerate(rows):
        acc = acc + w[k] * r
    return jax.nn.silu(acc)


def _selective(c: jax.Array, layer: Params, cfg: ModelConfig, dtype):
    """c [..., d] float32 -> dt [..., d] float32, bc [..., 2N] float32:
    the projections in `dtype`, the three norms (`cfg.mamba1_norms`),
    the softplus."""
    r, n = cfg.dt_rank, cfg.ssm_state
    x = _einsum("...f,fr->...r", c.astype(dtype), layer["x_proj"])
    dl, b, cm = x[..., :r], x[..., r:r + n], x[..., r + n:]
    if cfg.mamba1_norms:
        dl, b, cm = (rms_norm(a, layer[w], cfg.norm_eps, False)
                     for a, w in ((dl, "dt_norm"), (b, "b_norm"),
                                  (cm, "c_norm")))
    dt = jax.nn.softplus(
        _einsum("...r,rf->...f", dl.astype(dtype),
                layer["dt_proj"]).astype(jnp.float32)
        + layer["dt_bias"].astype(jnp.float32))
    return dt, jnp.concatenate([b, cm], axis=-1).astype(jnp.float32)


def _a_neg(layer: Params, cfg: ModelConfig) -> jax.Array:
    n, g, w, _ = dims(cfg)
    return -jnp.exp(layer["A_log"].astype(jnp.float32)).reshape(n, g, w)


def _out(y: jax.Array, c: jax.Array, z: jax.Array, layer: Params,
         dtype) -> tuple[jax.Array, jax.Array]:
    """m = y + D c, and m silu(z) through the out-projection; y, c
    [..., d] f32. -> (out, m)."""
    m = y + layer["D"].astype(jnp.float32) * c
    out = _einsum("...f,fe->...e",
                  (m * jax.nn.silu(z.astype(jnp.float32))).astype(dtype),
                  layer["out_proj"]).astype(dtype)
    return out, m


def mamba1_step(h: jax.Array, layer: Params, cfg: ModelConfig,
                ssm: jax.Array, conv: jax.Array, l, rows: jax.Array,
                active: jax.Array, emit: bool = False):
    """One decode token a row. h [B,1,E]; ssm [R,L,N,G,W] / conv
    [R,L,K-1,G,W] EVERY slot's state of the run, `l` the layer in it;
    rows [B] each batch row's state row; rows with `active` False keep
    their state. -> (out [B,1,E], ssm, conv), and with `emit` m
    [B,1,d] float32."""
    n, g, w, k1 = dims(cfg)
    r_all = ssm.shape[0]
    # Slot order: the batch's rows at their state rows, every other slot
    # (and every row that must not advance) at dt = 0.
    hs = jnp.zeros((r_all, h.shape[-1]), h.dtype).at[rows].set(h[:, 0])
    live = jnp.zeros((r_all,), bool).at[rows].set(active)
    u, z = _in(hs, layer, cfg)
    cur = u.astype(jnp.float32)                           # [R, d]
    tail = jax.lax.dynamic_index_in_dim(conv, l, 1, keepdims=False)
    tail_f = tail.reshape(r_all, k1, g * w)
    c = _conv([tail_f[:, k] for k in range(k1)] + [cur], layer)
    dt, bc = _selective(c, layer, cfg, h.dtype)
    dt = jnp.where(live[:, None], dt, 0.0).reshape(r_all, 1, g, w)
    cg = c.reshape(r_all, 1, g, w)
    if kernel.decline_reason(cfg.mamba1_dim, n) is None:
        # The scan kernel at one token a block, a block a slot: each
        # state byte read once and written once, in place.
        every = jnp.arange(r_all, dtype=jnp.int32)
        y, ssm, _ = kernel.mamba1_scan(
            dt[:, 0], cg[:, 0], bc, _a_neg(layer, cfg), ssm,
            jnp.asarray(l, jnp.int32), every, jnp.full_like(every, -1),
            jnp.zeros_like(every), block=1, n_seqs=1)
    else:
        s = jax.lax.dynamic_index_in_dim(ssm, l, 1, keepdims=False)
        new = jnp.exp(dt * _a_neg(layer, cfg)) * s \
            + (dt * cg) * bc[:, :n, None, None]
        y = jnp.sum(new * bc[:, n:, None, None], axis=1)  # [R, G, W]
        ssm = jax.lax.dynamic_update_index_in_dim(ssm, new, l, 1)
    out, m = _out(y.reshape(r_all, g * w), c, z, layer, h.dtype)
    moved = jnp.concatenate([tail[:, 1:], cur.reshape(r_all, 1, g, w)],
                            axis=1)
    conv = jax.lax.dynamic_update_index_in_dim(
        conv, jnp.where(live[:, None, None, None], moved, tail), l, 1)
    if emit:
        return out[rows][:, None], ssm, conv, m[rows][:, None]
    return out[rows][:, None], ssm, conv


def scan_blocks(dt, c, bc, a, state, layer, block_slot, block_cap,
                block_seq, *, block: int, n_seqs: int):
    """`pallas/mamba1.mamba1_scan`'s contract in `jax.numpy`, the
    recurrence a token at a time: what serves where the kernel declines,
    and what the kernel is tested against."""
    n = a.shape[0]
    nb = dt.shape[0] // block

    def blocks(x):
        return x.reshape(nb, block, *x.shape[1:])

    def one_block(carry, xs):
        state, held = carry
        slot, cap_at, seq, dt_b, c_b, bc_b = xs
        s0 = state[slot, layer]

        def token(carry, ts):
            s, kept = carry
            t, dt_t, c_t, bc_t = ts
            s = jnp.exp(dt_t * a) * s \
                + (dt_t * c_t) * bc_t[:n, None, None]
            kept = jnp.where(t == cap_at, s, kept)
            return (s, kept), jnp.sum(s * bc_t[n:, None, None], axis=0)

        (s, kept), y = jax.lax.scan(
            token, (s0, held[seq]), (jnp.arange(block), dt_b, c_b, bc_b))
        return (state.at[slot, layer].set(s), held.at[seq].set(kept)), y

    held0 = jnp.zeros((n_seqs,) + a.shape, jnp.float32)
    (state, held), y = jax.lax.scan(
        one_block, (state, held0),
        (block_slot, block_cap, block_seq, blocks(dt), blocks(c),
         blocks(bc)))
    return y.reshape(dt.shape), state, held


def mamba1_ragged(h: jax.Array, layer: Params, cfg: ModelConfig,
                  ssm: jax.Array, conv: jax.Array, l, rg: dict,
                  snaps: Optional[tuple] = None,
                  snap_idx: Optional[jax.Array] = None,
                  emit: bool = False):
    """A Mamba-1 mixer over the flat token buffer. h [1,T,E]; ssm / conv
    EVERY slot's state of the run (`mamba1_step`), `l` the layer in it;
    `rg` as `hybrid.ragged_meta` builds it. With `snaps` (the store's
    two arrays of the run) each sequence's state after `rg["cap_n"]` of
    its tokens is written at `snap_idx` (sequences with none: the
    scratch snapshot). -> (out [1,T,E], ssm, conv, snaps), and with
    `emit` m [1,T,d] float32."""
    n, g, w, k1 = dims(cfg)
    t = h.shape[1]
    q = rg["block"]
    u, z = _in(h[0], layer, cfg)
    raw = u.astype(jnp.float32)                           # [T, d]
    tok_slot = rg["seq_slot"][rg["token_seq"]]
    run_idx = rg["run_idx"]
    tail_all = jax.lax.dynamic_index_in_dim(conv, l, 1, keepdims=False) \
        .reshape(conv.shape[0], k1, g * w)
    rows = []
    for back in range(k1, 0, -1):
        # The input `back` tokens ago: in the buffer while the run
        # reaches that far, else in the slot's tail.
        prev = raw[jnp.clip(jnp.arange(t) - back, 0, t - 1)]
        old = tail_all[tok_slot, jnp.clip(k1 + run_idx - back, 0, k1 - 1)]
        rows.append(jnp.where((run_idx >= back)[:, None], prev, old))
    c = _conv(rows + [raw], layer)
    dt, bc = _selective(c, layer, cfg, h.dtype)
    dt = jnp.where(rg["token_valid"][:, None], dt, 0.0)

    # The block holding the last token before a sequence's snapshot
    # point, and that token's index in it (-1: no snapshot here).
    cap_n = rg["cap_n"]
    seq_b = rg["seq_of_block"]
    last = cap_n[seq_b] - 1
    block_cap = jnp.where((cap_n[seq_b] > 0)
                          & (rg["block_qstart"] == last // q * q),
                          last % q, -1)
    scan = (scan_blocks if kernel.decline_reason(cfg.mamba1_dim, n)
            else kernel.mamba1_scan)
    y, ssm, held = scan(
        dt.reshape(t, g, w), c.reshape(t, g, w), bc, _a_neg(layer, cfg),
        ssm, jnp.asarray(l, jnp.int32), rg["block_slot"], block_cap, seq_b,
        block=q, n_seqs=cap_n.shape[0])
    out, m = _out(y.reshape(t, g * w), c, z, layer, h.dtype)
    out = out[None]

    def tails(count):
        # The last K-1 inputs of [old tail; the run's first `count`].
        j = jnp.arange(k1)[None, :]
        src = count[:, None] - k1 + j                     # index in run
        new = raw[jnp.clip(rg["seq_start"][:, None] + src, 0, t - 1)]
        old = tail_all[rg["seq_slot"][:, None],
                       jnp.clip(count[:, None] + j, 0, k1 - 1)]
        return jnp.where((src >= 0)[..., None], new, old) \
            .reshape(-1, k1, g, w)

    conv = conv.at[rg["seq_slot"], l].set(tails(rg["seq_len"]))
    if snaps is not None:
        snaps = (snaps[0].at[snap_idx, l].set(held),
                 snaps[1].at[snap_idx, l].set(tails(cap_n)))
    if emit:
        return out, ssm, conv, snaps, m[None]
    return out, ssm, conv, snaps


def mamba1_prefill(h: jax.Array, layer: Params, cfg: ModelConfig,
                   ssm: jax.Array, conv: jax.Array, l, rows: jax.Array,
                   lengths: jax.Array,
                   snaps: Optional[tuple] = None,
                   cap_len: Optional[jax.Array] = None,
                   snap_idx: Optional[jax.Array] = None,
                   emit: bool = False):
    """A Mamba-1 mixer over [B, T] rows, each from its own state row:
    the flat buffer of `mamba1_ragged` with a run a row. h [B,T,E];
    rows [B] the state rows; lengths [B] valid tokens a row; with
    `snaps`, `cap_len` [B] (0: none) and `snap_idx` [B] a snapshot a row.
    -> (out [B,T,E], ssm, conv, snaps), and with `emit` m [B,T,d]."""
    from ..serving_loop import RAGGED_BLOCK_Q as q
    b, t, e = h.shape
    tp = -(-t // q) * q
    if tp != t:
        h = jnp.pad(h, [(0, 0), (0, tp - t), (0, 0)])
    seq = jnp.arange(b)
    run_idx = jnp.tile(jnp.arange(tp), b)
    token_seq = jnp.repeat(seq, tp)
    seq_of_block = jnp.repeat(seq, tp // q)
    rg = {
        "block": q, "token_seq": token_seq, "run_idx": run_idx,
        "token_valid": run_idx < lengths[token_seq],
        "seq_slot": rows, "seq_len": lengths, "seq_start": seq * tp,
        "block_slot": rows[seq_of_block], "seq_of_block": seq_of_block,
        "block_qstart": jnp.tile(jnp.arange(0, tp, q), b),
        "cap_n": (jnp.zeros((b,), jnp.int32) if cap_len is None
                  else cap_len),
    }
    out, ssm, conv, snaps, *m = mamba1_ragged(
        h.reshape(1, b * tp, e), layer, cfg, ssm, conv, l, rg, snaps,
        snap_idx, emit)
    return (out.reshape(b, tp, e)[:, :t], ssm, conv, snaps,
            *(a.reshape(b, tp, -1)[:, :t] for a in m))
