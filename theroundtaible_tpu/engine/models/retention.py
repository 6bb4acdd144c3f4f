"""Power retention (`ModelConfig.layer_kinds`: "retention"): a mixer with
NO keys and values to keep. Per kv head m and query head n of its group,
causal, degree 2, one gate a kv head (log g_t = log sigmoid(W_g h_t)):

    a_tj = exp(sum_{l=j+1..t} log g_l) (q_t . k_j)^2        j <= t
    y_t  = sum_j a_tj v_j / sum_j a_tj

The form served is the recurrent one. With phi(u) the degree-2 feature
map, phi(q) . phi(k) = (q . k)^2 exactly, and a kv head's state is

    S_t = g_t S_{t-1} + phi(k_t) (x) v_t      Z_t = g_t Z_{t-1} + phi(k_t)
    y_t = phi(q_t) . S_t / phi(q_t) . Z_t

both float32 (Z is the 129th column of the paper's state: the gated sum
of keys, the normaliser). Nothing reads a past position again.

**The layout.** phi(u) is held as D/2 + 1 rows of D: row d holds
c_d u_a u_{(a+d) mod D} for every a, with c_0 = c_{D/2} = 1 and sqrt 2
between (row D/2 holds each of its pairs twice at weight 1). That is
D (D/2 + 1) entries where D (D + 1) / 2 are the least (8320 for 8256 at
D = 128, 0.78 % over), every row a whole lane row, and a row is one
rotation and one product: no gather, no triangular index. The state is
`ret` [rows, K, D/2+1, D(v), D(a)] and `retn` [rows, K, D/2+1, D(a)].

Three entry points, as `hybrid.mamba2_*` has them, all on the WHOLE
slot arrays (a state is 34 MB a layer a sequence at the published
widths: nothing gathers the batch's rows or returns a capture beside
the store):

- `retention_step`: one token a row. On the chip ONE Pallas call a
  layer (`pallas/retention.py`) reads each row's state once and writes
  it once, in place; elsewhere the same recurrence in `jax.numpy`.
- `retention_prefill` ([B, T] rows) and `retention_ragged` (the
  scheduler's flat buffer): both `_retention_runs`, which cuts every
  run into chunks that end where a page ends (`chunk` = the page size),
  so the state at a page boundary is a chunk's output and a snapshot is
  one more copy of it, straight into the store. A chunk computes
      e^{b_i} phi(q_i) . S_in + sum_{j<=i} e^{b_i-b_j} (q_i.k_j)^2 [v_j, 1]
  and hands S_out = e^{b_C} S_in + sum_j e^{b_C-b_j} phi(k_j) (x) [v_j, 1]
  to the next: on the chip the terms with S through `retention_chunk`
  (the same pass over the state as the step's, in place), what lies
  inside the chunk in XLA; elsewhere all of it in `jax.numpy`, phi(Q)
  and phi(K) a chunk at a time. A run of ONE token (a decode row riding
  a join's dispatch) goes through the step kernel.

A row or token that must not advance its state rides with g = 1 and
k = 0: the identity on S and Z.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..pallas import retention as kernel
from .common import ModelConfig, Params, _einsum, project_qkv

POWER = 2
_HI = jax.lax.Precision.HIGHEST


def feature_rows(head_dim: int) -> int:
    """Rows of D the laid-out phi(u) has."""
    return head_dim // 2 + 1


def state_rows(head_dim: int) -> int:
    """Entries of phi(u) as laid out (8320 at D = 128)."""
    return feature_rows(head_dim) * head_dim


def state_rows_min(head_dim: int) -> int:
    """The least a degree-2 feature map needs: D (D + 1) / 2."""
    return head_dim * (head_dim + 1) // 2


def bytes_per_state(cfg: ModelConfig) -> int:
    """One sequence, one retention layer: S and Z, float32."""
    return (cfg.num_kv_heads * state_rows(cfg.head_dim)
            * (cfg.head_dim + 1) * 4)


def zero_state(cfg: ModelConfig, rows: int) -> dict:
    k, d, nd = cfg.num_kv_heads, cfg.head_dim, feature_rows(cfg.head_dim)
    n = len(cfg.retention_layers)
    return {"ret": [jnp.zeros((rows, k, nd, d, d), jnp.float32)
                    for _ in range(n)],
            "retn": [jnp.zeros((rows, k, nd, d), jnp.float32)
                     for _ in range(n)]}


def _coef(head_dim: int) -> jax.Array:
    c = [1.0] + [math.sqrt(2.0)] * (head_dim // 2 - 1) + [1.0]
    return jnp.asarray(c, jnp.float32)


def phi(u: jax.Array) -> jax.Array:
    """[..., D] -> [..., D/2+1, D] float32: row d = c_d u_a u_{a+d}."""
    u = u.astype(jnp.float32)
    d = u.shape[-1]
    turned = jnp.stack([jnp.roll(u, -s, axis=-1)
                        for s in range(feature_rows(d))], axis=-2)
    return _coef(d)[:, None] * u[..., None, :] * turned


def project(h: jax.Array, layer: Params, cfg: ModelConfig,
            positions: jax.Array):
    """h [B,T,E] -> q [B,T,H,D] (scaled), k, v [B,T,K,D] in h's dtype
    and log g [B,T,K] float32."""
    q, k, v = project_qkv(h, layer, cfg, positions)
    log_g = jax.nn.log_sigmoid(
        _einsum("bte,ek->btk", h, layer["g_proj"]).astype(jnp.float32))
    return q.astype(h.dtype), k, v, log_g


def _out(y: jax.Array, layer: Params, dtype) -> jax.Array:
    return _einsum("bthd,hde->bte", y.astype(dtype), layer["o_proj"],
                   tp="row").astype(dtype)


def _quotient(num: jax.Array, den: jax.Array) -> jax.Array:
    # den == 0 only where nothing has been seen (a pad row on a zero
    # state): every weight is >= 0. No epsilon where it is defined.
    return num / jnp.where(den == 0.0, 1.0, den)[..., None]


# --- one token a row ---------------------------------------------------------


def step_rows(q, k, v, log_g, ret, retn):
    """The recurrence for B rows whose states are given: q [B,K,R,D],
    k, v [B,K,D], log_g [B,K], ret [B,K,ND,D,D], retn [B,K,ND,D]; all
    float32 -> (y [B,K,R,D], ret, retn)."""
    g = jnp.exp(log_g)
    fk, fq = phi(k), phi(q)                       # [B,K,ND,D] [B,K,R,ND,D]
    ret = g[..., None, None, None] * ret \
        + v[:, :, None, :, None] * fk[:, :, :, None, :]
    retn = g[..., None, None] * retn + fk
    num = jnp.einsum("bkrda,bkdva->bkrv", fq, ret, precision=_HI)
    den = jnp.einsum("bkrda,bkda->bkr", fq, retn, precision=_HI)
    return _quotient(num, den), ret, retn


def retention_step(h: jax.Array, layer: Params, cfg: ModelConfig,
                   positions: jax.Array, ret: jax.Array, retn: jax.Array,
                   rows: jax.Array, active: jax.Array):
    """One decode token a row. h [B,1,E]; ret / retn EVERY slot's state;
    rows [B] the batch rows' state rows (pads: the scratch row);
    `active` False: the row keeps its state. -> (out [B,1,E], ret,
    retn)."""
    b = h.shape[0]
    kh, d = cfg.num_kv_heads, cfg.head_dim
    q, k, v, log_g = project(h, layer, cfg, positions)
    q = q[:, 0].astype(jnp.float32).reshape(b, kh, -1, d)
    keep = active[:, None]
    k = jnp.where(keep[..., None], k[:, 0].astype(jnp.float32), 0.0)
    v = v[:, 0].astype(jnp.float32)
    log_g = jnp.where(keep, log_g[:, 0], 0.0)
    if kernel.decline_reason(d, q.shape[2]) is None:
        y, ret, retn = kernel.retention_step(q, k, v, log_g, ret, retn,
                                             rows)
    else:
        y, new, newn = step_rows(q, k, v, log_g, ret[rows], retn[rows])
        ret, retn = ret.at[rows].set(new), retn.at[rows].set(newn)
    return _out(y.reshape(b, 1, -1, d), layer, h.dtype), ret, retn


# --- runs of tokens, a chunk at a time ---------------------------------------


def _within(q, k, v, log_g, valid):
    """What lies inside one chunk and touches no state. q [C,K,R,D],
    k, v [C,K,D] float32, log_g [C,K], valid [C] (a prefix) -> (num
    [C,K,R,D], den [C,K,R]: the causal, decayed (q.k)^2 weights times
    [v, 1]; e^b [C,K]: what the state before the chunk is worth at each
    token; w [C,K]: what each key is worth in the state after it; carry
    [K] = e^{b_C})."""
    log_g = jnp.where(valid[:, None], log_g, 0.0)
    b = jnp.cumsum(log_g, axis=0)                           # [C,K]
    c = q.shape[0]
    causal = (jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]) \
        & valid[None, :]
    seg = (b[:, None, :] - b[None, :, :]).transpose(2, 0, 1)  # [K,Ci,Cj]
    decay = jnp.where(causal[None], jnp.exp(
        jnp.where(causal[None], seg, 0.0)), 0.0)
    s = jnp.einsum("ikrd,jkd->krij", q, k, precision=_HI)
    a = decay[:, None] * jnp.square(s)                      # [K,R,Ci,Cj]
    num = jnp.einsum("krij,jkv->ikrv", a, v, precision=_HI)
    den = jnp.sum(a, axis=-1).transpose(2, 0, 1)
    w = jnp.where(valid[:, None], jnp.exp(b[-1][None, :] - b), 0.0)
    return num, den, jnp.exp(b), w, jnp.exp(b[-1])


def _chunk(q, k, v, log_g, valid, s_in, z_in):
    """One chunk of one sequence. q [C,K,R,D], k, v [C,K,D] float32,
    log_g [C,K], valid [C] (a prefix), s_in [K,ND,D,D], z_in [K,ND,D]
    -> (y [C,K,R,D], s_out, z_out)."""
    num, den, eb, w, carry = _within(q, k, v, log_g, valid)
    fq = phi(q) * eb[:, :, None, None, None]              # [C,K,R,ND,D]
    num = num + jnp.einsum("ikrda,kdva->ikrv", fq, s_in, precision=_HI)
    den = den + jnp.einsum("ikrda,kda->ikr", fq, z_in, precision=_HI)
    fk = phi(k) * w[:, :, None, None]                     # [C,K,ND,D]
    s_out = carry[:, None, None, None] * s_in \
        + jnp.einsum("jkv,jkda->kdva", v, fk, precision=_HI)
    z_out = carry[:, None, None] * z_in + jnp.sum(fk, axis=0)
    return _quotient(num, den), s_out, z_out


def _chunk_in_place(q, k, v, log_g, valid, ret, retn, slot):
    """`_chunk` on the slot arrays through the chunk kernel: the state
    is read once and written once, in place -> (y, ret, retn)."""
    num, den, eb, w, carry = _within(q, k, v, log_g, valid)
    heads_first = (1, 0, 2)                                 # [C,K,D]
    inter, under, ret, retn = kernel.retention_chunk(
        q.transpose(1, 2, 0, 3), k.transpose(heads_first),
        (k * w[..., None]).transpose(heads_first),
        v.transpose(heads_first), carry, ret, retn, slot)
    num = num + eb[:, :, None, None] * inter.transpose(2, 0, 1, 3)
    den = den + eb[:, :, None] * under.transpose(2, 0, 1)
    return _quotient(num, den), ret, retn


def _retention_runs(q, k, v, log_g, runs: dict, ret, retn, snaps, chunk):
    """Every run of the flat buffer through its slot's state.

    q [T,H,D], k, v [T,K,D], log_g [T,K]; `runs`: row0 [S] (the buffer
    row a run starts at), pos0 [S] (its first token's position), len
    [S] (0: no run), slot [S], cap_n [S] (snapshot after this many
    tokens of the run, a page boundary; 0: none), snap_idx [S];
    snaps (ret, retn) of the store or None. A run's chunks are the
    pages its positions fall in. -> (y [T,H,D] float32, ret, retn,
    snaps)."""
    t, heads, d = q.shape
    kh = k.shape[1]
    n_runs = runs["len"].shape[0]
    steps = t // chunk + 2 * n_runs
    off = runs["pos0"] % chunk
    n_chunks = jnp.where(runs["len"] > 0,
                         (off + runs["len"] + chunk - 1) // chunk, 0)
    upto = jnp.cumsum(n_chunks)
    ci = jnp.arange(steps)
    seq = jnp.minimum(jnp.searchsorted(upto, ci, side="right"), n_runs - 1)
    live = ci < upto[-1]
    kk = ci - (upto[seq] - n_chunks[seq])
    tok0 = jnp.maximum(kk * chunk - off[seq], 0)
    tok1 = jnp.minimum((kk + 1) * chunk - off[seq], runs["len"][seq])
    n_tok = jnp.where(live, tok1 - tok0, 0)
    row0 = runs["row0"][seq] + tok0
    want_cap = snaps is not None
    capture = live & (runs["cap_n"][seq] > 0) & (tok1 == runs["cap_n"][seq])

    def padded(a):
        return jnp.pad(a.astype(jnp.float32),
                       [(0, chunk)] + [(0, 0)] * (a.ndim - 1))

    qp = padded(q.reshape(t, kh, heads // kh, d))
    kp, vp, gp = padded(k), padded(v), padded(log_g)

    def window(a, at):
        return jax.lax.dynamic_slice_in_dim(a, at, chunk, 0)

    in_place = chunk == kernel.LANES and kernel.decline_reason(
        d, heads // kh) is None

    def advance(carry, at, n, slot, cap, snap):
        y_all, ret, retn, snaps = carry
        valid = jnp.arange(chunk) < n
        operands = (window(qp, at), window(kp, at), window(vp, at),
                    window(gp, at), valid)
        if in_place:
            y, ret, retn = _chunk_in_place(*operands, ret, retn, slot)
        else:
            y, s_out, z_out = _chunk(
                *operands,
                jax.lax.dynamic_index_in_dim(ret, slot, 0, keepdims=False),
                jax.lax.dynamic_index_in_dim(retn, slot, 0, keepdims=False))
            ret = jax.lax.dynamic_update_index_in_dim(ret, s_out, slot, 0)
            retn = jax.lax.dynamic_update_index_in_dim(retn, z_out, slot,
                                                       0)
        y_all = jax.lax.dynamic_update_slice_in_dim(
            y_all, jnp.where(valid[:, None, None, None], y,
                             window(y_all, at)), at, 0)
        if want_cap:
            s_out = jax.lax.dynamic_index_in_dim(ret, slot, 0,
                                                 keepdims=False)
            z_out = jax.lax.dynamic_index_in_dim(retn, slot, 0,
                                                 keepdims=False)
            snaps = jax.lax.cond(
                cap, lambda sn: (
                    jax.lax.dynamic_update_index_in_dim(sn[0], s_out,
                                                        snap, 0),
                    jax.lax.dynamic_update_index_in_dim(sn[1], z_out,
                                                        snap, 0)),
                lambda sn: sn, snaps)
        return y_all, ret, retn, snaps

    def body(carry, xs):
        at, n, slot, cap, snap = xs
        return jax.lax.cond(
            n > 0, lambda c: advance(c, at, n, slot, cap, snap),
            lambda c: c, carry), None

    y0 = jnp.zeros((t + chunk, kh, heads // kh, d), jnp.float32)
    (y_all, ret, retn, snaps), _ = jax.lax.scan(
        body, (y0, ret, retn, snaps if want_cap else ()),
        (row0, n_tok, runs["slot"][seq], capture, runs["snap_idx"][seq]))
    return (y_all[:t].reshape(t, heads, d), ret, retn,
            snaps if want_cap else None)


def retention_prefill(h: jax.Array, layer: Params, cfg: ModelConfig,
                      positions: jax.Array, ret: jax.Array,
                      retn: jax.Array, rows: jax.Array,
                      lengths: jax.Array, chunk: int,
                      snaps: Optional[tuple] = None,
                      cap_len: Optional[jax.Array] = None,
                      snap_idx: Optional[jax.Array] = None):
    """A retention mixer over [B, T] rows, each from its own slot's
    state (row `rows[b]` of ret / retn; a row with lengths 0 touches
    none). With `snaps` (the store's arrays for this layer) the state
    after `cap_len` tokens goes to `snap_idx` (cap_len 0: none). ->
    (out [B,T,E], ret, retn, snaps)."""
    b, t, _e = h.shape
    q, k, v, log_g = project(h, layer, cfg, positions)
    zero = jnp.zeros((b,), jnp.int32)
    runs = {"row0": jnp.arange(b, dtype=jnp.int32) * t,
            "pos0": positions[:, 0], "len": lengths, "slot": rows,
            "cap_n": zero if cap_len is None else cap_len,
            "snap_idx": zero if snap_idx is None else snap_idx}
    flat = [a.reshape(b * t, *a.shape[2:]) for a in (q, k, v, log_g)]
    y, ret, retn, snaps = _retention_runs(*flat, runs, ret, retn, snaps,
                                          chunk)
    return (_out(y.reshape(b, t, *y.shape[1:]), layer, h.dtype), ret,
            retn, snaps)


def single_token_runs(q, k, v, log_g, row0, single, slot, ret, retn, *,
                      interpret: Optional[bool] = None):
    """The runs of ONE token (the decode rows that ride a join's
    dispatch) through the step kernel, the `single` sequences first and
    only they visited: a run of one token is one pass over its state,
    not a chunk's products. q [T,H,D], k, v [T,K,D], log_g [T,K]; row0,
    single, slot [S]. -> (y [S,H,D] float32 — rows of sequences that are
    not `single`: undefined — order [S], ret, retn)."""
    kh, d = k.shape[1], k.shape[2]
    order = jnp.argsort(~single)                 # stable: singles first
    at = row0[order]
    y, ret, retn = kernel.retention_step(
        q[at].astype(jnp.float32).reshape(at.shape[0], kh, -1, d),
        k[at].astype(jnp.float32), v[at].astype(jnp.float32), log_g[at],
        ret, retn, slot[order], live=jnp.sum(single, dtype=jnp.int32),
        interpret=interpret)
    return y.reshape(at.shape[0], -1, d), order, ret, retn


def retention_ragged(h: jax.Array, layer: Params, cfg: ModelConfig,
                     positions: jax.Array, ret: jax.Array,
                     retn: jax.Array, rg: dict, chunk: int, snaps: tuple,
                     snap_idx: jax.Array):
    """A retention mixer over the flat token buffer. h [1,T,E]; `rg`
    is `hybrid.ragged_meta`'s (seq_start, seq_len, seq_slot, cap_n and
    each sequence's first position `seq_pos0`): every sequence's run
    restarts from its slot's row and leaves it advanced; a run with
    cap_n leaves the state at that page boundary in `snaps` at
    `snap_idx`. Where the step kernel serves, the runs of one token go
    through it. -> (out [1,T,E], ret, retn, snaps)."""
    q, k, v, log_g = project(h, layer, cfg, positions)
    # A sequence without a slot (the inert one every pad points at, the
    # unused ones) has the scratch row: no run.
    real = rg["seq_slot"] < ret.shape[0] - 1
    length = jnp.where(real, rg["seq_len"], 0)
    stepped = kernel.decline_reason(cfg.head_dim, cfg.kv_repeat) is None
    if stepped:
        # (a capture is a chunk's: a one-token run that ends on a page
        # boundary stays with the chunks)
        single = (length == 1) & (rg["cap_n"] == 0)
        y1, order, ret, retn = single_token_runs(
            q[0], k[0], v[0], log_g[0], rg["seq_start"], single,
            rg["seq_slot"], ret, retn)
        length = jnp.where(single, 0, length)
    runs = {"row0": rg["seq_start"], "pos0": rg["seq_pos0"],
            "len": length, "slot": rg["seq_slot"],
            "cap_n": rg["cap_n"], "snap_idx": snap_idx}
    y, ret, retn, snaps = _retention_runs(q[0], k[0], v[0], log_g[0], runs,
                                          ret, retn, snaps, chunk)
    if stepped:
        rows = jnp.where(single[order], rg["seq_start"][order], y.shape[0])
        y = y.at[rows].set(y1, mode="drop")
    return _out(y[None], layer, h.dtype), ret, retn, snaps
