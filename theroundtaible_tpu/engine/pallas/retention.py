"""The decode step of a power-retention layer (models/retention.py) as
ONE pass over a state, in place:

    S <- g S + phi(k) (x) v      Z <- g Z + phi(k)
    y_n = phi(q_n) . S / phi(q_n) . Z        for the kv head's R queries

One grid step is one (batch row, kv head): its S [D/2+1, D(v), D(a)]
arrives as one block (4.26 MB at D = 128), chosen by the row's STATE ROW
(scalar prefetch: the batch's rows are scattered over the slot array),
and leaves through the same buffer (`input_output_aliases`): each state
byte is read once and written once, and nothing gathers or scatters the
batch's rows. phi is built a row at a time inside — row d of phi(u) is
c_d u rotated by d lanes times u — so neither phi(q) nor phi(k) exists
outside a register. XLA's two fusions for the same step (the update,
then the product) read S twice.

A row that must not advance rides with g = 1 and k = 0. Pad rows all
point at the scratch state row: their blocks overlap and hold nothing
anyone reads.

`retention_chunk` is the same pass for a CHUNK of one sequence (a join's
or a prologue's 128 tokens, models/retention.py: _retention_runs): per
kv head, with S_in the state before the chunk,

    inter_i = phi(q_i) . S_in   (numerator and, with Z_in, denominator)
    S_out   = e^{b_C} S_in + sum_j w_j phi(k_j) (x) v_j

a row of phi at a time: one product of the chunk's 5 x 128 rotated
queries with S_d, one of V^T with the rotated weighted keys into S_d —
phi(Q) (170 MB a chunk a layer in float32) and phi(K) never exist. What
lies inside the chunk (the causal, decayed (q.k)^2 weights) is the
caller's: it touches no state.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
# Two buffers each of the state's block in and out, the accumulators and
# slack: over the compiler's default scoped limit, well under the chip's.
VMEM_LIMIT = 48 << 20


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def decline_reason(head_dim: int, group: int) -> Optional[str]:
    """Why the kernel does not serve a retention step of this geometry
    here (None: it does); the `jax.numpy` recurrence then computes the
    same step. One rule for `describe()["declines"]` and the call."""
    if _interpret():
        return "not on a TPU (no Mosaic): the jax.numpy recurrence"
    if head_dim != LANES:
        return f"head_dim {head_dim} is not a lane row: the jax.numpy " \
            "recurrence"
    if group > SUBLANES:
        return f"group {group} over {SUBLANES} query heads a kv head: " \
            "the jax.numpy recurrence"
    return None


def _turned(u, d, width: int, interpret: bool):
    """u [r, D] -> u_{(a+d) mod D} at lane a."""
    if interpret:
        return jnp.roll(u, -d, axis=1)
    return pltpu.roll(u, (width - d) % width, 1)


def _kernel(rows, q_ref, kvg_ref, s_in, z_in, y_ref, s_out, z_out, acc,
            *, group: int, interpret: bool):
    del rows
    nd, width = s_in.shape[0], s_in.shape[2]
    q = q_ref[...]                                         # [8, D]
    k, v, g = kvg_ref[0:1, :], kvg_ref[1:2, :], kvg_ref[2:3, :]
    # v down the sublanes, the same in every lane.
    v_col = jnp.broadcast_to(v, (width, width)).T
    acc[...] = jnp.zeros_like(acc)
    root2 = jnp.float32(math.sqrt(2.0))

    def row(d, den):
        c = jnp.where((d == 0) | (d == nd - 1), jnp.float32(1.0), root2)
        fk = c * k * _turned(k, d, width, interpret)       # [1, D]
        fq = c * q * _turned(q, d, width, interpret)       # [8, D]
        s_new = g * s_in[d] + v_col * fk                   # [D(v), D(a)]
        s_out[d] = s_new
        z_new = g * z_in[pl.ds(d, 1), :] + fk
        z_out[pl.ds(d, 1), :] = z_new
        for n in range(group):
            acc[n] += fq[n:n + 1, :] * s_new
        return den + fq * z_new

    den = lax.fori_loop(0, nd, row, jnp.zeros_like(q))
    den = jnp.sum(den, axis=1, keepdims=True)              # [8, 1]
    den = jnp.where(den == 0.0, 1.0, den)
    ones = jnp.ones((SUBLANES, width), jnp.float32)
    y_ref[...] = jnp.zeros_like(y_ref)
    for n in range(group):
        # Sum over the lanes (a), v from the sublanes to the lanes: a
        # product with ones, contracting both last dimensions.
        num = lax.dot_general(ones, acc[n], (((1,), (1,)), ((), ())),
                              precision=lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
        y_ref[n:n + 1, :] = num[0:1, :] / den[n:n + 1, :]


def retention_step(q: jax.Array, k: jax.Array, v: jax.Array,
                   log_g: jax.Array, ret: jax.Array, retn: jax.Array,
                   rows: jax.Array, *, live: Optional[jax.Array] = None,
                   interpret: Optional[bool] = None):
    """q [B,K,R,D], k, v [B,K,D], log_g [B,K] float32; ret [rows,K,D/2+1,
    D,D] / retn [rows,K,D/2+1,D] EVERY slot's state; rows [B] int32 the
    state row of each batch row -> (y [B,K,R,D], ret, retn), the two
    arrays updated in place. With `live` (a traced count) only the
    first `live` batch rows are visited: the others' states are not
    read, and their y is undefined."""
    b, kh, group, d = q.shape
    nd = ret.shape[2]
    if interpret is None:
        interpret = _interpret()
    q8 = jnp.pad(q, [(0, 0), (0, 0), (0, SUBLANES - group), (0, 0)])
    kvg = jnp.stack([k, v, jnp.broadcast_to(
        jnp.exp(log_g)[..., None], k.shape)], axis=2)
    kvg = jnp.pad(kvg, [(0, 0), (0, 0), (0, SUBLANES - 3), (0, 0)])

    def small(bi, mi, rows):
        return bi, mi, 0, 0

    def state(bi, mi, rows):
        return rows[bi], mi, 0, 0, 0

    def norm(bi, mi, rows):
        return rows[bi], mi, 0, 0

    y, ret, retn = pl.pallas_call(
        functools.partial(_kernel, group=group, interpret=interpret),
        out_shape=(jax.ShapeDtypeStruct((b, kh, SUBLANES, d), jnp.float32),
                   jax.ShapeDtypeStruct(ret.shape, jnp.float32),
                   jax.ShapeDtypeStruct(retn.shape, jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b if live is None else live, kh),
            in_specs=[pl.BlockSpec((None, None, SUBLANES, d), small),
                      pl.BlockSpec((None, None, SUBLANES, d), small),
                      pl.BlockSpec((None, None, nd, d, d), state),
                      pl.BlockSpec((None, None, nd, d), norm)],
            out_specs=[pl.BlockSpec((None, None, SUBLANES, d), small),
                       pl.BlockSpec((None, None, nd, d, d), state),
                       pl.BlockSpec((None, None, nd, d), norm)],
            scratch_shapes=[pltpu.VMEM((group, d, d), jnp.float32)]),
        # Operands count the prefetched rows: ret is 3, retn 4.
        input_output_aliases={3: 1, 4: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name="retention_step",
    )(rows.astype(jnp.int32), q8, kvg, ret, retn)
    return y[:, :, :group], ret, retn


def _chunk_kernel(slot, q_ref, k_ref, kw_ref, vt_ref, carry_ref, s_in, z_in,
                  num_ref, den_ref, s_out, z_out, *, interpret: bool):
    del slot
    nd, width = s_in.shape[0], s_in.shape[2]
    q, k, kw, vt = q_ref[...], k_ref[...], kw_ref[...], vt_ref[...]
    carry = carry_ref[0:1, :]                              # [1, D]
    num_ref[...] = jnp.zeros_like(num_ref)
    den_ref[...] = jnp.zeros_like(den_ref)
    root2 = jnp.float32(math.sqrt(2.0))

    def row(d, _):
        c = jnp.where((d == 0) | (d == nd - 1), jnp.float32(1.0), root2)
        fq = c * q * _turned(q, d, width, interpret)       # [R C, D(a)]
        s_d = s_in[d]                                      # [D(v), D(a)]
        z_d = z_in[pl.ds(d, 1), :]
        num_ref[...] += lax.dot_general(
            fq, s_d, (((1,), (1,)), ((), ())),
            precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)            # [R C, D(v)]
        den_ref[...] += fq * z_d
        fk = c * kw * _turned(k, d, width, interpret)      # [C, D(a)]
        s_out[d] = carry * s_d + jnp.dot(
            vt, fk, precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        z_out[pl.ds(d, 1), :] = carry * z_d + jnp.sum(fk, axis=0,
                                                      keepdims=True)
        return 0

    lax.fori_loop(0, nd, row, 0)


def retention_chunk(q: jax.Array, k: jax.Array, kw: jax.Array, v: jax.Array,
                    carry: jax.Array, ret: jax.Array, retn: jax.Array,
                    slot: jax.Array, *, interpret: Optional[bool] = None):
    """One chunk of one sequence against its slot's state, in place.
    q [K,R,C,D], k, kw (= w k: the key times its weight in S_out, 0 for
    a token past the chunk's end), v [K,C,D], carry [K] (= e^{b_C}), all
    float32; ret / retn EVERY slot's state; slot int32 the sequence's
    state row -> (num [K,R,C,D] = phi(q) . S_in, den [K,R,C] =
    phi(q) . Z_in, ret, retn)."""
    kh, group, c, d = q.shape
    nd = ret.shape[2]
    if interpret is None:
        interpret = _interpret()
    rows = group * c
    carry8 = jnp.broadcast_to(carry[:, None, None], (kh, SUBLANES, d))

    def head(mi, slot):
        return mi, 0, 0

    def state(mi, slot):
        return slot[0], mi, 0, 0, 0

    def norm(mi, slot):
        return slot[0], mi, 0, 0

    num, den, ret, retn = pl.pallas_call(
        functools.partial(_chunk_kernel, interpret=interpret),
        out_shape=(jax.ShapeDtypeStruct((kh, rows, d), jnp.float32),
                   jax.ShapeDtypeStruct((kh, rows, d), jnp.float32),
                   jax.ShapeDtypeStruct(ret.shape, jnp.float32),
                   jax.ShapeDtypeStruct(retn.shape, jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(kh,),
            in_specs=[pl.BlockSpec((None, rows, d), head),
                      pl.BlockSpec((None, c, d), head),
                      pl.BlockSpec((None, c, d), head),
                      pl.BlockSpec((None, d, c), head),
                      pl.BlockSpec((None, SUBLANES, d), head),
                      pl.BlockSpec((None, None, nd, d, d), state),
                      pl.BlockSpec((None, None, nd, d), norm)],
            out_specs=[pl.BlockSpec((None, rows, d), head),
                       pl.BlockSpec((None, rows, d), head),
                       pl.BlockSpec((None, None, nd, d, d), state),
                       pl.BlockSpec((None, None, nd, d), norm)]),
        # Operands count the prefetched slot: ret is 6, retn 7.
        input_output_aliases={6: 2, 7: 3},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name="retention_chunk",
    )(jnp.reshape(slot, (1,)).astype(jnp.int32), q.reshape(kh, rows, d), k,
      kw, jnp.swapaxes(v, 1, 2), carry8, ret, retn)
    return (num.reshape(kh, group, c, d),
            jnp.sum(den, axis=-1).reshape(kh, group, c), ret, retn)
