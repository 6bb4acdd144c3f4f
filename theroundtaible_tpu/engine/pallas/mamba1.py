"""The selective scan of a Mamba-1 layer (models/mamba1.py) as ONE kernel
over a dispatch's token buffer, the state in place:

    S_t[n, d] = exp(dt_t[d] A[n, d]) S_{t-1}[n, d] + dt_t[d] c_t[d] B_t[n]
    y_t[d]    = sum_n S_t[n, d] C_t[n]

The decay is a value for every (state index, channel) pair, so the scan
has no product form for the MXU: it is exponentials and multiply-adds on
the vector units, a token after the other. XLA's two ways both lose — an
associative scan writes [T, N, d_inner] float32 to HBM several times
(328 KB a token a layer at the published widths), a `lax.scan` over
tokens is T dependent steps a layer — so the kernel keeps a sequence's
state [N, d_inner] in VMEM while its tokens pass: `dt`, `c`, `B`, `C`
and the state are read once, `y` and the state written once.

**Layout.** Channels are folded onto whole registers: d_inner =
G x 128 lanes, the state of a sequence [N, G, 128], so that for one
state index n and eight lane rows the decay, the state and the input
are one register each and `B_t[n]`, `C_t[n]` are SCALARS (read from
SMEM): every product is a register times a register or a scalar, the
sum over n a chain of adds, no broadcast along lanes and no reduction
across sublanes.

**The buffer.** Tokens come as blocks of `block` rows that belong to one
sequence each (serving_loop.build_ragged_batch: RAGGED_BLOCK_Q; a
prologue's [B, T] rows are B x T / block such blocks). One grid step is
one block: the state's block is chosen by the block's STATE ROW (scalar
prefetch) out of EVERY slot's state [rows, layers, N, G, 128] and leaves
through the same buffer (`input_output_aliases`), so a run restarts from
its slot's row where the row changes and nothing gathers or scatters
states. A token with dt = 0 is the identity on the state (pads, rows
that must not advance). Where a block holds the token after which a
snapshot is due (`block_cap` >= 0) the state there goes to `caps`, one
row a sequence.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
# The state's block in and out and the capture's, two buffers each
# (0.33 MB a block at the published widths), the running state, the
# token blocks: far under the chip's, over nothing.
VMEM_LIMIT = 32 << 20
# A one-dimensional SMEM operand is tiled by 1024 words: B and C of
# 1024 / 2N tokens arrive a time, and a block reads its own part.
SMEM_TILE = 1024


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def fold(d_inner: int) -> tuple[int, int]:
    """d_inner as (lane rows, lanes): whole registers where it can be."""
    if d_inner % LANES == 0:
        return d_inner // LANES, LANES
    return 1, d_inner


def decline_reason(d_inner: int, d_state: int) -> Optional[str]:
    """Why the kernel does not serve a scan of this geometry here (None:
    it does); `models/mamba1.scan_reference` then computes the same
    scan. One rule for `describe()["declines"]` and the call."""
    if _interpret():
        return "not on a TPU (no Mosaic): the jax.numpy scan"
    if SMEM_TILE % (2 * d_state * SUBLANES):
        return (f"B and C of an 8-token block ({16 * d_state} scalars) do "
                f"not tile {SMEM_TILE} SMEM words: the jax.numpy scan")
    if d_inner % (LANES * SUBLANES):
        return (f"d_inner {d_inner} is not whole registers of "
                f"{LANES * SUBLANES} channels: the jax.numpy scan")
    return None


def _kernel(layer, slots, caps, seqs, dt_ref, c_ref, bc_ref, a_ref, s_in,
            y_ref, s_out, cap_ref, run, *, block: int, d_state: int,
            tile: int):
    del layer, seqs
    i = pl.program_id(0)
    slot = slots[i]
    fresh = jnp.logical_or(i == 0, slots[jnp.maximum(i - 1, 0)] != slot)

    @pl.when(fresh)
    def _():
        run[...] = s_in[...]

    cap_at = caps[i]
    n_tiles = run.shape[1] // tile
    per = block * 2 * d_state
    # Where this block's B and C begin in the SMEM tile it shares.
    base = (i % (SMEM_TILE // per)) * per

    def lane_rows(g, carry):
        rows = pl.ds(pl.multiple_of(g * tile, tile), tile)
        a = [a_ref[n, rows, :] for n in range(d_state)]
        s = [run[n, rows, :] for n in range(d_state)]
        for t in range(block):
            dt, c = dt_ref[t, rows, :], c_ref[t, rows, :]
            dc = dt * c
            y = None
            for n in range(d_state):
                s[n] = jnp.exp(dt * a[n]) * s[n] \
                    + dc * bc_ref[base + t * 2 * d_state + n]
                term = s[n] * bc_ref[base + (t * 2 + 1) * d_state + n]
                y = term if y is None else y + term
            y_ref[t, rows, :] = y

            @pl.when(cap_at == t)
            def _():
                for n in range(d_state):
                    cap_ref[n, rows, :] = s[n]
        for n in range(d_state):
            run[n, rows, :] = s[n]
        return carry

    lax.fori_loop(0, n_tiles, lane_rows, 0)
    s_out[...] = run[...]


@functools.partial(jax.jit,
                   static_argnames=("block", "n_seqs", "interpret"))
def mamba1_scan(dt: jax.Array, c: jax.Array, bc: jax.Array, a: jax.Array,
                state: jax.Array, layer: jax.Array, block_slot: jax.Array,
                block_cap: jax.Array, block_seq: jax.Array, *, block: int,
                n_seqs: int, interpret: Optional[bool] = None):
    """dt, c [T,G,W] float32 (dt 0: the identity); bc [T,2N] (B then C);
    a [N,G,W] (= -exp(A_log)); state [rows,layers,N,G,W] EVERY slot's,
    updated in place at `layer`; per block of `block` tokens:
    block_slot (its sequence's state row), block_cap (the index in the
    block of the token after which the state is captured; -1: none),
    block_seq (the row of `caps`, of `n_seqs`, the capture goes to).
    -> (y [T,G,W], state, caps [n_seqs,N,G,W]: rows without a capture
    hold garbage).

    A jit of its own, as `pallas.attention._ragged_walk` is: a model's
    Mamba layers call it with the same shapes, so it is traced once a
    process and lowered once a program."""
    t, g, w = dt.shape
    n = a.shape[0]
    nb = t // block
    if interpret is None:
        interpret = _interpret()
    tile = SUBLANES if g % SUBLANES == 0 else g

    def tokens(i, layer, slots, caps, seqs):
        return i, 0, 0

    per = block * 2 * n
    if SMEM_TILE % per:
        raise ValueError(f"mamba1_scan: {block} tokens of 2 x {n} scalars "
                         f"do not tile {SMEM_TILE} SMEM words")
    flat = bc.reshape(t * 2 * n)
    flat = jnp.pad(flat, (0, -flat.shape[0] % SMEM_TILE))

    def scalars(i, layer, slots, caps, seqs):
        return (i * per // SMEM_TILE,)

    def whole(i, layer, slots, caps, seqs):
        return 0, 0, 0

    def row(i, layer, slots, caps, seqs):
        return slots[i], layer[0], 0, 0, 0

    def cap(i, layer, slots, caps, seqs):
        return seqs[i], 0, 0, 0

    y, state, held = pl.pallas_call(
        functools.partial(_kernel, block=block, d_state=n, tile=tile),
        out_shape=(jax.ShapeDtypeStruct((t, g, w), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32),
                   jax.ShapeDtypeStruct((n_seqs, n, g, w), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(nb,),
            in_specs=[pl.BlockSpec((block, g, w), tokens),
                      pl.BlockSpec((block, g, w), tokens),
                      pl.BlockSpec((SMEM_TILE,), scalars,
                                   memory_space=pltpu.SMEM),
                      pl.BlockSpec((n, g, w), whole),
                      pl.BlockSpec((None, None, n, g, w), row)],
            out_specs=[pl.BlockSpec((block, g, w), tokens),
                       pl.BlockSpec((None, None, n, g, w), row),
                       pl.BlockSpec((None, n, g, w), cap)],
            scratch_shapes=[pltpu.VMEM((n, g, w), jnp.float32)]),
        # Operands count the four prefetched arrays: the state is 8.
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        # (one token a block: a decode step's pass over the slots)
        interpret=interpret,
        name="mamba1_scan" if block > 1 else "mamba1_step",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      block_slot.astype(jnp.int32), block_cap.astype(jnp.int32),
      block_seq.astype(jnp.int32), dt, c, flat, a, state)
    return y, state, held
