"""A grouped matrix product for the routed experts (models/hybrid.py:
routed_experts): rows sorted by group, each multiplied by its OWN
group's matrix, in one Pallas call a projection.

    out[r] = rows[r] @ weights[g]   for offsets[g] <= r < offsets[g+1]

The algorithm is megablox's (jax.experimental.pallas.ops.tpu.megablox):
the rows are cut into tiles of `tm`; a VISIT is one (group, row tile)
pair with rows in common, visits run in row order, the grid's middle
axis walks them, and a visit multiplies the whole tile by the group's
matrix and stores the rows that are the group's. A group with no rows is
never visited, so its matrix is never read: a decode step reads the
experts it hit. What differs from the library's `gmm`, and why it is not
called: the visits are computed ONCE a layer (`group_visits`) and shared
by the two or three products, from a dozen primitives where the library
recomputes them inside every call from some forty jitted `jnp`
functions — every start of the process lowers all of that again in every
step program (only the compile is cached), which is what put 8 s on
`setup_s` in PR 34 and 5-7 s with `gmm` called here (PERF.md, PR 36) —
and the call carries a `name=`.

Rows past the last group's end (another chip's assignments, padding)
belong to no visit; their output is undefined and the caller masks it.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_TILE = 128


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def decline_reason(k: int, n: int, dtype) -> Optional[str]:
    """Why the kernel does not serve a grouped product of these widths
    here (None: it does); `lax.ragged_dot` then computes the same
    product. One rule for `describe()["declines"]` and the call."""
    if _interpret():
        return "not on a TPU (no Mosaic): lax.ragged_dot"
    if dtype not in (jnp.bfloat16, jnp.float32):
        return f"rows in {jnp.dtype(dtype).name}: lax.ragged_dot"
    if min(k, n) < 128:
        return f"widths {k} x {n} under a lane row: lax.ragged_dot"
    return None


def padded_rows(m: int) -> int:
    """Rows the caller pads to: whole row tiles, or for fewer rows than
    one tile a multiple of 16 (a bfloat16 sublane pair)."""
    step = ROW_TILE if m > ROW_TILE else 16
    return -(-m // step) * step


def tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    """(row, contraction, column) tile from the shapes alone. Rows:
    ROW_TILE — the groups are small (a held expert sees a few rows of a
    decode step, tens of a join), and a tile is multiplied whole once
    for every group with rows in it. Contraction: all of it while 512
    columns of it are a block of 3 MiB or less, else its largest divisor
    in whole lane rows up to 2048. Columns: 1024 where that block stays
    within 4 MiB, else 512 — an expert's matrix arrives in blocks of
    1-4 MiB and the rows are read again once a column block, not more
    (kernel alone at the three cells' widths: PERF.md, PR 36)."""
    tk = k
    if k % 128 == 0 and k > 3072:
        tk = max(128 * d for d in range(1, k // 128 + 1)
                 if (k // 128) % d == 0 and 128 * d <= 2048)
    tn = 1024 if tk * 1024 * 2 <= 4 << 20 else 512
    return min(m, ROW_TILE), tk, min(-(-n // 128) * 128, tn)


def group_visits(sizes: jax.Array, m: int):
    """sizes int32[G] (rows a group, in group order from row 0) ->
    (offsets [G+1], group of visit [V], row tile of visit [V], visits
    [1]) with V = m / tm + G - 1, the most there can be: a group's
    visits are the tiles from its first row's to its last row's. Past
    `visits` the arrays repeat a valid pair (never run: the grid ends at
    `visits`)."""
    g = sizes.shape[0]
    tm = min(m, ROW_TILE)
    tiles = m // tm
    ends = lax.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    count = lax.select(sizes > 0, (ends - 1) // tm - first + 1,
                       jnp.zeros_like(sizes))
    upto = lax.cumsum(count)                       # visits through group g
    v = lax.iota(jnp.int32, tiles + g - 1)
    # group of visit v: the first whose running count passes v.
    later = (v[:, None] >= upto[None, :]).astype(jnp.int32)
    gid = lax.min(later.sum(axis=1), jnp.int32(g - 1))
    pick = gid[:, None] == lax.iota(jnp.int32, g)[None, :]
    before = jnp.where(pick, (upto - count)[None, :], 0).sum(axis=1)
    tile = jnp.where(pick, first[None, :], 0).sum(axis=1) + v - before
    tile = lax.clamp(jnp.int32(0), tile, jnp.int32(tiles - 1))
    offsets = lax.concatenate([jnp.zeros((1,), jnp.int32), ends], 0)
    return offsets, gid, tile, upto[-1:]


def _kernel(offsets, gids, tiles, visits, rows, w, out, acc, *,
            tm: int, tn: int, tiles_k: int, transposed: bool):
    del visits
    v, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += lax.dot_general(
        rows[...], w[...],
        (((1,), (1 if transposed else 0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == tiles_k - 1)
    def _():
        g = gids[v]
        row = tiles[v] * tm + lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
        mine = (row >= offsets[g]) & (row < offsets[g + 1])
        out[...] = jnp.where(mine, acc[...], out[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def grouped_matmul(rows: jax.Array, weights: jax.Array, visits, *,
                   interpret: bool = False) -> jax.Array:
    """rows [m,K] (m whole row tiles: `padded_rows`), weights [G,K,N],
    visits = group_visits(sizes, m) -> [m,N] float32. A jit of its own:
    an expert layer's projections of one shape (gate and up) are one
    trace and one lowering of the kernel."""
    (m, k), n = rows.shape, weights.shape[2]
    tm, tk, tn = tiling(m, k, n)
    assert m % tm == 0 and k % tk == 0, (m, k, tm, tk)
    # The chip stores a matrix whose columns fill no whole lane rows
    # contraction-minor (nemotron_h's up: [64, 2688, 1856] lies as
    # [64, 1856, 2688]); the kernel takes it as it lies. Asked for the
    # other way it is copied whole before every call: 638 MB, 2.0 ms of a
    # 2.6 ms product (PERF.md, PR 36).
    transposed = bool(n % 128) and not k % 128
    if transposed:
        weights = jnp.swapaxes(weights, 1, 2)
        w_spec = pl.BlockSpec(
            (None, tn, tk), lambda ni, v, ki, off, gid, tile, nv:
            (gid[v], ni, ki))
    else:
        w_spec = pl.BlockSpec(
            (None, tk, tn), lambda ni, v, ki, off, gid, tile, nv:
            (gid[v], ki, ni))
    tiles_k = k // tk
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, tn=tn, tiles_k=tiles_k,
                          transposed=transposed),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(-(-n // tn), visits[3][0], tiles_k),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda ni, v, ki, off, gid, tile,
                             nv: (tile[v], ki)),
                w_spec],
            out_specs=pl.BlockSpec((tm, tn), lambda ni, v, ki, off, gid,
                                   tile, nv: (tile[v], ni)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret, name="grouped_matmul",
    )(*visits, rows, weights)
