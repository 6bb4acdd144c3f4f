"""The page copier (engine/paging.py: `copy_pages_fn`) as DMAs in place:
page `src` of every pool of every layer onto page `dst` of the same
pool, HBM to HBM, and nothing else touched — a call costs the bytes of
the pages it copies, whatever the pools' geometry.

XLA's program for the same copy, `p.at[dst_ids].set(p[src_ids])` for
every pool (engine.py: `scatter_pages`), is a gather and a scatter
whose cost follows the pool's layout and not the pages: over
`bf16[1024, 128, 4, 128]` the scatter wants its operand head-major and
every call copies the whole pool in and out again (PERF.md, PR 45). Here the pools stay where they
are (`pl.ANY`, each aliased to its output), the ids arrive as scalar
prefetch, and one pair of the call is one DMA a pool, two pairs'
worth in flight.

The contract is `paging.plan_copy_calls`'s: the pairs of a call are
independent — no destination is a source of the call or named twice —
so any order of the DMAs leaves the same bytes. A pair onto itself is
a pad row (the caller pads to a width with a scratch page) and moves
nothing.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PATH = "dma"


def decline_reason(pools: list) -> Optional[str]:
    """Why the DMA copier does not serve these pools (the flat list of
    every layer's pool arrays) here (None: it does); XLA's gather and
    scatter then makes the same copies. One rule for
    `describe()["declines"]` and the call, read off the arrays and the
    backend. (No comma in a reason: it is a label of the programs'
    series too.)"""
    if jax.default_backend() != "tpu":
        return "not on a TPU (no Mosaic): XLA's gather and scatter"
    if not pools:
        return "no pool holds a byte: a copy moves nothing"
    if any(len(p.sharding.device_set) > 1 for p in pools):
        return "pools sharded over a mesh: XLA's gather and scatter"
    for p in pools:
        layout = p.format.layout
        name = f"{p.dtype.name}[{'x'.join(map(str, p.shape))}]"
        order = tuple(layout.major_to_minor)
        if order != tuple(range(p.ndim)):
            # A kernel's operand is row-major: XLA would re-lay the
            # whole pool out on the way in and out, every call.
            return (f"a pool {name} stored in the order "
                    f"{'-'.join(map(str, order))} and not row-major: "
                    "XLA's gather and scatter")
        tile = tuple(layout.tiling[0]) if layout.tiling else ()
        if any(n % t for n, t in zip(p.shape[p.ndim - len(tile):], tile)):
            # Mosaic refuses a slice that ends inside a tile.
            return (f"a page of {name} does not fill whole "
                    f"{'x'.join(map(str, tile))} tiles: XLA's gather "
                    "and scatter")
    return None


def _kernel(ids, *refs, n_pools: int):
    pools, sem = refs[n_pools:2 * n_pools], refs[2 * n_pools]
    width = ids.shape[1]

    def copies(i, go: bool):
        src, dst = ids[0, i], ids[1, i]

        @pl.when(src != dst)
        def _():
            for n, pool in enumerate(pools):
                c = pltpu.make_async_copy(pool.at[src], pool.at[dst],
                                          sem.at[i % 2, n])
                c.start() if go else c.wait()

    def pair(i, _):
        copies(i, True)

        @pl.when(i > 0)
        def _():
            copies(i - 1, False)
        return 0

    lax.fori_loop(0, width, pair, 0)
    copies(width - 1, False)


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("interpret",))
def copy_pages(pools: list, src_ids: jax.Array, dst_ids: jax.Array,
               *, interpret: bool = False) -> list:
    """pools: the cache's pool tree (a tuple of `[P, ...]` arrays a
    layer), donated. -> the same tree, page dst_ids[i] of every pool
    holding what page src_ids[i] of that pool held."""
    flat, tree = jax.tree.flatten(pools)
    n = len(flat)
    if not n:
        return pools
    ids = jnp.stack([src_ids, dst_ids]).astype(jnp.int32)
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(_kernel, n_pools=n),
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in flat],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[any_space] * n, out_specs=[any_space] * n,
            scratch_shapes=[pltpu.SemaphoreType.DMA((2, n))]),
        # Operands count the prefetched ids: pool i is operand 1 + i.
        input_output_aliases={1 + i: i for i in range(n)},
        interpret=interpret, name="page_copy",
    )(ids, *flat)
    return jax.tree.unflatten(tree, out)
