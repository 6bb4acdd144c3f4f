"""The page copier as DMAs in place (ISSUE 45, engine/pallas/
page_copy.py) behind a PagedKVCache's queue, against a numpy replay of
the queued pairs made one by one: every pool of every layer, bit for
bit, the pages nobody named included.

On the CPU the kernel runs in interpret mode. On the chip
(`python -m pytest --noconftest tests/test_page_copy.py`: conftest.py
holds JAX to the CPU) it is compiled wherever its own rule lets it
serve, and XLA's gather and scatter runs where it declines — what an
engine does.
"""

import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from theroundtaible_tpu.engine.engine import InferenceEngine
from theroundtaible_tpu.engine.models.registry import get_model_config
from theroundtaible_tpu.engine.pallas import page_copy
from theroundtaible_tpu.engine.paging import PagedKVCache
from theroundtaible_tpu.engine.sampling import SamplingParams

ON_CHIP = jax.default_backend() == "tpu"
PAGES = 72          # a scratch page, 32 sources, 32 destinations, spare
LAYERS = 2

# name -> (a layer's pools, a layer's scale pools): (trailing shape,
# dtype) each. A latent page is A.X-K1's 576 values in whole lane rows.
GEOMETRIES = {
    "gqa_8_heads": ([((128, 8, 128), jnp.bfloat16)] * 2, []),
    "gqa_4_heads": ([((128, 4, 128), jnp.bfloat16)] * 2, []),
    "latent": ([((128, 640), jnp.bfloat16)], []),
    "int8_with_scale_rows": ([((128, 8, 128), jnp.int8)] * 2,
                             [((128, 8, 1), jnp.float32)] * 2),
    "no_pools": ([], []),
}
# What an engine holds in a benchmark cell: the kernel serves them.
SERVED_ON_CHIP = ("gqa_8_heads", "gqa_4_heads", "latent")


def disjoint(n):
    return [([1 + i], [33 + i]) for i in range(n)]


# name -> (_run_page_copy's arguments in queue order, programs)
QUEUES = {
    "1_pair": (disjoint(1), 1),
    "8_pairs": (disjoint(8), 1),
    "9_pairs": (disjoint(9), 1),
    "32_pairs": (disjoint(32), 1),
    "chain": ([([1], [2]), ([2], [3])], 1),
    "destination_twice": ([([1], [2]), ([3], [2])], 1),
    "destination_is_a_source": ([([5, 8], [6, 9]), ([7], [5])], 2),
}


def xla_copier(pools, src, dst):
    return [tuple(p.at[dst].set(p[src]) for p in layer) for layer in pools]


def bits(shape, dtype, rng):
    """An array of `shape` of random bits, the exponent's top bit
    clear: no NaN, whose bits a copy need not keep."""
    raw = rng.integers(0, 256, (*shape, jnp.dtype(dtype).itemsize),
                       dtype=np.uint8)
    raw[..., -1] &= 0xBF
    return jnp.asarray(raw.view(jnp.dtype(dtype)).reshape(shape))


def as_bytes(pool):
    return np.ascontiguousarray(pool).view(np.uint8).reshape(
        pool.shape[0], -1)


@pytest.mark.parametrize("queue", list(QUEUES))
@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_the_copier_leaves_what_copies_made_one_by_one_leave(geometry,
                                                             queue):
    rng = np.random.default_rng(45)
    pools, scales = ([tuple(bits((PAGES, *tail), dt, rng)
                            for tail, dt in kind)
                      for _ in range(LAYERS if kind else 0)]
                     for kind in GEOMETRIES[geometry])
    copier = functools.partial(page_copy.copy_pages, interpret=True)
    if ON_CHIP:
        reason = page_copy.decline_reason(jax.tree.leaves(pools + scales))
        assert reason is None or geometry not in SERVED_ON_CHIP, reason
        copier = page_copy.copy_pages if reason is None else xla_copier
    kv = PagedKVCache(get_model_config("tiny-gemma", max_seq_len=128), 4,
                      128, jnp.float32, page_size=16, num_pages=PAGES,
                      copy_pages_fn=copier)
    kv.pools, kv.scales = pools, scales or None
    want = [as_bytes(p).copy() for p in jax.tree.leaves(pools + scales)]
    script, programs = QUEUES[queue]
    for src, dst in script:
        kv._run_page_copy(src, dst, "share")
        for a, b in zip(src, dst):              # the copy made on the spot
            for pool in want:
                pool[b] = pool[a]
    got = jax.tree.leaves(kv.combined_pools())
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(as_bytes(g), w)
    assert kv.page_copy_programs == programs


def test_where_the_kernel_declines_xlas_program_runs_and_says_so():
    """A tiny-gemma engine's pools: the CPU has no Mosaic, and on the
    chip a page of 64-wide heads ends inside a tile. One reason, under
    describe()["declines"] and as the path of every program."""
    eng = InferenceEngine(
        get_model_config("tiny-gemma", max_seq_len=256), num_slots=4,
        kv_layout="paged", page_size=32, num_pages=24,
        sampling=SamplingParams(temperature=0.0, max_new_tokens=8))
    reason = eng.describe()["declines"]["page_copy"]
    assert reason == page_copy.decline_reason(
        jax.tree.leaves(eng.kv.combined_pools()))
    assert reason != page_copy.PATH and "," not in reason
    if not ON_CHIP:
        assert reason.startswith("not on a TPU")
    kv = eng.kv
    assert kv.page_copy_path == reason
    rng = np.random.default_rng(45)
    kv.pools = [tuple(bits(p.shape, p.dtype, rng) for p in layer)
                for layer in kv.pools]
    want = [as_bytes(p).copy() for p in jax.tree.leaves(kv.pools)]
    kv._run_page_copy([3, 4], [5, 6], "cow")
    for pool in want:
        pool[[5, 6]] = pool[[3, 4]]
    for g, w in zip(jax.tree.leaves(kv.combined_pools()), want):
        np.testing.assert_array_equal(as_bytes(g), w)
    paging = eng.describe()["paging"]
    assert paging["page_copy_path"] == reason
    assert paging["page_copy_programs_by_path"] == {reason: 1}
