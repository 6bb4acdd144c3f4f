"""Paged KV cache: allocator invariants, HBM accounting, and end-to-end
token identity with the cache-free greedy decode of
tests/reference_decode.py (PAPERS.md "Ragged Paged Attention")."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from theroundtaible_tpu.engine.engine import InferenceEngine
from theroundtaible_tpu.engine.models.registry import get_model_config
from theroundtaible_tpu.engine.paging import PagedKVCache
from theroundtaible_tpu.engine.sampling import SamplingParams

from reference_decode import assert_greedy

PS = 16  # small pages so tiny prompts span several


def recording_copier(recorded, raw):
    """The engine's copier (every source gathered, then scattered) for
    a cache's tests: each call's id arrays go to `raw` as they came,
    and its real pairs — the pad rows, a scratch page onto itself,
    dropped — to `recorded`. A call never names a destination twice,
    nor one it reads (ISSUE 45: its pairs may move in place)."""

    def copy_fn(pools, src, dst):
        assert isinstance(src, np.ndarray) and isinstance(dst, np.ndarray)
        raw.append((src.copy(), dst.copy()))
        real = src != dst
        assert len(set(dst[real])) == real.sum()
        assert not set(dst[real]) & set(src[real])
        recorded.append((src[real], dst[real]))
        return [tuple(p.at[dst].set(p[src]) for p in layer)
                for layer in pools]

    return copy_fn


def make_cache(num_slots=4, max_seq=128, num_pages=None, copies=None,
               data_size=1, kv_quant=None):
    cfg = get_model_config("tiny-gemma", max_seq_len=max_seq)
    recorded, raw = [], []
    kv = PagedKVCache(cfg, num_slots, max_seq, jnp.float32,
                      page_size=PS, num_pages=num_pages,
                      copy_pages_fn=recording_copier(recorded, raw),
                      data_size=data_size, kv_quant=kv_quant)
    if copies is not None:
        copies.extend([recorded])  # alias for inspection
    kv._recorded_copies = recorded   # one entry a program of the copier
    kv._recorded_raw = raw
    return kv


def issued(kv):
    """The copier's calls so far, whatever was pending issued first."""
    kv.combined_pools()
    return kv._recorded_copies


class TestAllocator:
    def test_capacity_allocates_and_frees(self):
        kv = make_cache()
        kv.acquire("a")
        kv.ensure_capacity("a", 40, write_from=0)   # 3 pages of 16
        assert kv.pages_in_use() == 3
        kv.commit("a", list(range(20)))             # 2 pages needed
        assert kv.pages_in_use() == 2
        kv.release("a")
        assert kv.pages_in_use() == 0

    def test_hbm_scales_with_pool_not_slots(self):
        cfg = get_model_config("tiny-gemma", max_seq_len=128)
        small = PagedKVCache(cfg, 8, 128, jnp.float32, page_size=PS,
                             num_pages=9, copy_pages_fn=None)
        big = PagedKVCache(cfg, 8, 128, jnp.float32, page_size=PS,
                           num_pages=65, copy_pages_fn=None)
        assert small.hbm_bytes() * 7 < big.hbm_bytes()
        # a slot cache's equivalent: 8 slots × 128 positions = 64 pages worth;
        # the small pool serves the same slot COUNT in 1/7th the HBM
        assert small.num_pages == 9

    def test_alias_span_shares_whole_pages(self):
        kv = make_cache()
        kv.acquire("a")
        kv.ensure_capacity("a", 64, write_from=0)
        kv.commit("a", list(range(64)))             # 4 full pages
        before = kv.pages_in_use()
        kv.acquire("b")
        kv.alias_span("a", "b", 0, 48)              # 3 whole pages
        # aliasing added ZERO new pages (pure refcount)
        assert kv.pages_in_use() == before
        assert kv._slots["b"].pages == kv._slots["a"].pages[:3]
        assert not issued(kv)

    def test_alias_span_copies_partial_boundary(self):
        kv = make_cache()
        kv.acquire("a")
        kv.ensure_capacity("a", 64, write_from=0)
        kv.commit("a", list(range(64)))
        kv.acquire("b")
        kv.alias_span("a", "b", 0, 40)  # 2 whole pages + 8 into page 2
        assert kv._slots["b"].pages[:2] == kv._slots["a"].pages[:2]
        # boundary page is a COPY, not an alias
        assert kv._slots["b"].pages[2] != kv._slots["a"].pages[2]
        assert len(issued(kv)) == 1

    def test_cow_on_write_into_shared_page(self):
        kv = make_cache()
        kv.acquire("a")
        kv.ensure_capacity("a", 48, write_from=0)
        kv.commit("a", list(range(48)))
        kv.acquire("b")
        kv.alias_span("a", "b", 0, 48)              # 3 aliased pages
        shared_page = kv._slots["b"].pages[2]
        # b now extends: writing from position 40 lands inside page 2
        kv.ensure_capacity("b", 80, write_from=40)
        assert kv._slots["b"].pages[2] != shared_page   # COW'd
        assert kv._slots["a"].pages[2] == shared_page   # donor untouched

    def test_eviction_frees_pages_for_new_slots(self):
        kv = make_cache(num_slots=4, num_pages=9)   # 8 usable pages
        kv.acquire("a")
        kv.ensure_capacity("a", 128, write_from=0)  # all 8 pages
        kv.commit("a", list(range(128)))
        kv.acquire("b")
        kv.ensure_capacity("b", 32, write_from=0, pinned=("b",))
        assert "a" not in kv._slots                 # evicted
        assert kv.pages_in_use() == 2

    def test_alias_span_never_evicts_donor(self):
        """Boundary-copy allocation under pressure must not evict the
        donor whose pages are about to be aliased (review r2 finding:
        incref after eviction would resurrect freed pages)."""
        kv = make_cache(num_slots=4, max_seq=96, num_pages=7)  # 6 usable
        kv.acquire("a")
        kv.ensure_capacity("a", 96, write_from=0)   # all 6 pages
        kv.commit("a", list(range(96)))
        kv.acquire("b")
        with pytest.raises(RuntimeError, match="exhaust"):
            kv.alias_span("a", "b", 0, 40)          # tail copy needs alloc
        # the donor survived with its pages intact
        assert len(kv._slots["a"].pages) == 6
        assert kv.pages_in_use() == 6

    def test_pool_exhaustion_raises_when_all_pinned(self):
        kv = make_cache(num_slots=4, num_pages=9)
        kv.acquire("a")
        kv.ensure_capacity("a", 128, write_from=0, pinned=("a", "b"))
        kv.acquire("b")
        with pytest.raises(RuntimeError, match="exhaust"):
            kv.ensure_capacity("b", 32, write_from=0, pinned=("a", "b"))


class TestSlotSurface:
    """Named slots: acquire / release, LRU eviction, and the own-slot
    reuse plan with its crash-safe truncation."""

    def test_acquire_release(self):
        kv = make_cache(num_slots=2)
        a = kv.acquire("A")
        kv.acquire("B")
        assert kv.acquire("A") is a  # stable
        kv.ensure_capacity("A", 2 * PS, write_from=0)
        held = kv.pages_in_use()
        kv.release("A")
        assert kv.pages_in_use() == held - 2  # its pages went with it
        kv.acquire("C")
        assert set(kv.slot_names()) == {"B", "C"}

    def test_eviction_on_overflow(self):
        kv = make_cache(num_slots=1)
        kv.acquire("A")
        kv.commit("A", [1, 2, 3])
        kv.acquire("B")  # evicts A
        assert kv.slot_names() == ["B"]

    def test_reuse_plan_prefix(self):
        kv = make_cache(num_slots=2)
        kv.commit("A", [1, 2, 3, 4])
        _, reuse = kv.reuse_plan("A", [1, 2, 3, 4, 5, 6])
        assert reuse == 4
        kv.commit("A", [1, 2, 3, 4])
        _, reuse = kv.reuse_plan("A", [1, 2, 9, 9])
        assert reuse == 2
        # full-match capped at len-1 so one token is always fed
        kv.commit("A", [1, 2, 3, 4])
        _, reuse = kv.reuse_plan("A", [1, 2, 3, 4])
        assert reuse == 3

    def test_reuse_plan_truncates_record_for_crash_safety(self):
        # Positions >= reuse get overwritten by the in-flight turn; if that
        # turn dies (timeout) before commit, the slot must not still claim
        # the clobbered region as valid cache — nor hold pages beyond it.
        kv = make_cache(num_slots=2)
        kv.ensure_capacity("A", 3 * PS, write_from=0)
        kv.commit("A", list(range(1, 3 * PS + 1)))
        kv.reuse_plan("A", [1, 2, 900, 900])  # turn starts, then "crashes"
        assert len(kv.acquire("A").pages) == 1
        _, reuse = kv.reuse_plan("A", list(range(1, 3 * PS + 1)))
        assert reuse == 2  # only the untouched prefix survives

    def test_eviction_is_lru_not_fifo(self):
        kv = make_cache(num_slots=2)
        kv.acquire("A")
        kv.acquire("B")
        kv.acquire("A")  # A is now most recently used
        kv.acquire("C")  # must evict B, the LRU — not A, the first-inserted
        assert set(kv.slot_names()) == {"A", "C"}


class TestPageLoans:
    """Raw page loans for tree-verify private path tables (ISSUE 13):
    free-list-only borrowing (graceful degradation, never eviction),
    plain-decref returns, and the accepted-path swap_in_page adoption."""

    def test_take_free_pages_never_evicts(self):
        kv = make_cache(num_slots=4, num_pages=9)   # 8 usable pages
        kv.acquire("a")
        kv.ensure_capacity("a", 96, write_from=0)   # 6 of 8 pages
        free = kv.free_pages()
        loan = kv.take_free_pages(2)
        assert loan is not None and len(loan) == 2
        assert kv.free_pages() == free - 2
        # A loan larger than the free list returns None and takes
        # NOTHING — resident slots and the free list are untouched.
        assert kv.take_free_pages(free) is None
        assert kv.free_pages() == free - 2
        assert "a" in kv._slots
        kv.give_back_pages(loan)
        assert kv.free_pages() == free

    def test_swap_in_page_adopts_loan_and_frees_old(self):
        kv = make_cache()
        kv.acquire("a")
        kv.ensure_capacity("a", 40, write_from=0)   # 3 pages
        old = kv._slots["a"].pages[1]
        free = kv.free_pages()
        [loan] = kv.take_free_pages(1)
        kv.swap_in_page("a", 1, loan)
        assert kv._slots["a"].pages[1] == loan
        # The exclusive old page freed; the loan's reference became the
        # slot's mapping reference — net free count is unchanged (one
        # out on loan-now-resident, one back from the old mapping).
        assert kv.free_pages() == free
        assert old in kv._free_by_replica[0]
        kv.release("a")
        assert kv.pages_in_use() == 0

    def test_swap_in_page_keeps_shared_old_page_alive(self):
        kv = make_cache()
        kv.acquire("a")
        kv.ensure_capacity("a", 64, write_from=0)
        kv.commit("a", list(range(64)))
        kv.acquire("b")
        kv.alias_span("a", "b", 0, 48)              # pages shared a<->b
        shared = kv._slots["b"].pages[1]
        [loan] = kv.take_free_pages(1)
        kv.swap_in_page("b", 1, loan)
        # b's mapping moved to the loan; a (the other holder) keeps the
        # original page — decref, never force-free.
        assert kv._slots["a"].pages[1] == shared
        assert shared not in kv._free_by_replica[0]
        assert kv.refcount(shared) == 1

    def test_give_back_after_swap_does_not_double_free(self):
        kv = make_cache()
        kv.acquire("a")
        kv.ensure_capacity("a", 40, write_from=0)
        loan = kv.take_free_pages(2)
        kv.swap_in_page("a", 0, loan[0])
        # The settlement path gives back only the UNUSED loan — the
        # swapped page's reference now belongs to the slot mapping.
        kv.give_back_pages(loan[1:])
        assert loan[0] not in kv._free_by_replica[0]
        assert loan[1] in kv._free_by_replica[0]
        kv.release("a")
        assert loan[0] in kv._free_by_replica[0]


def mark_pages(kv):
    """Give every page of every pool bytes of its own: page p holds p
    in a layer's first pool and -p in its second, and p + 0.5 / p +
    0.25 in a quantized cache's scale pools (int8 payload: p < 128)."""

    def fill(pool, sign, plus):
        p = jnp.arange(pool.shape[0]).reshape(
            (-1,) + (1,) * (pool.ndim - 1))
        return jnp.broadcast_to(sign * p + plus, pool.shape).astype(
            pool.dtype)

    kv.pools = [(fill(k, 1, 0), fill(v, -1, 0)) for k, v in kv.pools]
    if kv.scales is not None:
        kv.scales = [(fill(k, 1, 0.5), fill(v, 1, 0.25))
                     for k, v in kv.scales]


def page_origins(kv):
    """Which page's first bytes each page holds now ([P] ints), read
    from a layer's first pool once every pool of every layer — scale
    pools too — has been seen to agree."""
    kv.combined_pools()
    origin = np.asarray(kv.pools[0][0]).reshape(kv.num_pages, -1)[:, 0]
    origin = origin.astype(np.int64)
    for k, v in kv.pools:
        for pool, sign in ((k, 1), (v, -1)):
            flat = np.asarray(pool).reshape(kv.num_pages, -1)
            assert (flat == sign * origin[:, None]).all()
    for k, v in kv.scales or ():
        for pool, plus in ((k, 0.5), (v, 0.25)):
            flat = np.asarray(pool).reshape(kv.num_pages, -1)
            assert (flat == origin[:, None] + plus).all()
    return origin


# Thirty-one pairs that touch nothing the cases name: with one pair
# before them they fill the widest call, so the pair after opens the
# next.
FILLERS = [([10 + i], [60 + i]) for i in range(31)]

# Each a script of _run_page_copy's arguments ("flush": the pools are
# taken in between) and the programs it must go out as.
QUEUE_CASES = {
    # a pending destination becomes a source: 3 ends with 1's bytes
    "chain": ([([1], [2]), ([2], [3])], 1),
    "chain_across_calls": ([([1], [2])] + FILLERS + [([2], [3])], 2),
    # a destination queued twice keeps the last pair, and whoever
    # copied from it in between keeps what it saw
    "destination_twice": ([([1], [2]), ([3], [2])], 1),
    "chain_past_a_dropped_pair":
        ([([1], [2]), ([2], [4]), ([3], [2])], 1),
    # a destination the call reads closes it (ISSUE 45: the pairs of a
    # call are independent, so a copier may move them in place)
    "there_and_back": ([([1], [2]), ([2], [1])], 2),
    # a source freed, handed out again and made a destination while
    # its copy is pending: the earlier pair read it first
    "freed_source_same_flush": ([([5], [6]), ([7], [5])], 2),
    "freed_source_across_calls":
        ([([5], [6])] + FILLERS + [([7], [5])], 2),
    "two_pages_a_copy": ([([1, 2], [3, 4]), ([3, 4], [5, 6])], 1),
    "taken_in_between":
        ([([1], [2]), "flush", ([2], [3]), ([4], [2])], 3),
}

POOL_KINDS = {
    "bf16": {},
    "int8_scales": {"kv_quant": "int8"},
    "two_replicas": {"data_size": 2},
}


class TestCopyQueue:
    """ISSUE 38: a page copy is a pair of host integers on the cache
    until `combined_pools()` — the one way the pool tree leaves it —
    issues everything pending as one call of the copier."""

    @staticmethod
    def shared_setup(kv):
        """a: three full pages; b aliases all three (so a's are
        shared); c is empty."""
        for name in ("a", "b", "c"):
            kv.acquire(name)
        kv.ensure_capacity("a", 48, write_from=0)
        kv.commit("a", list(range(48)))
        assert kv.alias_span("a", "b", 0, 48) == (3, 0)

    # What queues one copy, by cause -> (src page, dst page).

    def _cow(kv):
        shared = kv._slots["b"].pages[2]
        kv.ensure_capacity("b", 80, write_from=40)
        return shared, kv._slots["b"].pages[2]

    def _share(kv):
        kv.alias_span("a", "c", 0, 40)
        return kv._slots["a"].pages[2], kv._slots["c"].pages[2]

    def _alias(kv):
        kv.ensure_capacity("c", 8, write_from=0)
        kv.commit("c", list(range(8)))
        kv.adopt_span("c", kv._slots["a"].pages, 8, 48)
        return kv._slots["a"].pages[0], kv._slots["c"].pages[0]

    ONE_COPY = {"cow": _cow, "share": _share, "alias": _alias}

    @pytest.mark.parametrize("cause", sorted(ONE_COPY))
    def test_a_copy_waits_for_the_pools(self, cause):
        kv = make_cache()
        self.shared_setup(kv)
        mark_pages(kv)
        src, dst = self.ONE_COPY[cause](kv)
        assert src != dst
        # queued: host integers, no program, the pools as they were
        assert kv._pending == [(src, dst, cause)]
        assert not kv._recorded_copies and kv.page_copy_programs == 0
        assert kv.page_copies[cause] == 1
        assert page_origins(kv)[dst] == src       # taking them issues it
        assert kv._pending == [] and kv.page_copy_programs == 1
        ((s, d),) = kv._recorded_copies
        assert (list(s), list(d)) == ([src], [dst])
        kv.combined_pools()                       # nothing left to issue
        assert len(kv._recorded_copies) == 1

    def test_what_several_operations_queued_goes_out_as_one_program(self):
        kv = make_cache(num_slots=5)
        self.shared_setup(kv)
        pairs = [self.ONE_COPY["share"](kv), self.ONE_COPY["cow"](kv)]
        kv.acquire("d")
        kv.alias_span("a", "d", 0, 40)
        pairs.append((kv._slots["a"].pages[2], kv._slots["d"].pages[2]))
        assert kv._pending == [
            pair + (cause,)
            for pair, cause in zip(pairs, ("share", "cow", "share"))]
        assert not kv._recorded_copies
        ((s, d),) = issued(kv)
        assert list(zip(s, d)) == pairs
        assert kv.describe() == {
            "pages_allocated": kv.pages_allocated,
            "page_copies": 3,
            "page_copies_by_cause": {"alias": 0, "share": 2, "cow": 1},
            "page_copy_programs": 1,
            "page_copy_path": "unnamed",
            "page_copy_programs_by_path": {"unnamed": 1},
            "copy_widths": [8, 32],
        }

    @pytest.mark.parametrize("pools", sorted(POOL_KINDS))
    @pytest.mark.parametrize("case", sorted(QUEUE_CASES))
    def test_the_bytes_equal_what_immediate_copies_give(self, case,
                                                        pools):
        from theroundtaible_tpu.engine.kv_quant import KVQuantSpec
        kw = dict(POOL_KINDS[pools])
        if "kv_quant" in kw:
            kw["kv_quant"] = KVQuantSpec(bits=8)
        kv = make_cache(num_pages=100, **kw)
        mark_pages(kv)
        script, programs = QUEUE_CASES[case]
        want = np.arange(kv.num_pages)
        for step in script:
            if step == "flush":
                kv.combined_pools()
                continue
            src, dst = step
            kv._run_page_copy(src, dst, "share")
            for a, b in zip(src, dst):     # the copy made on the spot
                want[b] = want[a]
        assert not (want == np.arange(kv.num_pages)).all()
        np.testing.assert_array_equal(page_origins(kv), want)
        assert kv.page_copy_programs == programs
        assert kv.page_copies["share"] == sum(
            len(step[0]) for step in script if step != "flush")
        # pad rows: replica 0's scratch page onto itself, whatever the
        # replicas, in numpy to the program's door
        for src, dst in kv._recorded_raw:
            assert src.dtype == dst.dtype == np.int32
            pad = src == dst
            assert (src[pad] == kv.scratch_page(0)).all()

    @pytest.mark.parametrize("pairs,widths", [
        (1, [8]), (8, [8]), (9, [32]), (32, [32]), (33, [32, 8]),
        (70, [32, 32, 8])])
    def test_the_smallest_width_that_holds_and_chunks_beyond(self, pairs,
                                                             widths):
        kv = make_cache(num_pages=200)
        mark_pages(kv)
        src = list(range(1, pairs + 1))
        dst = list(range(101, pairs + 101))
        for i in range(0, pairs, 2):          # one or two pages a copy
            kv._run_page_copy(src[i:i + 2], dst[i:i + 2], "alias")
        origin = page_origins(kv)
        assert list(origin[dst]) == src
        assert [len(s) for s, _ in kv._recorded_raw] == widths
        assert kv.page_copy_programs == len(widths)
        # queue order is kept across the calls
        assert [int(x) for s, _ in kv._recorded_copies for x in s] == src

    def test_a_failed_join_leaves_a_pair_that_harms_nothing(self):
        """A join that fails after its span was aliased in
        (scheduler._alias_due) releases the follower with the boundary
        page's copy pending; the page is handed out again and made the
        destination of another copy. The last pair wins."""
        kv = make_cache(num_slots=5, num_pages=9)    # eight usable pages
        for name in ("a", "b", "c", "d", "e"):
            kv.acquire(name)
        kv.ensure_capacity("a", 40, write_from=0)
        kv.commit("a", list(range(40)))
        mark_pages(kv)
        kv.alias_span("a", "b", 0, 40)
        stale = kv._slots["b"].pages[2]
        kv.release("b")                              # the join failed
        assert kv._pending == [(kv._slots["a"].pages[2], stale, "share")]
        kv.ensure_capacity("d", 40, write_from=0)
        kv.commit("d", list(range(100, 140)))
        kv.ensure_capacity("e", 16, write_from=0)    # the free list's
        kv.alias_span("d", "c", 0, 40)               # head is `stale`
        assert kv._slots["c"].pages[2] == stale
        src = kv._slots["d"].pages[2]
        assert page_origins(kv)[stale] == src
        ((s, d),) = kv._recorded_copies
        assert (list(s), list(d)) == ([src], [stale])
        assert kv.page_copies["share"] == 2 and kv.page_copy_programs == 1

    @pytest.mark.parametrize("how", ["flush", "revive"])
    def test_dropping_the_pools_drops_what_is_pending(self, how):
        kv = make_cache()
        self.shared_setup(kv)
        self.ONE_COPY["share"](kv)
        assert kv._pending
        if how == "flush":
            assert kv.flush() == 3
        else:
            for layer in kv.pools:
                for pool in layer:
                    pool.delete()        # a failed donated dispatch
            assert kv.revive_if_dead()
        assert kv._pending == []
        kv.combined_pools()
        assert not kv._recorded_copies and kv.page_copy_programs == 0
        assert kv.page_copies["share"] == 1          # it was queued

    def test_warm_copier_runs_every_width_and_counts_nothing(self):
        kv = make_cache()
        self.shared_setup(kv)
        self.ONE_COPY["cow"](kv)
        kv.warm_copier()                 # issues what was pending first
        assert [len(s) for s, _ in kv._recorded_raw] == [8, 8, 8, 32, 32]
        assert kv.page_copy_programs == 1
        assert all(len(s) == 0 for s, _ in kv._recorded_copies[1:])


class TestPagedEngineParity:
    """The paged engine must produce the cache-free greedy decode's
    tokens (reference_decode) — same parameters, every serving feature."""

    def _engine(self, mesh=None, **kw):
        return InferenceEngine(
            get_model_config("tiny-gemma", max_seq_len=256),
            mesh_shape=mesh, num_slots=4, page_size=32,
            dtype=jnp.float32,
            sampling=SamplingParams(temperature=0.0, max_new_tokens=8),
            **kw)

    def test_generate_parity(self):
        p = "the knights debate the session store design at length"
        assert_greedy(self._engine(), [("a", p)], 8)

    def test_warmup_compiles_every_width_of_the_copier(self,
                                                      monkeypatch):
        """ISSUE 38: a flush pads to a width of paging.COPY_WIDTHS, and
        warmup() has compiled each — so under
        ROUNDTABLE_RECOMPILE_STRICT=1 neither a short flush, a long one
        nor one in chunks compiles once the engine serves."""
        from theroundtaible_tpu.engine import compile_watch
        from theroundtaible_tpu.engine.paging import COPY_WIDTHS
        paged = InferenceEngine(
            get_model_config("tiny-gemma", max_seq_len=256), num_slots=4,
            page_size=32, num_pages=64,
            sampling=SamplingParams(temperature=0.0, max_new_tokens=8))
        paged.warmup(max_prompt_tokens=32, batch_sizes=(1,))
        monkeypatch.setenv("ROUNDTABLE_RECOMPILE_STRICT", "1")
        before = compile_watch.steady_state_compiles()
        kv = paged.kv
        free = kv._free_by_replica[0]
        assert len(free) > COPY_WIDTHS[-1] + 2
        for pairs in (1, COPY_WIDTHS[0] + 1, COPY_WIDTHS[-1] + 2):
            # free pages onto free pages: bytes nothing reads
            kv._run_page_copy(free[:pairs], free[1:pairs + 1], "cow")
            kv.combined_pools()
        assert kv.page_copy_programs >= 4
        assert compile_watch.steady_state_compiles() == before, [
            e.get("label") for e in compile_watch.history()[-6:]]
        assert {e["label"] for e in compile_watch.history()} >= {
            f"page_copy[w={w}]" for w in COPY_WIDTHS}

    def test_multiturn_delta_prefill_parity(self):
        paged = self._engine()
        base = "round one establishes the shared context for everyone here."
        ext = base + " round two adds new arguments and asks for a score."
        paged.generate(base, slot_name="k", max_new_tokens=8)
        assert_greedy(paged, [("k", ext)], 8)
        assert paged.last_stats.reused_tokens > 0

    def test_batch_with_shared_prefix_parity(self):
        paged = self._engine()
        shared = ("the common context paragraph that every knight receives "
                  "before their personal instructions begin here. ")
        prompts = [(f"kn{i}", shared + f"You are knight {i}.")
                   for i in range(3)]
        ids = assert_greedy(paged, prompts, 8)
        # the leader prefilled the common span once; the other two
        # aliased its pages
        common = len(paged.tokenizer.encode(shared)) - 1
        assert paged.last_stats.reused_tokens >= 2 * (common - 1)
        assert paged.last_stats.prefill_tokens < sum(map(len, ids))

    def test_ring_prefill_with_replica_padding(self):
        """data>1 pool-direct + seq_parallel: fresh long prompts take the
        ring program with replica-PADDED rows (regression: _prefill_ring
        sized its arrays from the unpadded slot_ids and crashed on any
        padded batch). Uneven groups + a pad row, against the
        cache-free decode."""
        cfg = get_model_config("tiny-llama", max_seq_len=512)
        sp = SamplingParams(temperature=0.0, max_new_tokens=8)
        ring = InferenceEngine(
            cfg, mesh_shape={"data": 2, "model": 2}, num_slots=4,
            page_size=32, num_pages=40,
            dtype=jnp.float32, seed=3,
            seq_parallel=4, long_threshold=32, sampling=sp)
        assert ring.paged_direct and ring._paged_replicas == 2
        bos = ring.tokenizer.bos_id
        prompts = [("a", [bos] + [7] * 255),   # tpad 256 → ring path
                   ("b", [bos] + [9] * 199),
                   ("c", [bos] + [11] * 179)]  # 3 rows / 2 replicas → pad
        assert_greedy(ring, prompts, 8)

    def test_paged_engine_pages_scale_with_use(self):
        paged = self._engine()
        paged.generate("short", slot_name="s", max_new_tokens=8)
        used_short = paged.kv.pages_in_use()
        paged.generate("a much longer prompt " * 8, slot_name="l",
                       max_new_tokens=8)
        assert paged.kv.pages_in_use() > used_short
        d = paged.describe()
        assert d["kv_layout"] == "paged"
        assert d["kv_hbm_bytes"] > 0

    def test_single_device_uses_pool_direct_decode(self):
        """On a 1-device mesh the decode segment must run the page-table-
        aware kernel (no [B,S,K,D] gather view) and stay token-identical
        to the cache-free decode — incl. multi-turn delta prefill and a
        batch, so frontier-page writes and table-following reads are both
        proven. (The suite's other parity tests run the default 8-device
        mesh = the gather-view path.)"""
        one_dev = {"data": 1, "model": 1}
        paged = self._engine(mesh=one_dev)
        assert paged.paged_direct is True
        assert paged.describe()["paged_decode"] == "pool-direct"
        base = "the pool direct decode must follow the page table exactly."
        ext = base + " a second turn extends across a page boundary here."
        paged.generate(base, slot_name="k", max_new_tokens=8)
        assert_greedy(paged, [("k", ext)], 8)
        assert paged.last_stats.reused_tokens > 0
        assert_greedy(paged, [(f"kn{i}", base + f" knight {i} speaks.")
                              for i in range(3)], 8)

    def test_tp_mesh_pool_direct_matches_the_cache_free_decode(self):
        """Multi-device pool-direct (paged_decode_spmd: kv heads on the
        model axis, matching the pool's sharding) must stay token-
        identical to the cache-free decode of the same parameters."""
        paged = self._engine(mesh={"data": 1, "model": 2})
        assert paged.paged_direct is True
        base = "the sharded pool direct decode follows its page table."
        ext = base + " the second turn crosses a page boundary again."
        paged.generate(base, slot_name="k", max_new_tokens=8)
        assert_greedy(paged, [("k", ext)], 8)
        assert paged.last_stats.reused_tokens > 0

    def test_tp_mesh_pool_direct_mqa_replicated_kv(self):
        """MQA (1 kv head — the gemma-2b layout): the single kv head
        replicates, only q heads shard; pool-direct must still match."""
        cfg = get_model_config("tiny-gemma", max_seq_len=256,
                               num_kv_heads=1)
        paged = InferenceEngine(
            cfg, mesh_shape={"data": 1, "model": 2}, num_slots=2,
            page_size=32, dtype=jnp.float32,
            sampling=SamplingParams(temperature=0.0, max_new_tokens=8))
        assert paged.paged_direct is True
        p = "one kv head shared by every query head across two devices"
        assert_greedy(paged, [("m", p)], 8)

    def test_timeout_mid_serve_leaves_engine_serviceable(self):
        """A deadline hit mid-call must leave the pool/allocator in a
        state where the next call serves normally (slot records are
        truncated first, so interrupted turns only under-claim)."""
        paged = self._engine(mesh={"data": 1, "model": 1})
        # >1 decode segment so work is genuinely unfinished at the
        # deadline check (a single-segment run that completes its whole
        # budget goes all-done and rightly does NOT time out)
        with pytest.raises(TimeoutError):
            paged.generate("never finishes", slot_name="t",
                           max_new_tokens=120, timeout_s=0.0)
        p = "recovery prompt after the timeout"
        out = paged.generate(p, slot_name="t", max_new_tokens=8)
        fresh = self._engine(mesh={"data": 1, "model": 1})
        assert out == fresh.generate(p, slot_name="f", max_new_tokens=8)

    def test_nonpartitionable_heads_fall_back_to_gather_view(self):
        # 4 q heads on a 3-way model axis cannot partition: the engine
        # must route paged decode through the gather view, not the
        # shard_map'd kernel.
        eng = InferenceEngine(
            get_model_config("tiny-gemma", max_seq_len=256),
            mesh_shape={"data": 1, "model": 3}, num_slots=4,
            page_size=32,
            sampling=SamplingParams(temperature=0.0, max_new_tokens=8))
        assert eng.paged_direct is False
        assert eng.describe()["paged_decode"] == "gather-view"
        out = eng.generate("fallback still serves", slot_name="f",
                           max_new_tokens=8)
        assert isinstance(out, str)

    def test_paged_flash_tp_matches_dense(self):
        """Paged gather-view + Pallas-under-shard_map together: the
        kernels must see the same position-aligned view on a TP mesh."""
        def build(attn):
            return InferenceEngine(
                get_model_config("tiny-gemma", max_seq_len=256),
                mesh_shape={"data": 1, "model": 2}, num_slots=4,
                page_size=32, attn=attn,
                sampling=SamplingParams(temperature=0.0,
                                        max_new_tokens=8))

        flash_eng, dense_eng = build("flash"), build("dense")
        assert flash_eng.cfg.attn_impl == "flash"
        shared = ("a long enough shared preamble that the aliasing path "
                  "fires for every knight in the batch today. ")
        prompts = [(f"pf{i}", shared + f"knight {i}") for i in range(2)]
        out_f, stats_f = flash_eng.generate_batch_with_stats(
            prompts, max_new_tokens=8)
        out_d, stats_d = dense_eng.generate_batch_with_stats(
            prompts, max_new_tokens=8)
        assert out_f == out_d
        assert stats_f.reused_tokens == stats_d.reused_tokens > 0

    @pytest.mark.parametrize("mesh,model", [
        ({"data": 1, "model": 1}, "tiny-gemma"),
        ({"data": 1, "model": 2}, "tiny-gemma"),
        ({"data": 2, "model": 2}, "tiny-llama"),
    ], ids=["one-device", "tp", "data-sharded"])
    def test_bf16_pool_direct_matches_the_gather_view(self, mesh, model):
        """The dtype the cells serve in: at bfloat16 (the default) the
        pool-direct kernels give the gather view's tokens on every mesh
        kind — two knights that share a prefix, then both again with a
        longer prompt (the delta alone prefills), same seed, greedy.
        (The float32 cases above hold both to the cache-free decode.)"""
        def build(attn):
            return InferenceEngine(
                get_model_config(model, max_seq_len=256),
                mesh_shape=mesh, num_slots=4, page_size=32, attn=attn,
                seed=3, sampling=SamplingParams(temperature=0.0,
                                                max_new_tokens=8))

        direct, view = build("auto"), build("dense")
        assert direct.paged_direct is True and view.paged_direct is False
        assert direct.kv.pools[0][0].dtype == jnp.bfloat16
        shared = ("a long enough shared preamble that the aliasing path "
                  "fires for every knight in the batch today. ")
        first = [(f"kn{i}", shared + f"knight {i}") for i in range(2)]
        second = [(n, p + " and the second round asks them both again.")
                  for n, p in first]
        for turns in (first, second):
            out_d, stats_d = direct.generate_batch_with_stats(
                turns, max_new_tokens=8)
            out_v, stats_v = view.generate_batch_with_stats(
                turns, max_new_tokens=8)
            assert out_d == out_v
            assert stats_d.reused_tokens == stats_v.reused_tokens > 0

    def test_paged_accepts_seq_parallel(self):
        """paged + seq_parallel now composes (ring K/V scatters through
        the page tables); the token-parity proof lives in
        test_longcontext.TestEngineRingPath."""
        eng = InferenceEngine(
            get_model_config("tiny-gemma", max_seq_len=256),
            num_slots=2, page_size=32, seq_parallel=8)
        assert eng.seq_mesh is not None
        assert eng.describe()["kv_layout"] == "paged"


class TestPerReplicaPools:
    """Data-axis page pools (VERDICT r3 #7): the page axis shards over
    "data"; the allocator keeps the layout coherent — per-replica page
    ranges with their own scratch pages, slot→replica affinity, and
    cross-replica prefix sharing degrading from aliasing to copies."""

    def _kv(self, data_size=2, num_slots=4, num_pages=None):
        return make_cache(num_slots=num_slots, num_pages=num_pages,
                          data_size=data_size)

    def test_ranges_scratch_and_rounding(self):
        kv = self._kv(data_size=2, num_pages=33)  # rounds up to 34
        assert kv.num_pages == 34
        assert kv._scratch == [0, 17]
        assert kv._free_by_replica[0] == list(range(1, 17))
        assert kv._free_by_replica[1] == list(range(18, 34))

    def test_slots_balance_and_allocate_from_own_range(self):
        kv = self._kv(data_size=2)
        for n in "abcd":
            kv.acquire(n)
        replicas = [kv._slots[n].replica for n in "abcd"]
        assert replicas == [0, 1, 0, 1]
        for n in "abcd":
            kv.ensure_capacity(n, 40, write_from=0)  # 3 pages each
        per = kv._per_replica
        for n in "abcd":
            s = kv._slots[n]
            assert all(p // per == s.replica for p in s.pages)
            assert all(p not in kv._scratch for p in s.pages)

    def test_same_replica_alias_cross_replica_copy(self):
        kv = self._kv(data_size=2)
        for n in "abc":
            kv.acquire(n)
        # a (replica 0), b (replica 1), c (replica 0)
        kv.ensure_capacity("a", 3 * PS, write_from=0)
        kv.commit("a", list(range(3 * PS)))
        in_use = kv.pages_in_use()
        # c shares a's whole pages on the SAME replica: pure aliasing —
        # no new pages, ids shared
        kv.alias_span("a", "c", 0, 2 * PS)
        assert kv._slots["c"].pages == kv._slots["a"].pages[:2]
        assert kv.pages_in_use() == in_use
        # b is on the OTHER replica: same span arrives as page COPIES
        # into b's own range — distinct ids, b's replica, one dispatch
        n_copies_before = len(issued(kv))
        kv.alias_span("a", "b", 0, 2 * PS)
        b_pages = kv._slots["b"].pages
        assert len(b_pages) == 2
        assert not set(b_pages) & set(kv._slots["a"].pages)
        assert all(p // kv._per_replica == 1 for p in b_pages)
        assert len(issued(kv)) == n_copies_before + 1
        src, dst = kv._recorded_copies[-1]
        assert list(src) == kv._slots["a"].pages[:2]
        assert list(dst) == b_pages

    def test_eviction_spares_other_replicas_caches(self):
        """Exhausting replica 0 must evict only replica-0 victims:
        releasing a replica-1 slot frees nothing replica 0 can use, so
        destroying its cache would cost reuse for no benefit (review
        finding on the first implementation)."""
        kv = self._kv(data_size=2, num_pages=2 * (8 + 1))  # 8 usable each
        for n in ("a", "b", "c", "d"):   # a,c → replica 0; b,d → replica 1
            kv.acquire(n)
        for n in ("a", "b", "c", "d"):   # 4 pages each: both ranges full
            kv.ensure_capacity(n, 4 * PS, write_from=0)
            kv.commit(n, list(range(4 * PS)))
        # Both replicas host 2 slots; the tie sends "e" to replica 0.
        # Its allocation must evict a/c (replica 0), never b/d.
        kv.acquire("e")
        assert kv._slots["e"].replica == 0
        kv.ensure_capacity("e", 2 * PS, write_from=0, pinned=("e",))
        assert "b" in kv._slots and "d" in kv._slots
        assert kv._slots["b"].pages and kv._slots["d"].pages

    def test_best_donor_prefers_same_replica_on_ties(self):
        """Equal-prefix donors on both replicas: the same-replica one
        must win — its span ALIASES for free where the cross-replica one
        would be device-copied into duplicate pages (review finding)."""
        kv = self._kv(data_size=2)
        prefix = list(range(2 * PS))
        kv.acquire("a")                      # replica 0
        kv.acquire("b")                      # replica 1
        for n in ("a", "b"):
            kv.ensure_capacity(n, len(prefix), write_from=0)
            kv.commit(n, prefix)
        kv.acquire("c")                      # replica 0 (2 slots vs 2... tie→0)
        donor, n = kv.best_donor("c", prefix + [7])
        assert n == len(prefix)
        assert donor.replica == kv._slots["c"].replica

    def test_exhaustion_names_the_replica(self):
        kv = self._kv(data_size=2, num_pages=2 * (8 + 1))  # 8 usable each
        kv.acquire("a")
        with pytest.raises(RuntimeError, match="replica 0"):
            kv.ensure_capacity("a", 9 * PS, write_from=0, pinned=("a",))

    def test_table_pads_with_replica_scratch(self):
        kv = self._kv(data_size=2)
        kv.acquire("a")
        kv.acquire("b")
        kv.ensure_capacity("a", PS, write_from=0)
        kv.ensure_capacity("b", PS, write_from=0)
        table = kv.table_for(["a", "b"])
        assert table[0, -1] == kv._scratch[0]
        assert table[1, -1] == kv._scratch[1]

    def test_data_size_one_unchanged(self):
        kv = self._kv(data_size=1)
        assert kv._scratch == [0]
        kv.acquire("a")
        kv.ensure_capacity("a", 40, write_from=0)
        assert kv.pages_in_use() == 3


class TestDataShardedPagedEngine:
    """End-to-end: on a (data, model) mesh the pool's page axis is
    physically sharded over "data" (per-device pool HBM = total/data) and
    serving stays token-identical to the cache-free decode."""

    MESH = {"data": 2, "model": 2}

    def _engine(self):
        return InferenceEngine(
            get_model_config("tiny-llama", max_seq_len=256),
            mesh_shape=self.MESH, num_slots=4,
            page_size=32, num_pages=34, dtype=jnp.float32, seed=3,
            sampling=SamplingParams(temperature=0.0, max_new_tokens=10))

    def test_pool_page_axis_sharded_over_data(self):
        paged = self._engine()
        k0 = paged.kv.pools[0][0]
        spec = tuple(k0.sharding.spec)
        assert spec[0] == "data"
        assert k0.sharding.shard_shape(k0.shape)[0] == k0.shape[0] // 2
        # data>1 serves pool-direct too (VERDICT r4 #4): batches are
        # replica-grouped + padded, the gather view is never built
        assert paged.paged_direct
        assert paged.describe()["paged_decode"] == "pool-direct"
        assert paged._paged_replicas == 2

    def test_odd_batch_pads_replica_groups(self):
        """3 rows over data=2 replicas (groups 2/1) force a pad row;
        generations must be unaffected: the cache-free decode's."""
        paged = self._engine()
        prompts = [("a", "knight a considers the design."),
                   ("b", "knight b considers the design."),
                   ("c", "knight c considers the design.")]
        assert_greedy(paged, prompts, 10)
        # single-row follow-up turn pads to one row per replica
        assert_greedy(
            paged, [("b", prompts[1][1] + " and now a follow-up turn.")], 8)

    def test_warmup_covers_skewed_compositions(self):
        """b_padded depends on batch COMPOSITION (a 2-row batch on one
        replica pads to 4); warmup must pre-compile those shapes — incl.
        when num_slots doesn't divide the data axis — and cap warm
        prompt lengths at what the pool can pin instead of exhausting."""

        cfg = get_model_config("tiny-llama", max_seq_len=256)
        eng = InferenceEngine(
            cfg, mesh_shape={"data": 2, "model": 2}, num_slots=3,
            page_size=32, dtype=jnp.float32, seed=3,
            sampling=SamplingParams(temperature=0.0, max_new_tokens=4))
        # Record every padded DEVICE batch shape (ReplicaGroupPlan
        # b_padded) warmup compiles, then assert the skewed serve's
        # shape is in that set — the actual no-mid-serve-compile
        # guarantee, deterministic regardless of compile-cache state
        # and robust to future padding-rule changes.
        import theroundtaible_tpu.engine.engine as engine_mod
        recorded: list[int] = []
        real_plan = engine_mod.ReplicaGroupPlan

        class RecordingPlan(real_plan):
            def __init__(self, replicas, n):
                super().__init__(replicas, n)
                recorded.append(self.b_padded)

        engine_mod.ReplicaGroupPlan = RecordingPlan
        try:
            eng.warmup(batch_sizes=(2,))  # must not exhaust the pool
            warm_shapes = set(recorded)
            recorded.clear()
            for n in "abc":
                eng.kv.acquire(n)
            same = [n for n in "abc" if eng.kv.replica_of(n) == 0][:2]
            assert len(same) == 2
            outs = eng.generate_batch([(same[0], "one question"),
                                       (same[1], "two question")],
                                      max_new_tokens=4)
        finally:
            engine_mod.ReplicaGroupPlan = real_plan
        assert len(outs) == 2
        assert recorded, "skewed serve should build a plan"
        # the skewed 2-row batch pads to a shape warmup already compiled
        assert set(recorded) <= warm_shapes, (recorded, warm_shapes)
        assert max(warm_shapes) >= 4  # the skew shape itself

    def test_replica_group_plan_layout(self):
        from theroundtaible_tpu.engine.serving_loop import ReplicaGroupPlan
        plan = ReplicaGroupPlan([1, 0, 0, 1, 1], 2)
        assert plan.b_padded == 6 and plan.group == 3
        # block 0 = replica-0 rows (original order), block 1 = replica-1
        assert list(plan.pos) == [3, 0, 1, 4, 5]
        assert list(plan.pad_positions) == [2]
        assert plan.pad_replicas == [0]
        vals = plan.scatter_rows(np.asarray([10, 20, 30, 40, 50]), -1)
        assert list(np.asarray(vals)) == [20, 30, -1, 10, 40, 50]
        assert list(np.asarray(vals)[plan.pos]) == [10, 20, 30, 40, 50]
        table = np.arange(10).reshape(5, 2)
        padded = plan.pad_table(table, lambda r: 100 + r)
        assert list(padded[plan.pos].ravel()) == list(table.ravel())
        assert list(padded[2]) == [100, 100]

    def test_batch_parity_with_cross_replica_sharing(self):
        paged = self._engine()
        shared = ("a shared context preamble every knight receives "
                  "before its own tail marker. ")
        prompts = [("a", shared + "you are knight A"),
                   ("b", shared + "you are knight B"),
                   ("c", "a totally different question about pools"),
                   ("d", shared + "you are knight D")]
        assert_greedy(paged, prompts, 10)
        replicas = {n: paged.kv._slots[n].replica for n, _ in prompts}
        assert sorted(replicas.values()) == [0, 0, 1, 1]
        # second turn: LCP delta against the replica-local pages
        assert_greedy(paged, [("a", prompts[0][1] + " and a follow-up")], 8)
        assert paged.last_stats.reused_tokens > 0
