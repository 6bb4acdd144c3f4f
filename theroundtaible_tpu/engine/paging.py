"""Paged KV cache — page-pool allocation with copy-on-write sharing.

The engine's one KV layout (PR 46 removed the `[num_slots, max_seq_len,
K, D]` per-layer slot cache, whose HBM cost was num_slots × max_seq_len
regardless of use; PAPERS.md "Ragged Paged Attention"). Each layer
owns a page POOL `[num_pages, page_size, K, D]` and each slot maps
its logical positions onto pool pages through a page table:

- HBM scales with tokens actually cached, not slots × max_seq_len — the
  freed budget is what lets a second model stay resident (SURVEY.md §7.3
  hard part 3).
- Pages are position-aligned (page j of a slot covers absolute positions
  [j*page_size, (j+1)*page_size)), so two slots whose token prefixes agree
  can ALIAS the same pages: cross-knight shared-prefix reuse becomes a
  refcount bump instead of a device copy. Only the boundary page where the
  prompts diverge is copied (copy-on-write).
- Page 0 is a reserved scratch page: table rows are padded with it, and
  batch rows scatter their unused tail there. It is never aliased and
  never read (valid-length masks bound every attention read).

Data-axis sharding (per-replica pools, VERDICT r3 #7): on a mesh with a
data axis the PAGE axis shards over "data" — each replica physically
holds num_pages/data pages (plus its kv-head shard on "model"), so DP
and fleet configs no longer pay data× the pool HBM. The allocator makes
the layout coherent: pages partition into per-replica ranges (each with
its own scratch page — the first page of the range — so pad-cell
scatters stay replica-local), every slot is pinned to one replica at
creation (least-loaded, deterministic) and only ever allocates from its
replica's range, and cross-replica prefix sharing falls back from page
ALIASING to page COPIES (an aliased page cannot live on two replicas).
Serving under data>1 is pool-direct too (VERDICT r4 #4): the engine
permutes each batch into contiguous per-replica row blocks — matching
how shard_map splits the batch axis — pads every block to the largest
group with scratch-table rows that start done, and the spmd kernels
rebase each shard's table to its local page range via axis_index. The
gather view survives only as the non-partitionable-heads / attn="dense"
fallback.

The device side stays simple on purpose: the engine's jit'd programs
gather `pool[table]` into a position-aligned `[B, S, K, D]` view
that forward() and the flash kernels read as a plain cache, and scatter
the updated view back through the same table (the gather view; the
pool-direct programs of paged_forward.py never build it).

The reference has no counterpart (its KV memory lives inside Ollama's
llama.cpp, reference src/adapters/local-llm.ts); this is the engine-side
equivalent of vLLM/tpu-inference paged attention, re-designed for XLA.
"""

from __future__ import annotations

from collections import Counter
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import telemetry
from . import compile_watch
from .models.common import ModelConfig


# Widths of the page copier's id arrays (ISSUE 38): a flush of the
# pending copies pads to the smallest that holds them and goes out in
# chunks of the largest beyond it, so the engine's jitted copier
# compiles these shapes and no other — every one inside
# `engine.warmup()` (`PagedKVCache.warm_copier`). A copy is one or two
# pages and a round's start queues a few dozen; pad rows move a scratch
# page's bytes on the device, so the ladder stays short of a
# sequence's worth of pages.
COPY_WIDTHS = (8, 32)

# Why a page is copied: a prefix-cache attach's boundary page
# (adopt_span), alias_span's between two slots, a shared page about to
# be written (cow_page).
COPY_CAUSES = ("alias", "share", "cow")


def plan_copy_calls(pairs: list[tuple[int, int]],
                    width: int) -> list[dict[int, int]]:
    """Queued (src, dst) page copies, in queue order, as calls of a
    copier whose pairs a call are INDEPENDENT: no destination is named
    twice or is a source of the same call, so the copier may gather
    first and scatter after, or move each pair in place in any order
    (pallas/page_copy.py). -> one {dst: src} a call, at most `width`
    destinations each, whose calls in order leave every page with the
    bytes the copies made one by one would have left.

    Inside a call a source names the page as it was BEFORE the call: a
    pair whose source an earlier pair of the call wrote (A→B, then
    B→C) reads that pair's own source (C takes A's bytes), and a
    destination queued twice keeps the last pair (the earlier never
    lands; whoever copied from it in between was resolved past it). A
    call closes when it is full, or when a pair's destination is a
    page the call reads (A→B, then C→A: a page freed, handed out again
    and overwritten while its copy is pending); the next starts with
    nothing resolved, so queue order holds across calls."""
    calls: list[dict[int, int]] = []
    origin: dict[int, int] = {}
    for src, dst in pairs:
        if (dst not in origin and len(origin) == width) \
                or dst in origin.values():
            calls.append(origin)
            origin = {}
        origin[dst] = origin.get(src, src)
    if origin:
        calls.append(origin)
    return calls


@dataclass
class PagedSlot:
    """Host-side bookkeeping for one knight's slot."""

    name: str
    tokens: list[int] = field(default_factory=list)  # ids baked into cache
    pages: list[int] = field(default_factory=list)   # logical order
    replica: int = 0  # data-axis replica owning every page of this slot


class PagedKVCache:
    """Page-pool KV cache: named slots over refcounted pages.

    `copy_pages_fn(pools, src_ids, dst_ids)` is the engine-provided
    program that copies whole pages (`page_copy_path` names which: DMAs
    in place, or XLA's gather and scatter and why); it is the only
    device operation the allocator itself triggers, and it triggers it
    in ONE place (ISSUE 38). A page copy — a copy-on-write, an alias's
    or an attach's boundary page — is a pair of host integers on a
    pending list
    (`_run_page_copy`); `combined_pools()`, the one way the pool tree
    leaves the cache, issues everything pending as one call of the
    copier (a few beyond `COPY_WIDTHS[-1]` pairs) and installs the
    result before it hands the tree out. So no program that reads or
    writes pages, and no fetch of the offload tier, sees a pool with a
    copy outstanding, and no caller knows of the queue.

    Two page shapes, both owned here. `pools[l]` is the tuple of one
    attention layer's pools, every one indexed by page id on its first
    axis: a (keys, values) pair of `[P, ps, K, D]`, or — a model with
    latent attention (`cfg.latent`, models/mla.py) — ONE pool
    `[P, ps, W]` whose entry a position is the normed c_kv and the roped
    shared key part, padded to `cfg.page_width` (whole lane rows: the
    kernels copy and multiply pages as they lie); there is no value
    pool. Everything that addresses pages by id (slots, aliasing,
    copy-on-write, the radix index, the offload tier) treats a layer's
    pools as that tuple and never looks inside a page.
    """

    def __init__(self, cfg: ModelConfig, num_slots: int,
                 max_seq_len: Optional[int] = None, dtype=jnp.bfloat16,
                 sharding=None, page_size: int = 128,
                 num_pages: Optional[int] = None,
                 copy_pages_fn: Optional[Callable] = None,
                 data_size: int = 1, kv_quant=None):
        self.cfg = cfg
        self.num_slots = num_slots
        self.max_seq_len = max_seq_len or cfg.max_seq_len
        if self.max_seq_len % page_size:
            raise ValueError(
                f"max_seq_len {self.max_seq_len} must be a multiple of "
                f"page_size {page_size}")
        self.page_size = page_size
        self.pages_per_seq = self.max_seq_len // page_size
        self.data_size = max(int(data_size), 1)
        # Quantized pages (ISSUE 11): `kv_quant` is a
        # kv_quant.KVQuantSpec — pools store int8 payload (int4: packed
        # nibbles) with a parallel per-layer per-cell scale pool.
        # Scales are indexed by the SAME page axis, so every sharing
        # mechanism (alias/adopt/COW/commit/prefix-cache/offload)
        # carries them with the page for free.
        self.kv_quant = kv_quant
        self._kv_dtype_bytes = jnp.dtype(dtype).itemsize
        # Default pool: HALF of every slot at max_seq_len — the honest
        # claim of paging is serving the same slots in less HBM — plus
        # one scratch page per data replica (data_size == 1: page 0).
        # Quantized pools keep the SAME BYTE budget (the bf16 default's
        # bytes), so the freed bytes become MORE PAGES — the
        # 2-4x-resident-sessions payoff. Page demand math everywhere is
        # in pages; the dtype dependence lives here, once.
        if num_pages is None:
            num_pages = max(num_slots * self.pages_per_seq // 2,
                            self.data_size * self.pages_per_seq)
            if kv_quant is not None:
                from .kv_quant import page_ratio
                num_pages = int(num_pages * page_ratio(
                    kv_quant, cfg.head_dim, self._kv_dtype_bytes))
            num_pages += self.data_size
        # The page axis shards over "data": round up so it divides.
        self.num_pages = -(-num_pages // self.data_size) * self.data_size
        per_replica = self.num_pages // self.data_size
        if per_replica < self.pages_per_seq + 1:
            raise ValueError(
                f"num_pages {self.num_pages} over {self.data_size} "
                f"replica(s) cannot hold even one full sequence per "
                f"replica ({self.pages_per_seq} pages + scratch)")
        if kv_quant is None:
            latent = cfg.latent
            # (page_heads x page_width: [K, D], or two 64-wide heads a
            # lane row — ModelConfig.lane_pack)
            shape = ((self.num_pages, page_size, cfg.page_width) if latent
                     else (self.num_pages, page_size, cfg.page_heads,
                           cfg.page_width))
            make = (lambda: jnp.zeros(shape, dtype)) if sharding is None \
                else (lambda: jax.device_put(jnp.zeros(shape, dtype),
                                             sharding))
            self._make_pools = lambda n_pages: [
                tuple(make() for _ in range(1 if latent else 2))
                for _ in cfg.attention_layers]
        else:
            qshape = (self.num_pages, page_size, cfg.num_kv_heads,
                      kv_quant.packed_dim(cfg.head_dim))
            sshape = (self.num_pages, page_size, cfg.num_kv_heads,
                      kv_quant.num_groups(cfg.head_dim))

            def _mk(shape, dt):
                x = jnp.zeros(shape, dt)
                # Scale pools share the payload's sharding spec — same
                # page and kv-head axes, unsharded minor axis.
                return x if sharding is None else jax.device_put(
                    x, sharding)

            self._make_pools = lambda n_pages: [
                (_mk(qshape, jnp.int8), _mk(qshape, jnp.int8))
                for _ in range(cfg.num_layers)]
            self._make_scales = lambda n_pages: [
                (_mk(sshape, jnp.float32), _mk(sshape, jnp.float32))
                for _ in range(cfg.num_layers)]
        self.pools = self._make_pools(self.num_pages)
        self.scales = (self._make_scales(self.num_pages)
                       if kv_quant is not None else None)
        self._copy_pages_fn = copy_pages_fn
        # Which program that is: "dma" (pallas/page_copy.py), or why
        # that declined these pools and XLA's gather and scatter runs.
        # The engine names it once the pools exist.
        self.page_copy_path = "unnamed"
        # Page copies queued and not yet issued: (src, dst, cause) in
        # queue order.
        self._pending: list[tuple[int, int, str]] = []
        # Lifetime: pairs queued by cause, and calls of the copier —
        # the quotient says how often the queue gathers anything.
        self.page_copies = dict.fromkeys(COPY_CAUSES, 0)
        self.page_copy_programs = 0
        self.page_copy_programs_by_path: Counter[str] = Counter()
        self._slots: dict[str, PagedSlot] = {}
        # Replica r owns pages [r*per, (r+1)*per); the range's FIRST page
        # is that replica's scratch (never allocated, never aliased).
        self._per_replica = per_replica
        self._scratch = [r * per_replica for r in range(self.data_size)]
        self._free_by_replica: list[list[int]] = [
            list(range(r * per_replica + 1, (r + 1) * per_replica))
            for r in range(self.data_size)]
        self._refs: dict[int, int] = {}
        # Pages handed out by _alloc_page, lifetime (an admission's
        # `plan` span reports what it took: pages_allocated).
        self.pages_allocated = 0
        # Cross-session prefix cache (engine/prefix_cache.py, ISSUE 7):
        # attached by the engine after construction. The allocator's only
        # couplings are (a) commit() publishes complete pages into it,
        # (b) _alloc_page reclaims its refcount-0 pages before declaring
        # exhaustion, (c) flush()/revive drop it with the slots.
        self.prefix_cache = None

    # --- introspection / accounting ---

    def pages_in_use(self) -> int:
        free = sum(len(f) for f in self._free_by_replica)
        return self.num_pages - self.data_size - free

    def free_pages(self, replica: Optional[int] = None) -> int:
        """Immediately-allocatable pages (one replica's range, or all).
        Excludes everything reclaimable-under-pressure (idle evictable
        slots, refcount-0 prefix-cache nodes) — the scheduler's spill
        policy keys off this to spill idle sessions BEFORE the allocator
        destroys their caches."""
        if replica is not None:
            return len(self._free_by_replica[replica])
        return sum(len(f) for f in self._free_by_replica)

    def usable_pages(self) -> int:
        """Total non-scratch pages across every replica range."""
        return self.num_pages - self.data_size

    def pages_held(self, names: list[str]) -> int:
        """Pages currently mapped by the named slots (missing names count
        0). The scheduler's admission backpressure uses this to compute
        how much of the pool is PINNED by in-flight rows — everything
        else is reclaimable by the allocator's LRU eviction, so "free
        right now" would undercount what an admission could use."""
        return sum(len(self._slots[n].pages)
                   for n in names if n in self._slots)

    def hbm_bytes(self) -> int:
        """Resident pool bytes across all layers — payload plus, on
        quantized pools, the per-cell scale arrays (ISSUE 11)."""
        total = sum(p.size * p.dtype.itemsize
                    for layer in self.pools for p in layer)
        if self.scales is not None:
            s, _ = self.scales[0]
            total += 2 * s.size * s.dtype.itemsize * len(self.scales)
        return total

    def hbm_bytes_logical(self) -> int:
        """What the SAME pools would cost at the bf16 cell layout — the
        ledger's kv_bytes_logical counterpart to hbm_bytes (resident).
        Identical to hbm_bytes on unquantized pools."""
        if self.kv_quant is None:
            return self.hbm_bytes()
        return (2 * self.num_pages * self.page_size
                * self.cfg.num_kv_heads * self.cfg.head_dim
                * self._kv_dtype_bytes * len(self.pools))

    # --- combined pool pytree (ISSUE 11) ---
    #
    # The engine's donated jit programs carry pools and scales as ONE
    # pytree (per-layer (k, v) pairs, scale pairs appended), so every
    # dispatch seam moves them together and bf16 engines see exactly
    # the old list — the kill-switch byte-identity hinges on that.

    def combined_pools(self) -> list:
        """The pool tree, every queued page copy made: the ONE place
        the tree leaves the cache (step programs, the scatter, the
        offload tier's fetch and restore, the audits)."""
        if self._pending:
            self._issue_pending()
        return self._combined()

    def _combined(self) -> list:
        if self.scales is None:
            return self.pools
        return list(self.pools) + list(self.scales)

    def set_combined(self, combined: list) -> None:
        n = len(self.pools)
        if self.scales is None:
            self.pools = combined
        else:
            self.pools = list(combined[:n])
            self.scales = list(combined[n:])

    def _run_page_copy(self, src_ids, dst_ids, cause: str) -> None:
        """Queue whole-page copies src_ids[i] → dst_ids[i]: host
        integers on the pending list, no program (ISSUE 38 — a copy
        issued alone cost 3.3 ms of host time for microseconds of
        bytes, 23 times a round). `combined_pools()` issues them. The
        one writer of `page_copies` and its series; `cause` is one of
        COPY_CAUSES."""
        n = len(src_ids)
        self._pending.extend((s, d, cause)
                             for s, d in zip(src_ids, dst_ids))
        self.page_copies[cause] += n
        telemetry.inc("roundtable_page_copies_total", n,
                      engine=self.cfg.name, cause=cause)

    def _issue_pending(self) -> None:
        """Issue the pending copies through the engine's jit'd copier
        as `plan_copy_calls` lays them out — scale rows ride the same
        call on quantized pools (a COW'd or adopted page without its
        scales would dequantize garbage). The id arrays are numpy,
        padded to a width of the ladder with a scratch page (a pad row
        names it twice: identical bytes under any scatter order, and
        no DMA at all), and go to the program as they are. The list is
        dropped first: a donated call that fails takes the pools with
        it (`revive_if_dead`). Nothing reads the copies back, so the
        flush does not feed the loop clock: armed, its host time is a
        `page_copy` span (ISSUE 37) under whatever the calling thread
        has open — `copies` queued, of them by cause, `pages` the
        `programs` moved, by which `path`. The one writer of
        `page_copy_programs`, of them by path, and their series."""
        pending, self._pending = self._pending, []
        span = telemetry.NULL_SPAN
        if telemetry.ACTIVE:
            causes = dict.fromkeys(COPY_CAUSES, 0)
            for _src, _dst, cause in pending:
                causes[cause] += 1
            span = telemetry.start_span("page_copy", copies=len(pending),
                                        path=self.page_copy_path,
                                        **causes)
        calls = plan_copy_calls([(s, d) for s, d, _cause in pending],
                                COPY_WIDTHS[-1])
        pools = self._combined()
        for origin in calls:
            n = len(origin)
            width = next(w for w in COPY_WIDTHS if w >= n)
            ids = np.full((2, width), self._scratch[0], np.int32)
            ids[0, :n] = list(origin.values())
            ids[1, :n] = list(origin)
            pools = self._copy(pools, ids[0], ids[1])
        self.set_combined(pools)
        self.page_copy_programs += len(calls)
        self.page_copy_programs_by_path[self.page_copy_path] += len(calls)
        telemetry.inc("roundtable_page_copy_programs_total", len(calls),
                      engine=self.cfg.name, path=self.page_copy_path)
        if span is not telemetry.NULL_SPAN:
            span.attrs.update(pages=sum(len(c) for c in calls),
                              programs=len(calls))
        span.end()

    def warm_copier(self) -> None:
        """Compile the copier at every width of the ladder (scratch
        onto itself), twice each like every program that takes the
        pools donated — `engine.warmup()` calls this, so no flush
        compiles once the engine serves. Counted nowhere."""
        pools = self.combined_pools()
        for width in COPY_WIDTHS:
            pad = np.full((width,), self._scratch[0], np.int32)
            for _ in range(2):
                pools = self._copy(pools, pad, pad)
        self.set_combined(pools)

    def _copy(self, pools: list, src: np.ndarray, dst: np.ndarray) -> list:
        """One call of the copier, a compile of it named for what it
        is (and not for the step program whose dispatch flushed)."""
        with compile_watch.label(f"page_copy[w={len(src)}]",
                                 engine=self.cfg.name):
            return self._copy_pages_fn(pools, src, dst)

    def describe(self) -> dict:
        """engine.describe()["paging"] (keys bound in
        telemetry.SURFACE_BINDINGS["engine_paging"])."""
        return {
            "pages_allocated": self.pages_allocated,
            "page_copies": sum(self.page_copies.values()),
            "page_copies_by_cause": dict(self.page_copies),
            "page_copy_programs": self.page_copy_programs,
            "page_copy_path": self.page_copy_path,
            "page_copy_programs_by_path":
                dict(self.page_copy_programs_by_path),
            "copy_widths": list(COPY_WIDTHS),
        }

    def slot_names(self) -> list[str]:
        return list(self._slots)

    def memory_ledger(self) -> dict:
        """Paged-pool accounting for the memory ledger (ISSUE 6/7):
        pages in use / usable, slot occupancy, and internal
        FRAGMENTATION — the fraction of held page cells not backing a
        cached token (decode reserve + tail waste inside each slot's
        last pages). `pages_in_use` counts pool allocation (aliased
        shared pages once). Fragmentation is REFCOUNT-AWARE (ISSUE 7
        satellite): computed over DISTINCT pages with each page's
        covered cells taken once (the max over the slots mapping it),
        so a page shared by N sessions never counts N times and the
        ledger's shared/exclusive split is honest across sessions."""
        in_use = self.pages_in_use()
        usable = self.usable_pages()
        cached_tokens = sum(len(s.tokens) for s in self._slots.values())
        ps = self.page_size
        # page -> covered cells (max over the slots mapping it): shared
        # pages counted ONCE.
        covered: dict[int, int] = {}
        map_counts: dict[int, int] = {}
        for s in self._slots.values():
            for j, p in enumerate(s.pages):
                map_counts[p] = map_counts.get(p, 0) + 1
                cov = max(0, min(len(s.tokens) - j * ps, ps))
                if cov > covered.get(p, -1):
                    covered[p] = cov
        held_cells = len(covered) * ps
        frag = (round(1.0 - min(sum(covered.values()) / held_cells, 1.0),
                      3) if held_cells else 0.0)
        # "Shared" means DEDUPLICATED bytes: ≥2 slot mappings, or a
        # non-index external holder (offload tier / earlier spill). The
        # index's own bookkeeping ref is not sharing — one session with
        # the cache on would otherwise report every committed page as
        # shared and inflate the capacity-multiplier estimate the bench
        # derives from the exclusive count (review finding).
        pc = self.prefix_cache

        def _is_shared(p: int) -> bool:
            if map_counts.get(p, 0) >= 2:
                return True
            extra = self._refs.get(p, 1) - map_counts.get(p, 0)
            if pc is not None and pc.holds_page(p):
                extra -= 1
            return extra >= 1

        shared = sum(1 for p in covered if _is_shared(p))
        cache_pages = (self.prefix_cache.page_count()
                       if self.prefix_cache is not None else 0)
        n_slots = len(self._slots)
        # Quantized-page split (ISSUE 11 satellite): resident = what
        # the pools actually cost (payload + scales), logical = what
        # the same pools would cost at bf16 cells. The saved delta
        # feeds roundtable_kv_quant_bytes_saved.
        resident = self.hbm_bytes()
        logical = self.hbm_bytes_logical()
        return {
            "layout": "paged",
            "kv_dtype": (self.kv_quant.dtype_name
                         if self.kv_quant is not None else "bf16"),
            "kv_quant_bits": (self.kv_quant.bits
                              if self.kv_quant is not None else 0),
            "kv_bytes_resident": resident,
            "kv_bytes_logical": logical,
            "kv_quant_bytes_saved": max(logical - resident, 0),
            "slots_in_use": n_slots,
            "num_slots": self.num_slots,
            "slot_occupancy": round(n_slots / max(self.num_slots, 1), 3),
            "cached_tokens": cached_tokens,
            "pages_in_use": in_use,
            "usable_pages": usable,
            "page_utilization": round(in_use / max(usable, 1), 3),
            "fragmentation": frag,
            # ISSUE 7: the cross-session sharing split. `shared_pages`
            # are slot-mapped pages with >1 holder (other slots, the
            # prefix cache, the offload tier); `prefix_cache_pages` is
            # the index's own footprint (overlaps slot-mapped pages
            # while both reference them — pool allocation still counts
            # each page once via pages_in_use).
            "shared_pages": shared,
            "exclusive_pages": len(covered) - shared,
            "prefix_cache_pages": cache_pages,
            "hbm_bytes": resident,
        }

    def revive_if_dead(self) -> bool:
        """Reallocate the page pools if a failed donated dispatch deleted
        them (jax donate_argnums consumes inputs even when the program
        faults after transfer). Every slot,
        page mapping and refcount is dropped — the bytes are gone — so
        later prefills start from scratch. Returns True iff revived."""
        if not any(p.is_deleted() for layer in self.pools for p in layer):
            return False
        self.pools = self._make_pools(self.num_pages)
        if self.scales is not None:
            self.scales = self._make_scales(self.num_pages)
        self._slots.clear()
        self._refs.clear()
        self._pending = []      # their pages' bytes are gone too
        per = self._per_replica
        self._free_by_replica = [
            list(range(r * per + 1, (r + 1) * per))
            for r in range(self.data_size)]
        if self.prefix_cache is not None:
            # The indexed bytes died with the pools; drop the nodes
            # WITHOUT unref (the refs table was just cleared).
            self.prefix_cache.clear(unref=False)
        return True

    # --- slot lifecycle ---

    def acquire(self, name: str, pinned: tuple[str, ...] = ()) -> PagedSlot:
        if name in self._slots:
            self._slots[name] = self._slots.pop(name)  # LRU refresh
            return self._slots[name]
        if len(self._slots) >= self.num_slots:
            victim = next((n for n in self._slots if n not in pinned), None)
            if victim is None:
                raise RuntimeError(
                    f"PagedKVCache has {self.num_slots} slots but "
                    f"{len(pinned)} knights are pinned in one batch — "
                    "raise num_slots in the tpu-llm adapter config")
            self.release(victim)
        # Pin the new slot to the replica hosting the fewest slots, with
        # free pages breaking ties (slots acquire BEFORE they allocate,
        # so free-page counts alone tie at batch start and would pile
        # every slot onto replica 0). Deterministic: depends only on the
        # call sequence — multi-host lockstep safe.
        counts = [0] * self.data_size
        for s in self._slots.values():
            counts[s.replica] += 1
        replica = min(range(self.data_size),
                      key=lambda r: (counts[r],
                                     -len(self._free_by_replica[r]), r))
        state = PagedSlot(name=name, replica=replica)
        self._slots[name] = state
        return state

    def release(self, name: str) -> None:
        state = self._slots.pop(name, None)
        if state is not None:
            for p in state.pages:
                self._decref(p)

    def flush(self) -> int:
        """Release every per-knight slot (graceful drain's KV flush,
        fleet.drain): each slot's
        pages decref and free back to their replica ranges, and the
        prefix cache drops its index the same way — every holder UNREFS
        (never force-frees), so a page momentarily shared between a slot
        and the index frees exactly when the last reference goes.
        Returns how many slots were flushed."""
        names = list(self._slots)
        for name in names:
            self.release(name)
        if self.prefix_cache is not None:
            self.prefix_cache.drop_all()
        # Every destination of a pending copy has just been freed.
        self._pending = []
        return len(names)

    def reset_slot(self, name: str) -> None:
        if name in self._slots:
            state = self._slots[name]
            for p in state.pages:
                self._decref(p)
            state.pages = []
            state.tokens = []

    # --- refcounting ---

    def _decref(self, page: int) -> None:
        n = self._refs.get(page, 1) - 1
        if n <= 0:
            self._refs.pop(page, None)
            # A page always frees back to the replica range it belongs to.
            self._free_by_replica[page // self._per_replica].append(page)
        else:
            self._refs[page] = n

    def _incref(self, page: int) -> None:
        self._refs[page] = self._refs.get(page, 1) + 1

    def _shared(self, page: int) -> bool:
        return self._refs.get(page, 1) > 1

    def _index_only_share(self, page: int) -> bool:
        """True when `page`'s only holder besides the mapping slot is
        the prefix-cache index (refcount exactly 2 with an index hold).
        The write paths then make the page exclusive by FORGETTING the
        index entry instead of copy-on-write: the slot's divergence is
        invalidating that entry's continuation anyway, and the forget
        costs zero pages and zero dispatches where a COW under a full
        pool can be the allocation that doesn't exist (observed: a
        16-page pool serving one 16-page sequence died COWing page 0
        against the index's hold)."""
        return (self.prefix_cache is not None
                and self._refs.get(page, 1) == 2
                and self.prefix_cache.holds_page(page))

    # Public refcount surface (ISSUE 7): the prefix cache and the host
    # offload tier hold references of their own, so a page shared by N
    # sessions plus the index is stored once and only ever FREES when
    # every holder has unref'd — release/flush/retire paths decref, never
    # force-free.

    def ref(self, page: int) -> None:
        """Take one reference on `page` (index/offload-tier holders)."""
        self._incref(page)

    def unref(self, page: int) -> None:
        """Drop one reference; the page frees to its replica range only
        when the LAST holder lets go."""
        self._decref(page)

    def refcount(self, page: int) -> int:
        """Current holder count (1 = exactly one holder)."""
        return self._refs.get(page, 1)

    def replica_of_page(self, page: int) -> int:
        """The data replica whose range physically holds `page`."""
        return page // self._per_replica

    def cow_page(self, name: str, j: int,
                 pinned: tuple[str, ...] = ()) -> int:
        """Copy-on-write primitive: give `name` exclusive ownership of
        its logical page j, device-copying the shared original into a
        fresh page on the slot's replica. No-op (returns the existing
        id) when the page is already exclusive."""
        state = self._slots[name]
        p = state.pages[j]
        if not self._shared(p):
            return p
        if self._index_only_share(p):
            self.prefix_cache.forget_page(p)
            return p
        pinned = tuple(pinned) + (name,)
        fresh = self._alloc_page(pinned, state.replica)
        self._decref(p)
        state.pages[j] = fresh
        self._run_page_copy([p], [fresh], "cow")
        return fresh

    def _alloc_page(self, pinned_names: tuple[str, ...],
                    replica: int = 0) -> int:
        free = self._free_by_replica[replica]
        if not free and self.prefix_cache is not None:
            # CHEAPEST first: reclaim LRU refcount-0 prefix-cache nodes
            # on this replica (pages held ONLY by the index — a node
            # some live slot still aliases is never touched). With the
            # cache on, evicting a slot first would free almost nothing
            # (its complete pages stay index-held) while destroying the
            # slot's record — the loop could wipe every idle slot on
            # the replica before one pure-cache page was even tried.
            self.prefix_cache.reclaim(replica=replica)
        if not free:
            # Evict LRU slots (dict order = recency) until a page frees
            # ON THIS REPLICA — victims on other replicas free pages this
            # slot cannot use, so destroying their caches would cost
            # reuse without unblocking anything. A released victim's
            # index-held pages drop to refcount-0: reclaim between
            # victims so each eviction actually yields its pages.
            for victim in list(self._slots):
                if (victim in pinned_names
                        or self._slots[victim].replica != replica):
                    continue
                self.release(victim)
                if not free and self.prefix_cache is not None:
                    self.prefix_cache.reclaim(replica=replica)
                if free:
                    break
        if not free:
            raise RuntimeError(
                f"Page pool exhausted on data replica {replica}: all its "
                "pages pinned by the in-flight batch — raise num_pages "
                "(tpu-llm adapter config) or lower max_new_tokens")
        self.pages_allocated += 1
        return free.pop(0)

    # --- raw page loans (ISSUE 13: tree-verify private path pages) ---

    def take_free_pages(self, n: int,
                        replica: int = 0) -> Optional[list[int]]:
        """Borrow `n` pages from the FREE list only — never evicts a
        slot and never reclaims the prefix cache, so a borrower that
        can gracefully do without (the tree verify degrades a row to
        chain speculation) cannot destroy resident state to get its
        scratch. None when the replica's free list is short."""
        free = self._free_by_replica[replica]
        if len(free) < n:
            return None
        return [free.pop(0) for _ in range(n)]

    def give_back_pages(self, pages: list[int]) -> None:
        """Return pages taken by take_free_pages (or adopted-and-
        replaced pages) — plain decref, so a page that was swapped
        into a slot's table meanwhile is NOT freed under it."""
        for p in pages:
            self._decref(p)

    def swap_in_page(self, name: str, j: int, page: int) -> None:
        """Replace slot `name`'s logical page j with `page`, whose
        cells already hold the position range's K/V (the tree verify's
        accepted path: the private page was pre-COW'd from the old
        frontier page in-dispatch, then received the accepted tokens'
        writes — a copy-on-write whose copy already happened). The old
        page decrefs (an index/donor holder keeps its copy; exclusive
        pages free), and the loaned page's reference becomes the
        slot's mapping reference."""
        state = self._slots[name]
        self._decref(state.pages[j])
        state.pages[j] = page

    # --- prefix bookkeeping ---

    @staticmethod
    def common_prefix_len(cached: list[int], new: list[int]) -> int:
        from ..native import lcp
        return lcp(cached, new)

    def reuse_plan(self, name: str, tokens: list[int],
                   pinned: tuple[str, ...] = ()) -> tuple[int, int]:
        """(-1, reuse_len): how many leading tokens are already baked
        into the slot's pages; the caller prefills only
        tokens[reuse_len:], capped at len(tokens)-1 so at least one
        token is always fed (the model needs a last-token logit to start
        decoding). Rows are keyed by table_for(names), never by a device
        slot id (the -1 fails loudly if ever used as an index). Truncates the
        record now (crash safety) and drops whole pages beyond the reuse
        frontier."""
        state = self.acquire(name, pinned)
        reuse = self.common_prefix_len(state.tokens, tokens)
        reuse = min(reuse, len(tokens) - 1)
        state.tokens = state.tokens[:reuse]
        self._trim_pages(state, reuse)
        return -1, reuse

    def _trim_pages(self, state: PagedSlot, tokens_kept: int) -> None:
        """Free pages wholly beyond ceil(tokens_kept / page_size)."""
        keep = -(-tokens_kept // self.page_size) if tokens_kept else 0
        while len(state.pages) > keep:
            self._decref(state.pages.pop())

    def commit(self, name: str, tokens: list[int],
               index: bool = True) -> None:
        # `index=False` (ISSUE 10): the slot's pages hold
        # adapter-tinted K/V — commit the token record for own-slot
        # reuse, but never publish the pages into the cross-session
        # index (base rows of other sessions must not alias them).
        state = self.acquire(name)
        state.tokens = list(tokens)
        self._trim_pages(state, len(tokens))
        if (index and self.prefix_cache is not None
                and not name.startswith("__warmup_")):
            # Publish the slot's COMPLETE pages into the content-
            # addressed index (ISSUE 7): the next session whose prompt
            # starts with the same token blocks aliases them instead of
            # re-prefilling. Warmup slots are excluded — warm rows are
            # crafted to defeat prefix sharing so every (batch, bucket)
            # program actually compiles.
            self.prefix_cache.insert(state)

    def best_donor(self, name: str,
                   tokens: list[int]) -> tuple[Optional[PagedSlot], int]:
        """Longest-common-prefix donor; prefix-length ties prefer a donor
        on the SAME replica as `name` — same-replica spans alias for free
        while cross-replica spans degrade to device copies plus duplicate
        pages out of the destination replica's range (review finding).
        Donation is intra-session only (kvcache.session_of): sessions are
        isolation domains, and a cross-session alias would couple one
        session's page lifetime to another's fault recovery."""
        from .kvcache import session_of
        dst = self._slots.get(name)
        dst_replica = dst.replica if dst is not None else 0
        scope = session_of(name)
        best, best_key = None, (0, -1)
        for state in self._slots.values():
            if state.name == name or not state.tokens:
                continue
            if session_of(state.name) != scope:
                continue
            n = self.common_prefix_len(state.tokens, tokens)
            if n == 0:
                continue
            key = (n, 1 if state.replica == dst_replica else 0)
            if key > best_key:
                best, best_key = state, key
        return best, best_key[0]

    # --- capacity + sharing ---

    def ensure_capacity(self, name: str, upto_tokens: int,
                        write_from: int,
                        pinned: tuple[str, ...] = ()) -> None:
        """Make positions [0, upto_tokens) addressable and positions
        [write_from, upto_tokens) EXCLUSIVELY owned (copy-on-write any
        shared page the upcoming prefill/decode will write)."""
        pinned = tuple(pinned) + (name,)  # never self-evict mid-alloc
        state = self.acquire(name, pinned)
        need = -(-upto_tokens // self.page_size)
        while len(state.pages) < need:
            state.pages.append(self._alloc_page(pinned, state.replica))
        # ONE definition of the fork policy (cow_page): index-only
        # shares go exclusive by forgetting the index entry (no copy,
        # no alloc — under a full pool the COW alloc may be the page
        # that doesn't exist), real shares copy into a fresh page.
        # Write ranges are typically 0-1 shared pages (the attach
        # frontier is page-aligned); each copy is queued, not issued.
        for j in range(write_from // self.page_size, len(state.pages)):
            if self._shared(state.pages[j]):
                self.cow_page(name, j, pinned)

    def alias_span(self, src_name: str, dst_name: str, lo: int,
                   hi: int, pinned: tuple[str, ...] = ()
                   ) -> tuple[int, int]:
        """Give dst the K/V for positions [lo, hi) from src: whole pages
        alias (refcount++), the partial boundary pages are device-copied.
        Precondition: src's cache covers [0, hi) and the two token streams
        agree on [0, hi) (guaranteed by LCP-based callers).
        -> (pages aliased, pages copied)."""
        # Pin BOTH endpoints: _alloc_page's eviction may otherwise release
        # the donor mid-call and the later incref loop would resurrect
        # pages already sitting in the free list — silent corruption once
        # a future alloc hands the same page to another slot.
        pinned = tuple(pinned) + (src_name, dst_name)
        src = self.acquire(src_name, pinned)
        dst = self.acquire(dst_name, pinned)
        ps = self.page_size
        lo_page, hi_page = lo // ps, hi // ps
        # Aliasing requires both slots on the SAME data replica (an
        # aliased page cannot be resident in two replicas' pool shards);
        # cross-replica sharing degrades to whole-page device COPIES into
        # dst's replica — queued like every copy, still skips the prefill.
        same_replica = src.replica == dst.replica
        # dst keeps its own pages below lo; drop anything it holds beyond.
        self._trim_pages(dst, lo)
        if len(dst.pages) < lo_page:
            # lo is dst's cached length, so this cannot happen — guard for
            # misuse rather than corrupt silently.
            raise RuntimeError("alias_span: dst does not cover up to lo")
        cow_src, cow_dst = [], []
        aliased = 0

        def copy_into_dst(j: int) -> None:
            """Give dst its own exclusively-held page j, filled from
            src's page j (COW if dst's current page j is shared)."""
            if j < len(dst.pages):
                if self._shared(dst.pages[j]):
                    fresh = self._alloc_page(pinned, dst.replica)
                    self._decref(dst.pages[j])
                    dst.pages[j] = fresh
            else:
                dst.pages.append(self._alloc_page(pinned, dst.replica))
            cow_src.append(src.pages[j])
            cow_dst.append(dst.pages[j])

        if lo % ps and lo_page < hi_page:
            # dst's partial boundary page: dst's page holds dst tokens
            # [lo_page*ps, lo) == src's (common prefix), so copying src's
            # full page is a superset update.
            copy_into_dst(lo_page)
            lo_page += 1
        # whole pages [lo_page, hi_page): pure aliasing (same replica)
        # or device copies (cross-replica)
        for j in range(lo_page, hi_page):
            if same_replica:
                if j < len(dst.pages):
                    self._decref(dst.pages[j])
                    dst.pages[j] = src.pages[j]
                else:
                    dst.pages.append(src.pages[j])
                self._incref(src.pages[j])
                aliased += 1
            else:
                copy_into_dst(j)
        # partial tail [hi_page*ps, hi): device-copy src's page
        if hi % ps and hi_page < len(src.pages):
            copy_into_dst(hi_page)
        if cow_src:
            self._run_page_copy(cow_src, cow_dst, "share")
        return aliased, len(cow_src)

    def adopt_span(self, dst_name: str, src_pages: list[int], lo: int,
                   hi: int, pinned: tuple[str, ...] = ()) -> None:
        """alias_span's slot-free counterpart: give dst the K/V for
        positions [lo, hi) from an EXPLICIT page list covering [0, hi)
        at page granularity — the prefix cache's content-addressed pages
        (ISSUE 7). Whole pages on dst's replica alias (refcount++);
        pages physically on another replica, and the partial boundary
        page at lo, are device-copied into dst-owned pages. `hi` must be
        page-aligned (the index only ever matches complete blocks).

        Every source page is guard-ref'd for the duration: the COW/copy
        allocations below may trigger slot eviction and prefix-cache
        reclaim, and a refcount-0 source node freed mid-span would be
        resurrected from the free list — silent corruption once a later
        alloc hands the same page to another slot."""
        ps = self.page_size
        if hi % ps:
            raise ValueError("adopt_span: hi must be page-aligned")
        pinned = tuple(pinned) + (dst_name,)
        dst = self.acquire(dst_name, pinned)
        lo_page, hi_page = lo // ps, hi // ps
        self._trim_pages(dst, lo)
        if len(dst.pages) < lo_page:
            raise RuntimeError("adopt_span: dst does not cover up to lo")
        guards = {j: src_pages[j] for j in range(lo_page, hi_page)}
        for p in guards.values():
            self._incref(p)
        transferred: set[int] = set()
        cow_src, cow_dst = [], []

        def copy_into_dst(j: int) -> None:
            if j < len(dst.pages):
                if (self._shared(dst.pages[j])
                        and not self._index_only_share(dst.pages[j])):
                    fresh = self._alloc_page(pinned, dst.replica)
                    self._decref(dst.pages[j])
                    dst.pages[j] = fresh
                elif self._shared(dst.pages[j]):
                    # Index-only share about to be overwritten by the
                    # adopted copy: forgetting it is exclusive-for-free.
                    self.prefix_cache.forget_page(dst.pages[j])
            else:
                dst.pages.append(self._alloc_page(pinned, dst.replica))
            cow_src.append(src_pages[j])
            cow_dst.append(dst.pages[j])

        try:
            if lo % ps and lo_page < hi_page:
                # dst's partial boundary page holds tokens [lo_page*ps,
                # lo) — the source's full page is a superset update
                # (token streams agree on [0, hi), the caller's LCP
                # contract).
                copy_into_dst(lo_page)
                lo_page += 1
            for j in range(lo_page, hi_page):
                if self.replica_of_page(src_pages[j]) == dst.replica:
                    if j < len(dst.pages):
                        self._decref(dst.pages[j])
                        dst.pages[j] = src_pages[j]
                    else:
                        dst.pages.append(src_pages[j])
                    # The guard ref becomes dst's mapping reference.
                    transferred.add(j)
                else:
                    copy_into_dst(j)
            if cow_src:
                self._run_page_copy(cow_src, cow_dst, "alias")
        finally:
            for j, p in guards.items():
                if j not in transferred:
                    self._decref(p)

    # --- device tables ---

    def replica_of(self, name: str) -> int:
        """Data-axis replica owning every page of `name`'s slot — the
        engine's replica-grouped batch plan keys on this (pool-direct
        serving under data>1 shards batch rows over "data", so each row
        must sit in the batch block of the replica holding its pages)."""
        return self._slots[name].replica

    def pages_per_replica(self) -> int:
        """Usable (non-scratch) pages in each replica's range — what a
        replica's rows can collectively pin before exhaustion."""
        return self._per_replica - 1

    def scratch_page(self, replica: int) -> int:
        """The reserved scratch page of a replica's range — pad batch
        rows point their whole table here (never aliased, never read)."""
        return self._scratch[replica]

    def table_for(self, names: list[str]) -> np.ndarray:
        """[B, pages_per_seq] int32 page table, padded with each slot's
        OWN replica's scratch page (pad-cell scatters stay replica-local
        on data-sharded pools; data_size == 1 keeps page 0, as before)."""
        table = np.zeros((len(names), self.pages_per_seq), np.int32)
        for i, name in enumerate(names):
            state = self._slots[name]
            table[i, :] = self._scratch[state.replica]
            table[i, :len(state.pages)] = state.pages
        return table
