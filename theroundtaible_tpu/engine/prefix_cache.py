"""Cross-session radix-tree prefix cache over the paged KV pool.

Every roundtable discussion re-prefills the same bytes: the shared
system prompt, each knight's personality tail, and (across rounds) the
growing transcript. PR 4's donation (`best_donor`) deliberately stays
intra-session — sessions are fault-isolation domains, and a donor SLOT's
lifetime is coupled to its session's recovery ladder. This module adds
the production answer RTP-LLM documents (PAPERS.md): a CONTENT-ADDRESSED
index over the page pool itself, decoupled from any slot's lifetime.

Design (ISSUE 7 tentpole):

- **Radix tree keyed by token blocks.** One node per page-sized token
  block, children keyed by the block's token tuple (content-addressed
  with exact verification — a hash collision can therefore never serve
  wrong bytes). A node maps its block to ONE pool page whose K/V bytes
  are the deterministic function of the token prefix up to it.
- **The index is a reference holder, not an owner.** insert() takes one
  pool reference per node (`PagedKVCache.ref`); slots that later release
  or truncate merely UNREF — the page's bytes survive in the pool for as
  long as anyone (index, slot, offload tier) still references them.
- **attach() is the read path.** `InferenceEngine._prepare_batch`
  calls it per row after the slot's own reuse_plan: the longest
  complete-block match extends the row's reuse frontier by ALIASING
  the matched pages (refcount++, zero copy; pages on another data
  replica, and the partial boundary page, device-copy —
  `PagedKVCache.adopt_span`). The attached span is READ-ONLY by
  construction: `ensure_capacity` copy-on-writes any shared page in the
  row's write range before the first divergent write, so two sessions
  sharing a prefix fork exactly at the first page they disagree on.
- **Eviction is LRU over refcount-0 nodes only.** A node whose page some
  live slot (or the offload tier) still references is never reclaimed;
  leaf nodes whose page the index alone holds evict oldest-first, under
  an optional page cap and — last resort — from `_alloc_page` just
  before it would declare pool exhaustion. flush()/drain drop the whole
  index via unref (never force-free).

Safety invariant (the hard part of cross-session sharing): the index
NEVER hands out a writable page, never frees a referenced page, and a
session's fault recovery (slot invalidation, revive) can only ever
unref/clear — it cannot reach into another session's mappings.
"""

from __future__ import annotations

import threading
from typing import Optional

from ..utils import telemetry

# Test-visibility counters (tests/conftest.py `prefix_cache` marker
# guard): a test that CLAIMS prefix-cache coverage but records zero
# attach hits silently ran cache-off serving — fail it loud.
_test_hits = 0
_test_lock = threading.Lock()


def reset_test_counters() -> None:
    global _test_hits
    with _test_lock:
        _test_hits = 0


def hits_seen() -> int:
    return _test_hits


def _note_hit() -> None:
    global _test_hits
    with _test_lock:
        _test_hits += 1


def env_flag(flag: Optional[bool], env_name: str) -> bool:
    """Shared on/off decision for the paged-pool subsystems: an explicit
    config value wins, then the env kill-switch, then default ON. ONE
    definition (prefix cache + offload tier) so the accepted falsy
    spellings can never drift between the two knobs."""
    import os
    if flag is not None:
        return bool(flag)
    env = os.environ.get(env_name)
    if env is not None:
        return env not in ("0", "false", "off")
    return True


def cache_enabled(flag: Optional[bool]) -> bool:
    """The prefix cache's on/off decision for a paged engine (the cache
    is the serving path, not an experiment — default ON)."""
    return env_flag(flag, "ROUNDTABLE_PREFIX_CACHE")


class _Node:
    __slots__ = ("children", "parent", "block", "page", "tick", "snap")

    def __init__(self, parent=None, block=None, page=None):
        self.children: dict[tuple, "_Node"] = {}
        self.parent = parent
        self.block = block
        self.page = page
        self.tick = 0
        # Key of the recurrent-state snapshot taken at this page's end
        # (engine/hybrid_state.py), dropped with the node.
        self.snap = None


class PrefixCache:
    """The content-addressed index over one PagedKVCache pool.

    Single-writer like the pool itself: every caller already serializes
    on the engine's serve lock (scheduler thread / generate_batch), so
    no internal locking beyond the test counters."""

    def __init__(self, kv, engine: str = "engine",
                 max_pages: Optional[int] = None):
        self.kv = kv
        self.engine = engine
        self.page_size = kv.page_size
        # Default cap: the whole usable pool — the index is bounded by
        # reclaim-under-pressure, and idle capacity spent on cached
        # prefixes is the point. Set prefix_cache_pages to bound it hard.
        self.max_pages = max_pages or kv.usable_pages()
        self.root = _Node()
        self._pages = 0
        # A model with recurrent state attaches its HybridStateStore
        # here: snapshots are bound to the nodes of their pages at
        # insert and evicted with them (never outliving their pages).
        self.state_store = None
        # page id -> node (1:1 — a live node's page is ref-held, so an
        # id can back only one node at a time). The offload tier asks
        # `holds_page` to tell a cache-only share (spill the bytes,
        # leave the index copy reclaimable) from a genuine cross-slot
        # share (keep resident); the allocator's write path asks
        # `forget_page` to turn an index-only share exclusive without
        # a copy-on-write allocation.
        self._by_page: dict[int, _Node] = {}
        self._ticks = 0
        # Decision provenance, the int4_paths pattern: cumulative counts
        # surfaced via describe() and mirrored into the registry.
        self.hits = 0
        self.misses = 0
        self.deduped_pages = 0
        self.evictions = 0
        self.inserted_pages = 0
        self.reused_tokens = 0

    # --- introspection ---

    def page_count(self) -> int:
        return self._pages

    def holds_page(self, page: int) -> bool:
        return page in self._by_page

    def node_count(self) -> int:
        n = 0
        stack = [self.root]
        while stack:
            node = stack.pop()
            n += len(node.children)
            stack.extend(node.children.values())
        return n

    def describe(self) -> dict:
        return {
            "pages": self._pages,
            "max_pages": self.max_pages,
            "nodes": self.node_count(),
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "inserted_pages": self.inserted_pages,
            "reused_tokens": self.reused_tokens,
        }

    def _tick(self) -> int:
        self._ticks += 1
        return self._ticks

    def _publish_sizes(self) -> None:
        telemetry.set_gauge("roundtable_prefix_cache_pages", self._pages,
                            engine=self.engine)

    # --- write path ---

    def insert(self, state) -> int:
        """Index every COMPLETE page of a committed slot (PagedKVCache.
        commit calls this). New blocks take one pool reference each;
        blocks already present keep their existing page (first writer
        wins — the bytes are content-equal by construction, and keeping
        the older page preserves its accumulated sharing). Returns how
        many new pages were indexed."""
        ps = self.page_size
        n_pages = min(len(state.tokens) // ps, len(state.pages))
        node = self.root
        added = 0
        tick = self._tick()
        path = []
        for j in range(n_pages):
            block = tuple(state.tokens[j * ps:(j + 1) * ps])
            child = node.children.get(block)
            if child is None:
                page = state.pages[j]
                child = _Node(parent=node, block=block, page=page)
                node.children[block] = child
                self.kv.ref(page)
                self._pages += 1
                self._by_page[page] = child
                added += 1
            elif (self.state_store is not None
                  and child.page != state.pages[j]):
                # A model with recurrent state RE-WRITES the pages it
                # re-scans (its state stood before its pages' frontier),
                # so a committing slot often holds its own copy of a
                # block the index already has. The copy is content-equal
                # by construction: the slot takes the index's page and
                # frees its own, or every knight of a discussion would
                # keep a whole transcript of duplicates (my chip run,
                # PR 27: the 640-page pool full, admissions shed).
                self.kv.ref(child.page)
                self.kv.unref(state.pages[j])
                state.pages[j] = child.page
                self.deduped_pages += 1
            child.tick = tick
            node = child
            path.append(child)
        if self.state_store is not None:
            self.state_store.bind_nodes(state.name, path)
        if added:
            self.inserted_pages += added
            telemetry.inc("roundtable_prefix_cache_inserted_pages_total",
                          added, engine=self.engine)
            self._publish_sizes()
        if self._pages > self.max_pages:
            self.reclaim(want=self._pages - self.max_pages)
        return added

    # --- read path ---

    def match(self, tokens: list[int]) -> list[_Node]:
        """The longest chain of complete-block nodes prefixing `tokens`
        (LRU-refreshed). Content-verified: children are keyed by the
        literal token tuple, so a match IS prefix equality."""
        ps = self.page_size
        node = self.root
        out: list[_Node] = []
        tick = self._tick()
        j = 0
        while (j + 1) * ps <= len(tokens):
            child = node.children.get(tuple(tokens[j * ps:(j + 1) * ps]))
            if child is None:
                break
            child.tick = tick
            out.append(child)
            node = child
            j += 1
        return out

    def attach(self, name: str, tokens: list[int],
               pinned: tuple[str, ...] = ()) -> int:
        """Raise slot `name`'s cached coverage to the longest complete-
        page prefix of `tokens` present in the index, by aliasing (same
        replica) or copying (cross-replica / boundary) the matched
        pages. Returns the new covered token count, or 0 when the index
        could not extend the slot's own reuse. Respects the at-least-
        one-token-fed rule: coverage never reaches len(tokens)."""
        cap = len(tokens) - 1
        if cap < self.page_size:
            return 0
        nodes = self.match(tokens)
        n = min(len(nodes), cap // self.page_size)
        state = self.kv._slots.get(name)
        have = len(state.tokens) if state is not None else 0
        if n <= 0 or n * self.page_size <= have:
            if not nodes:
                self.misses += 1
                telemetry.inc("roundtable_prefix_cache_misses_total",
                              engine=self.engine)
            return 0
        hi = n * self.page_size
        self.kv.adopt_span(name, [nd.page for nd in nodes[:n]],
                           lo=have, hi=hi, pinned=pinned)
        state = self.kv._slots[name]
        state.tokens = list(tokens[:hi])
        gained = hi - have
        self.hits += 1
        self.reused_tokens += gained
        _note_hit()
        telemetry.inc("roundtable_prefix_cache_hits_total",
                      engine=self.engine)
        telemetry.inc("roundtable_prefix_reused_tokens_total", gained,
                      engine=self.engine)
        return hi

    def attach_rows(self, names: list[str],
                    all_tokens: list[list[int]], offsets: list[int],
                    pinned: tuple[str, ...] = ()) -> int:
        """The per-batch consult `_prepare_batch` runs after its
        own-slot reuse_plan pass. Mutates `offsets` in place; returns
        the tokens the index served."""
        gained = 0
        for i, name in enumerate(names):
            if name.startswith("__warmup_"):
                continue
            got = self.attach(name, all_tokens[i], pinned)
            if got > offsets[i]:
                gained += got - offsets[i]
                offsets[i] = got
        return gained

    # --- eviction / lifecycle ---

    def _evictable_leaves(self, replica: Optional[int]) -> list[_Node]:
        out = []
        stack = list(self.root.children.values())
        while stack:
            node = stack.pop()
            if node.children:
                stack.extend(node.children.values())
            elif self.kv.refcount(node.page) == 1 and (
                    replica is None
                    or self.kv.replica_of_page(node.page) == replica):
                out.append(node)
        return out

    def reclaim(self, replica: Optional[int] = None, want: int = 1) -> int:
        """Evict up to `want` LRU refcount-0 leaf nodes (optionally
        restricted to one data replica's pages), unref'ing their pages
        back to the pool. Interior nodes become leaves as their children
        go and are picked up by subsequent passes. Returns pages freed."""
        freed = 0
        while freed < want:
            leaves = self._evictable_leaves(replica)
            if not leaves:
                break
            victim = min(leaves, key=lambda nd: nd.tick)
            # One pass evicts the oldest chain suffix available, not one
            # node per full rescan.
            while victim is not None and freed < want:
                parent = victim.parent
                del parent.children[victim.block]
                self._drop_snapshot(victim)
                self.kv.unref(victim.page)
                self._pages -= 1
                self._by_page.pop(victim.page, None)
                freed += 1
                self.evictions += 1
                victim = None
                if (parent is not self.root and not parent.children
                        and self.kv.refcount(parent.page) == 1
                        and (replica is None
                             or self.kv.replica_of_page(parent.page)
                             == replica)):
                    victim = parent
        if freed:
            telemetry.inc("roundtable_prefix_cache_evictions_total",
                          freed, engine=self.engine)
            self._publish_sizes()
        return freed

    def forget_page(self, page: int) -> bool:
        """Drop the node backing `page` AND its whole subtree (the
        subtree's chain meaning includes the dropped block, so it can
        never be matched again) — the write path calls this when a slot
        is about to diverge inside a page whose ONLY other holder is
        the index: forgetting makes the page exclusive for free, where
        copy-on-write would burn an allocation and a dispatch to
        preserve an entry this slot's own divergence is invalidating."""
        node = self._by_page.get(page)
        if node is None:
            return False
        del node.parent.children[node.block]
        stack = [node]
        dropped = 0
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            self._drop_snapshot(n)
            self.kv.unref(n.page)
            self._by_page.pop(n.page, None)
            self._pages -= 1
            dropped += 1
        self.evictions += dropped
        telemetry.inc("roundtable_prefix_cache_evictions_total",
                      dropped, engine=self.engine)
        self._publish_sizes()
        return True

    def _drop_snapshot(self, node: _Node) -> None:
        if node.snap is not None and self.state_store is not None:
            self.state_store.drop(node.snap)
        node.snap = None

    def drop_all(self) -> int:
        """Unref every indexed page and clear the tree (flush/drain)."""
        dropped = self._pages
        stack = list(self.root.children.values())
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            self._drop_snapshot(node)
            self.kv.unref(node.page)
        self.root = _Node()
        self._pages = 0
        self._by_page.clear()
        self._publish_sizes()
        return dropped

    def clear(self, unref: bool = True) -> None:
        """Drop the index; unref=False when the pool itself was
        reallocated (revive_if_dead) and the refs table is already
        gone."""
        if unref:
            self.drop_all()
            return
        if self.state_store is not None:
            self.state_store.drop_all_snapshots()
        self.root = _Node()
        self._pages = 0
        self._by_page.clear()
        self._publish_sizes()
