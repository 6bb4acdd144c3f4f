"""Host-RAM KV offload tier — spill idle sessions' pages, restore
byte-identical, never re-prefill.

A consensus round can sit for minutes while humans type, and PR 4's
scheduler answers HBM pressure by either queueing admissions or letting
the page allocator EVICT idle slots — destroying exactly the caches that
make the next turn cheap. This tier (ISSUE 7 tentpole, the multi-tier KV
store RTP-LLM runs in production — PAPERS.md) gives idle sessions a
third state: their pages move to host RAM, their slot records leave the
pool, and the session's next submit brings them back — `device_put` into
freshly acquired pages, byte-identical — so `reuse_plan` sees the full
committed prefix and the turn prefills only its real delta, exactly as
if the session had never left.

Page-identity bookkeeping is SESSION-level: a span aliased by several of
the session's own knights (the intra-session donor/leader sharing of
PR 4, or prefix-cache attaches) spills its bytes ONCE and restores into
ONE fresh page that every sibling re-maps — the aliasing survives the
round trip instead of inflating into per-knight copies. Only pages some
holder OUTSIDE the session (another session's slot, an earlier spill's
resident hold) still references stay in HBM under a per-mapping tier
reference — they cost no extra memory and must stay byte-stable anyway;
pages shared only with the prefix-cache index spill too (the index copy
stays independently reclaimable under pressure, and restore never
depends on it surviving).

Compile discipline: the fetch/write programs run in fixed WIDTH-page
chunks (short chunks padded with the scratch page — never read, any
bytes), so each compiles exactly ONE shape; `engine.warmup()` warms both,
and under ROUNDTABLE_RECOMPILE_STRICT=1 the restore path compiles
nothing in steady state (the ISSUE 7 acceptance bar).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import telemetry
from .kvcache import session_of

# Pages moved per fetch/write dispatch. Spills are rare (idle-session
# boundaries, not the serving hot path); 8 keeps padding waste small
# (the narrowest of paging.COPY_WIDTHS, for the same reason).
WIDTH = 8


def offload_enabled(flag: Optional[bool]) -> bool:
    """Config value wins, then ROUNDTABLE_KV_OFFLOAD=0/1, then ON
    (prefix_cache.env_flag — one parsing rule for both kill-switches)."""
    from .prefix_cache import env_flag
    return env_flag(flag, "ROUNDTABLE_KV_OFFLOAD")


@dataclass
class SpilledSlot:
    """One slot's layout while its session is spilled. `entries[j]` is
    ("kept", page_id) for a page left resident under a tier reference,
    or ("host", row) indexing the session record's host store. Host
    entries are keyed by STORE ROW, never by the old pool page id — the
    old page was freed, its id can be reallocated to unrelated content,
    and an id-keyed dedup across spill calls would silently serve a
    stale spill's bytes into a reborn page's slot."""

    tokens: list[int]
    replica: int
    entries: list[tuple[str, int]] = field(default_factory=list)


@dataclass
class SpilledSession:
    """One session's spill record: per-slot layouts plus the host page
    store (rows deduped per spill call, while the pages were alive)."""

    slots: dict[str, SpilledSlot] = field(default_factory=dict)
    # Per layer: (k, v) stacked [n_rows, page, K, D] numpy.
    host: list[tuple[np.ndarray, np.ndarray]] = field(
        default_factory=list)
    replicas: list[int] = field(default_factory=list)  # per store row

    def n_rows(self) -> int:
        return len(self.replicas)

    def fully_host_resident(self) -> bool:
        """No "kept" pool pages — the ONE definition of "this record
        can cross engines": restorable_sessions() reports by it and
        adopt() filters by it (a record still referencing pool pages
        would alias unrelated content on a pool that never held
        them)."""
        return not any(kind == "kept"
                       for srec in self.slots.values()
                       for kind, _p in srec.entries)

    def host_bytes(self) -> int:
        return sum(a.nbytes for layer in self.host for a in layer)

    def append_rows(self, fetched, replicas: list[int]) -> None:
        if self.host:
            self.host = [
                tuple(np.concatenate([a0, a1]) for a0, a1 in zip(old, new))
                for old, new in zip(self.host, fetched)]
        else:
            self.host = fetched
        self.replicas.extend(replicas)


class HostOffloadTier:
    """Spill/restore for one paged InferenceEngine's sessions."""

    def __init__(self, engine):
        self.engine = engine
        self._spilled: dict[str, SpilledSession] = {}
        self.spills = 0
        self.restores = 0
        self._name = getattr(engine.cfg, "name", "engine")

        from jax.sharding import NamedSharding, PartitionSpec
        rep = NamedSharding(engine.mesh, PartitionSpec())

        @jax.jit
        def fetch_pages(pools, ids):
            # Replicated outputs so the host read works on any mesh
            # (the engines' host_read contract).
            return [tuple(jax.lax.with_sharding_constraint(p[ids], rep)
                          for p in layer) for layer in pools]

        @partial(jax.jit, donate_argnums=(0,))
        def write_pages(pools, ids, data):
            # Pad rows target the scratch page with zero bytes — never
            # read, and duplicate scratch indices only ever race other
            # pads (real ids are distinct fresh allocations).
            return [tuple(p.at[ids].set(d.astype(p.dtype))
                          for p, d in zip(layer, new))
                    for layer, new in zip(pools, data)]

        self._fetch_pages = fetch_pages
        self._write_pages = write_pages

    # --- introspection ---

    def spilled_sessions(self) -> list[str]:
        return list(self._spilled)

    def restorable_sessions(self) -> list[str]:
        """Sessions whose spill records are FULLY host-resident (no
        "kept" pool pages) — exactly the set adopt() will accept onto
        a fresh engine's tier. The supervisor uses this when an
        evacuation dies mid-cycle: these sessions survive the pool
        even though the evacuation itself failed."""
        return [s for s, rec in self._spilled.items()
                if rec.fully_host_resident()]

    def has(self, session: str) -> bool:
        return session in self._spilled

    def host_bytes(self) -> int:
        return sum(rec.host_bytes() for rec in self._spilled.values())

    def describe(self) -> dict:
        return {
            "spilled_sessions": len(self._spilled),
            "spilled_slots": sum(len(rec.slots)
                                 for rec in self._spilled.values()),
            "host_bytes": self.host_bytes(),
            "spills": self.spills,
            "restores": self.restores,
        }

    def _publish(self) -> None:
        telemetry.set_gauge("roundtable_kv_spilled_sessions",
                            len(self._spilled), engine=self._name)
        telemetry.set_gauge("roundtable_kv_host_bytes",
                            self.host_bytes(), engine=self._name)

    # --- device chunk helpers (fixed WIDTH shapes) ---

    def _fetch(self, page_ids: list[int],
               replica: int) -> list[tuple[np.ndarray, ...]]:
        kv = self.engine.kv
        scratch = kv.scratch_page(replica)
        # Combined pools (ISSUE 11): quantized pools spill their scale
        # arrays as extra "layers" in the same host record — int8
        # payload + scales is the whole state, so restore is exactly
        # lossless and spill bandwidth drops with the payload width.
        per_layer: list[list[tuple[np.ndarray, ...]]] = [
            [] for _ in kv.combined_pools()]
        from . import compile_watch
        for start in range(0, len(page_ids), WIDTH):
            ids = page_ids[start:start + WIDTH]
            n = len(ids)
            ids = ids + [scratch] * (WIDTH - n)
            with compile_watch.label("kv_spill[fetch]",
                                     engine=self._name):
                out = self._fetch_pages(kv.combined_pools(),
                                        jnp.asarray(ids, jnp.int32))
            for li, layer in enumerate(out):
                per_layer[li].append(tuple(np.asarray(a)[:n]
                                           for a in layer))
        arity = [len(layer) for layer in kv.combined_pools()]
        return [tuple(np.concatenate([c[j] for c in chunks])
                      if chunks else np.zeros(0) for j in range(width))
                for chunks, width in zip(per_layer, arity)]

    def _write(self, page_ids: list[int],
               host: list[tuple[np.ndarray, np.ndarray]],
               rows: list[int], replica: int) -> None:
        """Write `host` store rows `rows` into pool pages `page_ids`."""
        kv = self.engine.kv
        scratch = kv.scratch_page(replica)
        from . import compile_watch, deadlines
        for start in range(0, len(page_ids), WIDTH):
            ids = page_ids[start:start + WIDTH]
            sel = rows[start:start + WIDTH]
            n = len(ids)
            ids = ids + [scratch] * (WIDTH - n)
            data = []
            for layer in host:
                rows_l = []
                for a_all in layer:
                    a = a_all[sel]
                    if n < WIDTH:
                        pad = (WIDTH - n,) + a.shape[1:]
                        a = np.concatenate([a, np.zeros(pad, a.dtype)])
                    rows_l.append(jnp.asarray(a))
                data.append(tuple(rows_l))
            with compile_watch.label("kv_restore[write]",
                                     engine=self._name):
                pools = self._write_pages(
                    kv.combined_pools(), jnp.asarray(ids, jnp.int32),
                    data)
            with deadlines.commit_guard():
                kv.set_combined(pools)

    def warm(self) -> None:
        """Compile-and-stabilize the fetch/write programs (ONE shape
        each) so a first spill/restore in steady state compiles nothing
        — run twice for the donated-buffer layout fixpoint, exactly like
        engine.warmup's programs."""
        kv = self.engine.kv
        scratch = kv.scratch_page(0)
        for _ in range(2):
            host = self._fetch([scratch], 0)
            self._write([scratch], host, [0], 0)

    # --- spill ---

    def spill_session(self, session: str) -> int:
        """Move every slot of `session` out of the pool. Keep-resident
        (under one tier reference per mapping) ONLY pages some holder
        OUTSIDE the session still references — another session's slot,
        or an earlier spill's resident hold; everything else, including
        spans aliased between the session's own knights and pages shared
        only with the prefix-cache index, spills its bytes ONCE per
        unique page. Returns the number of slots spilled. The caller
        owns engine serialization (serve lock / scheduler thread)."""
        kv = self.engine.kv
        cache = getattr(kv, "prefix_cache", None)
        names = [n for n in kv.slot_names() if session_of(n) == session]
        # Pass 1 (no releases yet, so refcounts are stable): how many of
        # THIS session's own slots map each page — sibling aliases must
        # not count as external holders, or intra-session shared spans
        # (exactly the pages donor/leader sharing deduplicated) would
        # all stay resident and the spill would free almost nothing.
        own_maps: dict[int, int] = {}
        states = {}
        for name in names:
            state = kv._slots.get(name)
            if state is None:
                continue
            states[name] = state
            for p in state.pages:
                own_maps[p] = own_maps.get(p, 0) + 1
        rec = self._spilled.get(session) or SpilledSession()
        tier_refs: dict[int, int] = {}  # refs THIS call took, per page
        # Dedup WITHIN this call only (page -> store row): the pages are
        # alive and distinct for the duration, which is exactly the
        # window where id-based identity is sound.
        call_rows: dict[int, int] = {}
        spill_ids: list[int] = []
        empty: list[str] = []
        count = 0
        for name, state in states.items():
            if not state.tokens or not state.pages:
                # Release in pass 2 with the rest: dropping a sibling's
                # mappings mid-pass would skew the external-holder math
                # for pages it shares with later siblings.
                empty.append(name)
                continue
            entries: list[tuple[str, int]] = []
            for p in state.pages:
                external = (kv.refcount(p) - own_maps[p]
                            - (1 if cache is not None
                               and cache.holds_page(p) else 0)
                            - tier_refs.get(p, 0))
                if external >= 1:
                    kv.ref(p)          # per-mapping resident hold
                    tier_refs[p] = tier_refs.get(p, 0) + 1
                    entries.append(("kept", p))
                else:
                    row = call_rows.get(p)
                    if row is None:
                        row = rec.n_rows() + len(spill_ids)
                        call_rows[p] = row
                        spill_ids.append(p)
                    entries.append(("host", row))
            old = rec.slots.get(name)
            if old is not None:
                # Re-spill over a stale record (slot repopulated while
                # spilled): drop the superseded entries' resident holds
                # — the old host rows stay (row indices must remain
                # stable) and free with the record at restore.
                for kind, p in old.entries:
                    if kind == "kept":
                        kv.unref(p)
            rec.slots[name] = SpilledSlot(
                tokens=list(state.tokens), replica=state.replica,
                entries=entries)
            count += 1
        if spill_ids:
            # Fetch BEFORE any release: the pages are still alive under
            # their slots' mappings.
            rec.append_rows(self._fetch(spill_ids, 0),
                            [kv.replica_of_page(p) for p in spill_ids])
        # Pass 2: drop the slots (unrefs every mapping; host-spilled
        # pages free once their last sibling mapping goes).
        for name in states:
            if name in rec.slots or name in empty:
                kv.release(name)
        if count:
            self._spilled[session] = rec
            self.spills += count
            telemetry.inc("roundtable_kv_spills_total", count,
                          engine=self._name)
            self._publish()
        return count

    # --- restore ---

    def restore_session(self, session: str,
                        pinned: tuple[str, ...] = ()) -> int:
        """Bring a spilled session back, all-or-nothing: ONE fresh page
        per unique spilled page (sibling slots re-map it, so
        intra-session aliasing survives the round trip), host bytes
        device_put back, kept pages re-aliased (the tier's reference
        transfers to the slot mapping) — byte-identical to never having
        spilled. On failure (pool exhaustion mid-restore) every effect
        of this call is undone and the record re-filed intact. Returns
        the number of slots restored."""
        rec = self._spilled.pop(session, None)
        if rec is None:
            return 0
        kv = self.engine.kv
        pin = tuple(pinned) + tuple(rec.slots)
        fresh: dict[int, int] = {}      # store row -> fresh page
        mapped: set[int] = set()        # fresh pages already mapped once
        assigned: list[str] = []
        stale: list[str] = []
        try:
            # Staleness FIRST (a slot repopulated while spilled keeps
            # its live state), then materialize only rows a live slot's
            # entries still reference — allocating for stale records
            # would evict idle slots and reclaim warm cache nodes to
            # build pages the cleanup immediately frees.
            live = [name for name, srec in rec.slots.items()
                    if not getattr(kv._slots.get(name), "pages", None)]
            need_rows = sorted({p for name in live
                                for kind, p in rec.slots[name].entries
                                if kind == "host"})
            for row in need_rows:
                fresh[row] = kv._alloc_page(pin, rec.replicas[row])
            if fresh:
                self._write([fresh[r] for r in need_rows], rec.host,
                            need_rows, 0)
            for name, srec in rec.slots.items():
                state = kv.acquire(name, pin)
                if state.pages:
                    # Repopulated while spilled (pre-checked above, but
                    # re-verified on the live acquire) — keep the live
                    # state.
                    stale.append(name)
                    continue
                state.replica = srec.replica
                pages: list[int] = []
                for kind, p in srec.entries:
                    if kind == "kept":
                        pages.append(p)          # tier ref transfers
                    else:
                        fp = fresh[p]
                        if fp in mapped:
                            kv.ref(fp)           # sibling re-alias
                        else:
                            mapped.add(fp)
                        pages.append(fp)
                state.pages = pages
                state.tokens = list(srec.tokens)
                assigned.append(name)
        except BaseException:
            # Undo completely: re-take the tier's kept holds for
            # already-assigned slots (their release below drops the
            # transferred mapping refs), release those slots, free the
            # fresh pages nothing maps anymore, re-file the record.
            for name in assigned:
                for kind, p in rec.slots[name].entries:
                    if kind == "kept":
                        kv.ref(p)
                kv.release(name)
            for fp in fresh.values():
                if fp not in mapped:
                    kv.unref(fp)
            self._spilled[session] = rec
            raise
        # Stale slots consumed their records: drop the tier's holds AND
        # the fresh pages their skipped entries left unmapped — a fresh
        # page no slot adopted would otherwise leak out of the pool
        # until revive (review finding, reproduced).
        for name in stale:
            for kind, p in rec.slots[name].entries:
                if kind == "kept":
                    kv.unref(p)
        for fp in fresh.values():
            if fp not in mapped:
                kv.unref(fp)
        count = len(assigned)
        self.restores += count
        if count:
            telemetry.inc("roundtable_kv_restores_total", count,
                          engine=self._name)
        self._publish()
        return count

    def restore_for(self, names: list[str],
                    pinned: tuple[str, ...] = ()) -> int:
        """Restore every spilled session appearing among `names` —
        the engine-side seam `_prepare_batch` runs before reuse_plan, so
        a spilled session resumes transparently on ANY serving path
        (direct generate_batch or scheduler submit)."""
        if not self._spilled:
            return 0
        restored = 0
        # sorted: restore order drives _alloc_page's call sequence, and
        # the paged allocator's multi-host lockstep contract is
        # "deterministic given the call sequence" — set iteration order
        # is per-process hash noise.
        for session in sorted({session_of(n) for n in names}):
            if session and session in self._spilled:
                restored += self.restore_session(session, pinned)
        return restored

    # --- drain / evacuation / teardown ---

    def evacuate(self, sessions: Optional[list[str]] = None) -> dict:
        """Move sessions FULLY to host RAM and return a restorable
        manifest (ISSUE 12): first spill every still-resident targeted
        session (slots in the pool spill through spill_session — pages
        with external holders stay resident under tier refs), then
        convert those kept-resident holds to host bytes and drop them,
        so every targeted session's state lives entirely in host RAM —
        pool-independent, which is exactly what lets the supervisor
        graft the records onto a REBUILT engine's tier (adopt()) and
        restore byte-identical KV across an engine restart.

        `sessions=None` targets everything (the fleet.drain shape:
        after the flush released every slot and the index, the tier's
        kept pages are the only thing between a drained pool and zero
        pages in use). A subset selector evacuates only those sessions;
        the rest keep their pool/tier state untouched. The caller owns
        engine serialization (serve lock / scheduler thread).

        Manifest: {"pages_moved", "slots_spilled", "host_bytes",
        "sessions": {session: {"slots", "host_rows", "host_bytes"}}} —
        every listed session restores via restore_session/restore_for
        (or transparently at its next submit)."""
        kv = self.engine.kv
        targets = None if sessions is None else set(sessions)
        # Pass 1: spill targeted sessions whose slots still sit in the
        # pool (the supervisor path — fleet.drain's flush has usually
        # emptied the pool already, making this a no-op there).
        resident = sorted({session_of(n) for n in kv.slot_names()}
                          - {""})
        slots_spilled = 0
        for s in resident:
            if targets is None or s in targets:
                slots_spilled += self.spill_session(s)
        moved = 0
        for session, rec in self._spilled.items():
            if targets is not None and session not in targets:
                continue
            kept: dict[int, int] = {}   # page -> #mappings in this rec
            for srec in rec.slots.values():
                for kind, p in srec.entries:
                    if kind == "kept":
                        kept[p] = kept.get(p, 0) + 1
            if not kept:
                continue
            # Per-call page->row map (same identity rule as
            # spill_session: the pages are alive right now, so ids are
            # sound for the duration of this call only).
            ids = list(kept)
            base = rec.n_rows()
            rows = {p: base + i for i, p in enumerate(ids)}
            rec.append_rows(self._fetch(ids, 0),
                            [kv.replica_of_page(p) for p in ids])
            moved += len(ids)
            for srec in rec.slots.values():
                srec.entries = [("host", rows[p]) if kind == "kept"
                                else (kind, p)
                                for kind, p in srec.entries]
            for p, n_maps in kept.items():
                for _ in range(n_maps):
                    kv.unref(p)
        if moved or slots_spilled:
            self._publish()
        manifest: dict = {
            "pages_moved": moved,
            "slots_spilled": slots_spilled,
            "host_bytes": 0,
            "sessions": {},
        }
        for session, rec in self._spilled.items():
            if targets is not None and session not in targets:
                continue
            b = rec.host_bytes()
            manifest["sessions"][session] = {
                "slots": len(rec.slots),
                "host_rows": rec.n_rows(),
                "host_bytes": b,
            }
            manifest["host_bytes"] += b
        return manifest

    def adopt(self, other: "HostOffloadTier",
              sessions: Optional[list[str]] = None) -> list[str]:
        """Graft another tier's spill records onto THIS tier (the
        supervisor's engine rebuild: the dead engine's evacuated
        sessions become the fresh engine's restorable sessions).
        Records must be fully host-resident — evacuate() first: a
        record still holding "kept" pool pages references a pool this
        tier has never seen, and restoring it would alias unrelated
        content. Such records are refused (left on `other`, named in
        no list) rather than corrupting the new pool. Returns the
        adopted session names.

        `sessions` selects a subset (ISSUE 17: cross-replica migration
        moves ONE session's record between two live engines' tiers —
        adopting everything would steal the source replica's other
        spilled sessions); None keeps the supervisor's adopt-all shape."""
        targets = None if sessions is None else set(sessions)
        adopted: list[str] = []
        for session, rec in list(other._spilled.items()):
            if targets is not None and session not in targets:
                continue
            if not rec.fully_host_resident():
                continue
            if session in self._spilled:
                continue  # this tier's own record wins
            self._spilled[session] = rec
            del other._spilled[session]
            adopted.append(session)
        if adopted:
            self._publish()
            other._publish()
        return adopted

    def drop_all(self) -> None:
        """Forget every spilled record WITHOUT touching the pool — for
        revive_if_dead, where the pools (and the refs table) were just
        reallocated and the kept-page references no longer exist."""
        self._spilled.clear()
        self._publish()
