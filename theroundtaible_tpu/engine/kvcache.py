"""Per-knight persistent KV-cache slots.

The reference keeps no model state between turns — every turn re-sends the
full transcript, so token cost grows quadratically with rounds
(reference src/utils/prompt.ts:60-77; SURVEY.md §3.1 "hot loops"). Here each
knight owns a slot: device-resident K/V for every layer plus the host-side
token ids already baked into it. On the next turn the engine prefills only
the delta beyond the longest common token prefix.

Layout per layer: [num_slots, max_seq_len, kv_heads, head_dim], position-
aligned (cache index s holds position s). Slots ride the "data" mesh axis,
kv heads the "model" axis (sharding.kv_cache_spec).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp

from .models.common import ModelConfig

# Session-namespaced slot names (ISSUE 4 satellite: two concurrent
# discussions both acquiring "lancelot" used to map to ONE slot and
# cross-contaminate KV through reuse_plan). The separator is the ASCII
# unit separator — no tokenizer/config surface produces it, so a scoped
# name can never collide with a legal knight name.
SESSION_SEP = "\x1f"


def scoped_slot(session: Optional[str], name: str) -> str:
    """The canonical session-namespaced slot name: `session␟name`.
    None/"" session returns the bare name (single-session legacy)."""
    return f"{session}{SESSION_SEP}{name}" if session else name


def session_of(name: str) -> str:
    """The session namespace of a (possibly scoped) slot name; "" for
    un-scoped names. Used to keep cross-knight prefix DONATION within
    one session: sessions are isolation domains (a faulted session's
    slot invalidation must never ripple into another's KV lineage)."""
    return name.split(SESSION_SEP, 1)[0] if SESSION_SEP in name else ""


@dataclass
class SlotState:
    """Host-side bookkeeping for one knight's slot."""

    slot_id: int
    name: str
    tokens: list[int] = field(default_factory=list)  # ids baked into cache


class SlotBook:
    """Host-side slot bookkeeping alone — LRU allocation, LCP reuse
    planning, donor search. KVCache adds the contiguous device arrays."""

    def __init__(self, num_slots: int):
        self.num_slots = num_slots
        self._slots: dict[str, SlotState] = {}
        self._free = list(range(num_slots))

    # --- slot allocation ---

    def acquire(self, name: str, pinned: tuple[str, ...] = ()) -> SlotState:
        """Get the named knight's slot, allocating on first use.

        `pinned` names are never evicted — generate_batch pins every knight
        of the in-flight batch so two batch rows can't share a slot_id.
        """
        if name in self._slots:
            # Refresh recency so eviction below is true LRU, not FIFO.
            self._slots[name] = self._slots.pop(name)
            return self._slots[name]
        if not self._free:
            # Evict the least-recently-used slot (dict order = recency,
            # refreshed on every acquire) that is not pinned by the batch.
            victim = next((n for n in self._slots if n not in pinned), None)
            if victim is None:
                raise RuntimeError(
                    f"KVCache has {self.num_slots} slots but "
                    f"{len(pinned)} knights are pinned in one batch — "
                    "raise num_slots in the tpu-llm adapter config")
            self.release(victim)
        slot_id = self._free.pop(0)
        state = SlotState(slot_id=slot_id, name=name)
        self._slots[name] = state
        return state

    def release(self, name: str) -> None:
        state = self._slots.pop(name, None)
        if state is not None:
            self._free.append(state.slot_id)

    def reset_slot(self, name: str) -> None:
        """Forget cached tokens (cache rows need no zeroing — the valid-length
        mask makes stale entries unreachable)."""
        if name in self._slots:
            self._slots[name].tokens = []

    def forget_all(self) -> None:
        """Drop every slot record. For buffer reallocation after donation
        death (revive_if_dead): nothing cached survives, so every later
        prefill starts from scratch."""
        self._slots.clear()
        self._free = list(range(self.num_slots))

    def flush(self) -> int:
        """Release every per-knight slot through the normal release path
        (graceful drain's KV flush, fleet.drain): paged caches decref
        and free their pages, contiguous slots return to the free list.
        Returns how many slots were flushed."""
        names = list(self._slots)
        for name in names:
            self.release(name)
        return len(names)

    def revive_if_dead(self) -> bool:
        """Reallocate device buffers if a failed donated dispatch deleted
        them (jax donate_argnums consumes inputs even when the program
        faults after transfer). Base SlotBook owns no buffers — caches
        that do (KVCache, PagedKVCache) override. Returns True iff fresh
        buffers were allocated (all cached content lost)."""
        return False

    def scratch_slot(self, pinned: tuple[str, ...] = ()) -> Optional[int]:
        """A slot id safe to use as a throwaway WRITE target — the
        scheduler's bucketed decode batch points its masked pad rows
        here (all pads write identical bytes, so the duplicate-index
        scatter is deterministic; a free slot's stale cells are
        unreachable behind valid-length masks and the next real acquire
        prefills over them). Returns a free slot's id, evicting the LRU
        unpinned slot first when none is free; the id is NOT allocated
        (it stays at the head of the free list until a real acquire
        claims it), so use it within the current dispatch only. None
        when every slot is pinned."""
        if not self._free:
            victim = next((n for n in self._slots if n not in pinned),
                          None)
            if victim is None:
                return None
            self.release(victim)
        return self._free[0]

    def slot_names(self) -> list[str]:
        return list(self._slots)

    def memory_ledger(self) -> dict:
        """Slot-occupancy accounting for the memory ledger (ISSUE 6):
        the host-side view trace_hooks.publish_memory_ledger turns
        into registry gauges. Contiguous layouts pay HBM per SLOT
        regardless of use, so `cached_tokens` vs capacity is the
        interesting waste number here."""
        in_use = len(self._slots)
        return {
            "layout": "contiguous",
            "slots_in_use": in_use,
            "num_slots": self.num_slots,
            "slot_occupancy": round(in_use / max(self.num_slots, 1), 3),
            "cached_tokens": sum(len(s.tokens)
                                 for s in self._slots.values()),
            "hbm_bytes": None,  # SlotBook owns no buffers
        }

    # --- prefix reuse ---

    @staticmethod
    def common_prefix_len(cached: list[int], new: list[int]) -> int:
        # native rt_lcp when built (falls back to a Python loop inside)
        from ..native import lcp
        return lcp(cached, new)

    def reuse_plan(self, name: str, tokens: list[int],
                   pinned: tuple[str, ...] = ()) -> tuple[int, int]:
        """(slot_id, reuse_len): how many leading tokens are already baked
        into the slot's cache. The caller prefills only tokens[reuse_len:].

        reuse_len is capped at len(tokens)-1 so at least one token is always
        fed (the model needs a last-token logit to start decoding)."""
        state = self.acquire(name, pinned)
        reuse = self.common_prefix_len(state.tokens, tokens)
        reuse = min(reuse, len(tokens) - 1)
        # Positions >= reuse are about to be overwritten by prefill/decode.
        # Truncate the record NOW: if the turn dies mid-flight (timeout),
        # the slot must not claim cache contents that were clobbered.
        state.tokens = state.tokens[:reuse]
        return state.slot_id, reuse

    def commit(self, name: str, tokens: list[int],
               index: bool = True) -> None:
        """Record that the slot's cache now covers exactly `tokens`.
        `index` exists for signature parity with PagedKVCache.commit
        (ISSUE 10: persona rows must not feed the cross-session prefix
        cache) — the contiguous layout has no index, so it is
        ignored."""
        del index
        self.acquire(name).tokens = list(tokens)

    def best_donor(self, name: str,
                   tokens: list[int]) -> tuple[Optional[SlotState], int]:
        """The OTHER slot sharing the longest committed token prefix with
        `tokens` — the cross-knight reuse seam (SURVEY.md §7.3 hard part 2):
        knights' prompts share the giant context+transcript preamble
        (orchestrator _build_turn_prompt lays shared text first), so knight
        B's fresh slot can copy knight A's K/V for the common span instead
        of re-prefilling it. Donor records are truncated by reuse_plan when
        they join a batch, so a donor never advertises positions that are
        about to be overwritten. Donation is INTRA-session only: sessions
        are isolation domains (scoped_slot), so a donor from another
        concurrent discussion is never consulted even when its token
        prefix happens to match."""
        best, best_len = None, 0
        scope = session_of(name)
        for state in self._slots.values():
            if state.name == name or not state.tokens:
                continue
            if session_of(state.name) != scope:
                continue
            n = self.common_prefix_len(state.tokens, tokens)
            if n > best_len:
                best, best_len = state, n
        return best, best_len


def share_prefixes(kv, names, all_tokens, offsets, *, min_shared: int,
                   add_share, flush_shares, prefill_span,
                   extra_pinned: tuple[str, ...] = (),
                   defer_span=None,
                   donor_ok=None,
                   decline_leader=None) -> tuple[list[int], int]:
    """Two-pass cross-knight shared-prefix reuse — THE algorithm, used by
    both serving engines so the donor cap, batch-common-prefix fold,
    l_shared clamp, laggard threshold and extra_prefill accounting cannot
    drift between them (SURVEY.md §7.3 hard part 2).

    (a) donor pass — a slot committed by an earlier call that shares a
        longer token prefix than a row's own history donates its span;
    (b) leader pass — within one batch, the row with the most cache
        coverage prefills the batch-wide common span ONCE and the
        laggards copy it.

    Callbacks own the device mechanics:
      add_share(donor_state, row_i, lo, hi) — queue/apply one span share
        (contiguous: K/V copy; paged: page aliasing);
      flush_shares() — dispatch queued shares (called after each pass so
        leader-sourced copies never read a pending span);
      prefill_span(row_i, lo, hi) — prefill that row's token span
        (ring-eligible when long).

    `extra_pinned`: slot names OUTSIDE this batch that must survive any
    eviction the passes trigger — the session scheduler pins every
    actively-decoding row while a joining batch runs its passes.

    `defer_span(m, lo, hi, followers)` (ISSUE 8, ragged admission):
    when given and the leader's cache does NOT yet cover the common
    span, the leader pass DISPATCHES NOTHING — the leader's offset
    stays at its own coverage (its span joins the live decode segment
    as ragged chunks), the laggards' offsets still raise to the span
    end, and the callback records (leader index, leader coverage, span
    end, [(laggard, its pre-raise coverage), ...]) so the caller can
    alias the laggards AFTER the leader's chunks have written the span
    (aliasing unwritten pages would be copy-on-write'd away by the
    leader's own write-exclusivity). A leader that already covers the
    span aliases immediately — the content exists.

    `donor_ok(donor_state, row_i)` (ISSUE 10): extra donor gate —
    multi-LoRA engines pass an adapter-identity check, since K/V baked
    under one adapter is wrong under another. Conservative by design:
    a rejected best donor is dropped, not re-searched (the prefill it
    would have saved is small next to serving wrong bytes). The
    LEADER pass needs no gate — lora engines only reach it for
    uniform-adapter batches (engine._prepare_batch suppresses mixed
    ones).

    `decline_leader(n_laggards)`: when given, a leader whose cache
    does not cover the common span yet prefills NOTHING for the others
    and the callback counts the decline — a model with recurrent state
    cannot hand laggards the leader's state at the span's end through
    this pass (every row scans the span itself, from the deepest
    snapshot it finds). A leader that already covers the span still
    aliases its pages: that is KV alone.

    Returns (updated offsets, leader-prefilled token count)."""
    b = len(names)
    pinned = tuple(names) + tuple(extra_pinned)
    offsets = list(offsets)
    extra_prefill = 0

    for i in range(b):
        cap = len(all_tokens[i]) - 1
        donor, dlen = kv.best_donor(names[i], all_tokens[i])
        dlen = min(dlen, cap)
        if donor is not None and donor_ok is not None \
                and not donor_ok(donor, i):
            donor = None
        if donor is not None and dlen - offsets[i] >= min_shared:
            add_share(donor, i, offsets[i], dlen)
            offsets[i] = dlen
    flush_shares()

    if b < 2:
        return offsets, extra_prefill
    shared = all_tokens[0]
    for t in all_tokens[1:]:
        shared = shared[:kv.common_prefix_len(shared, t)]
    l_shared = min(len(shared), min(len(t) for t in all_tokens) - 1)
    m = max(range(b), key=lambda i: offsets[i])
    laggards = [i for i in range(b)
                if i != m and l_shared - offsets[i] >= min_shared]
    if not laggards:
        return offsets, extra_prefill
    if offsets[m] < l_shared:
        if decline_leader is not None:
            decline_leader(len(laggards))
            return offsets, extra_prefill
        if defer_span is not None:
            defer_span(m, offsets[m], l_shared,
                       [(i, offsets[i]) for i in laggards])
            for i in laggards:
                offsets[i] = l_shared
            return offsets, extra_prefill
        prefill_span(m, offsets[m], l_shared)
        extra_prefill += l_shared - offsets[m]
        offsets[m] = l_shared
    leader = kv.acquire(names[m], pinned)
    for i in laggards:
        add_share(leader, i, offsets[i], l_shared)
        offsets[i] = l_shared
    flush_shares()
    return offsets, extra_prefill


class KVCache(SlotBook):
    """num_slots × num_layers of contiguous device KV plus SlotBook's
    bookkeeping. Layout per layer: [num_slots, max_seq_len, K, D]."""

    def __init__(self, cfg: ModelConfig, num_slots: int,
                 max_seq_len: Optional[int] = None, dtype=jnp.bfloat16,
                 sharding=None):
        super().__init__(num_slots)
        self.cfg = cfg
        self.max_seq_len = max_seq_len or cfg.max_seq_len
        shape = (num_slots, self.max_seq_len, cfg.num_kv_heads, cfg.head_dim)
        make = (lambda: jnp.zeros(shape, dtype)) if sharding is None else \
            (lambda: jax.device_put(jnp.zeros(shape, dtype), sharding))
        # Kept for revive_if_dead: reallocation after donation death.
        self._make = make
        self.layers: list[tuple[jax.Array, jax.Array]] = [
            (make(), make()) for _ in range(cfg.num_layers)]

    def revive_if_dead(self) -> bool:
        if not self.layers[0][0].is_deleted():
            return False
        self.layers = [(self._make(), self._make())
                       for _ in range(self.cfg.num_layers)]
        self.forget_all()
        return True

    def memory_ledger(self) -> dict:
        led = super().memory_ledger()
        k, _ = self.layers[0]
        led["hbm_bytes"] = 2 * k.size * k.dtype.itemsize * len(self.layers)
        return led
