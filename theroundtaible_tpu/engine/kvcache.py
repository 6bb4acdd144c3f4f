"""Per-knight KV slots: how they are NAMED, and the share planner.

The reference keeps no model state between turns — every turn re-sends the
full transcript, so token cost grows quadratically with rounds
(reference src/utils/prompt.ts:60-77; SURVEY.md §3.1 "hot loops"). Here each
knight owns a named slot of the page pool (engine/paging.py: PagedKVCache
holds the slots, their pages and the token ids baked into them), and on
the next turn the engine prefills only the delta beyond the longest
common token prefix.

What lives here is what every holder of a slot name shares: the
session-scoped naming (`scoped_slot`, `session_of`) and the two-pass
cross-knight share planner (`share_prefixes`), which decides WHICH spans
move between slots and leaves the page mechanics to its callbacks.
"""

from __future__ import annotations

from typing import Optional

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


def share_prefixes(kv, names, all_tokens, offsets, *, min_shared: int,
                   add_share, prefill_span,
                   extra_pinned: tuple[str, ...] = (),
                   defer_span=None,
                   donor_ok=None,
                   decline_leader=None) -> tuple[list[int], int]:
    """Two-pass cross-knight shared-prefix reuse — THE algorithm: the
    donor cap, batch-common-prefix fold, l_shared clamp, laggard
    threshold and extra_prefill accounting in one place (SURVEY.md §7.3
    hard part 2).

    (a) donor pass — a slot committed by an earlier call that shares a
        longer token prefix than a row's own history donates its span;
    (b) leader pass — within one batch, the row with the most cache
        coverage prefills the batch-wide common span ONCE and the
        laggards take it.

    Callbacks own the device mechanics:
      add_share(donor_state, row_i, lo, hi) — one span share (page
        aliasing; its boundary-page copies queue on the cache and issue
        before any program reads the pools);
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
    span aliases immediately — the content exists. A callback that
    returns False has recorded nothing: the span cannot be deferred
    (below), and `decline_leader` takes it.

    `donor_ok(donor_state, row_i)` (ISSUE 10): extra donor gate —
    multi-LoRA engines pass an adapter-identity check, since K/V baked
    under one adapter is wrong under another. Conservative by design:
    a rejected best donor is dropped, not re-searched (the prefill it
    would have saved is small next to serving wrong bytes). The
    LEADER pass needs no gate — lora engines only reach it for
    uniform-adapter batches (engine._prepare_batch suppresses mixed
    ones).

    `decline_leader(n_laggards)`: given by a model with recurrent
    state, whose laggards need the leader's STATE at the span's end and
    not its pages alone. Its deferred pass is the one above — the
    leader scans the span once and leaves its state at a page boundary,
    the laggards start from there when they unblock
    (hybrid_state.expect) — wherever `defer_span` can have that state
    kept. Where it cannot (it returns False), or the admission is not
    deferred, the leader prefills NOTHING for the others and the
    callback counts the decline: every row scans the span itself, from
    the deepest snapshot it finds. A leader that already covers the
    span still aliases its pages: that is KV alone.

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
        if defer_span is not None and defer_span(
                m, offsets[m], l_shared,
                [(i, offsets[i]) for i in laggards]) is not False:
            for i in laggards:
                offsets[i] = l_shared
            return offsets, extra_prefill
        if decline_leader is not None:
            decline_leader(len(laggards))
            return offsets, extra_prefill
        prefill_span(m, offsets[m], l_shared)
        extra_prefill += l_shared - offsets[m]
        offsets[m] = l_shared
    leader = kv.acquire(names[m], pinned)
    for i in laggards:
        add_share(leader, i, offsets[i], l_shared)
        offsets[i] = l_shared
    return offsets, extra_prefill
