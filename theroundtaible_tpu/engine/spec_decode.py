"""Speculative decoding on the shared batch (ISSUE 9 + ISSUE 13).

int8 decode sits at 0.63-0.69 of the HBM-streaming ceiling — past
kernel wins the only way above the roofline is accepting more than one
token per forward pass. This module is the HOST side of that: the
drafter abstraction (n-gram, draft-model, LoRA-draft-head), the chain
and TREE acceptance rules, and the per-row adaptive throttle with
re-probe hysteresis. The DEVICE side is the PR-8 ragged seam: a verify
dispatch packs each speculating row's candidates as short multi-token
runs in the flat token buffer and scores every draft position in ONE
forward (engine._ragged_dispatch with a static `score_width` —
build_ragged_batch shapes stay a function of the token budget alone,
so mixed chain/tree/no-spec compositions compile nothing).

Drafters (ISSUE 13 — the `Drafter` protocol):

- ``ngram`` — the PR-9 zero-model prompt-lookup drafter. Roundtable
  transcripts are unusually repetitive (quoted proposals, score
  scaffolding, knight boilerplate recur verbatim across rounds), so an
  n-gram lookup over the row's OWN prompt plus committed output
  proposes long runs — but ONLY on scripted/repetitive traffic. On
  sampled real-weights traffic the lookup collapses and the throttle
  quietly turns speculation off fleet-wide (SPEC_r09's acceptance 1.0
  was a property of the scripted rounds, not the mechanism).
- ``model`` — a draft model served as EXTRA ROW SETS on the SAME
  engine: each target row gets a shadow draft slot in the same paged
  pool, and drafting dispatches are ordinary ragged dispatches with a
  `params` override (the draft checkpoint shares the ModelConfig
  shapes, so no second engine and no new compile shapes — different
  VALUES through already-warm programs). Default draft weights are the
  engine's own params (the distillation placeholder: zero extra HBM,
  proposals = the target's own greedy chain — on sampled traffic
  acceptance is then exactly the sampler's peakedness, which is what a
  well-distilled drafter approaches).
- ``lora`` — drafting as an ADAPTER: the draft head is a LoRA pair in
  the PR-10 `LoraStore`, so the drafter is hot-swappable per workload
  through the store's existing setter (zero recompiles), costs
  rank·(in+out) bytes, and draft rows ride the normal per-token
  adapter ids. RTP-LLM (PAPERS.md) ships draft-model speculation over
  continuous batching in production; the heterogeneous-LoRA-serving
  line motivates serving the drafter as just another adapter.

Acceptance (the output-invariance contract):

- The verify run for a chain row is ``[last, d_0, ..., d_{k-1}]`` fed
  at positions ``valid..valid+k``. The causal mask means the scored
  logits at the row of ``last`` are EXACTLY what plain decode would
  compute, the logits at ``d_0`` are exact given ``d_0`` in context,
  and so on.
- Greedy: the device returns per-position argmax ``t_0..t_k``; the
  accepted prefix is the longest ``j`` with ``d_j == t_j`` and the row
  commits ``t_0..t_a`` (the first mismatch — or the bonus token after a
  fully-accepted draft — rides free). Byte-identical to 1-token decode
  by construction.
- Sampled: the device SAMPLES ``t_j`` from each position's filtered
  distribution (the same sample_token_batch the decode loop uses) and
  the host accepts while ``d_j == t_j``. For a DETERMINISTIC drafter
  (point mass at ``d_j``) this is exactly standard rejection sampling:
  acceptance fires with probability ``p(d_j)``, and the first
  mismatching ``t_j`` is distributed as the renormalized residual — so
  the emitted stream is an exact ancestral sample of the target model.

Tree acceptance (ISSUE 13, `accept_tree`): a token TREE is expanded
into its root-to-leaf PATHS, each path a separate ``[last, path...]``
run of the SAME verify dispatch (per-path page tables keep sibling
K/V writes apart — engine/scheduler.py owns that metadata; causality
within each run is ordinary, which is why tree verify needs no new
Pallas kernel). The host then walks the tree from the root: at depth
j it takes the device's token for the CURRENT path at position j and
emits it — that token is a genuine target-model token (argmax or
exact sample) given the emitted prefix, so the emitted stream is
exact REGARDLESS of how the walk continues; if some path's node at
depth j equals the emitted token, the walk descends that path (its
deeper positions condition on exactly the accepted prefix) and the
edge counts as accepted. Greedy: at most one child can match the
argmax, so the walk is deterministic and byte-identical to 1-token
decode by the chain argument applied along the accepted path.
Sampled: each emitted token is one exact ancestral sample; matching a
point-mass child is precisely per-edge rejection sampling.

Rollback is free: rejected tail tokens only wrote K/V at positions
beyond the new committed ``valid``; every later dispatch's ``kv_valid``
stops at committed+written, so stale cells are never read and are
overwritten in place when real tokens reach those positions. The prefix
cache can never attach them either — PagedKVCache.commit publishes only
pages fully covered by the LITERAL committed token list (the paging
refcount surface), and rejected bytes live past it by definition.
"""

from __future__ import annotations

import bisect
import threading
from collections import deque
from typing import Any, Optional, Protocol, runtime_checkable

from .prefix_cache import env_flag

SPEC_ENV = "ROUNDTABLE_SPEC_DECODE"

DRAFTER_KINDS = ("ngram", "model", "lora")

# Drafts per row per verify dispatch (config `spec_max_draft`). The
# default keeps a row's verify run (1 + drafts) inside ONE
# RAGGED_BLOCK_Q tile, so a speculating batch packs exactly like a
# plain ragged decode batch.
DEFAULT_MAX_DRAFT = 4

# Longest n-gram the drafter keys on; it backs off to shorter grams
# when the longer suffix has no prior occurrence.
NGRAM_MAX = 3

# Adaptive throttle: after at least SPEC_MIN_DISPATCHES verify
# dispatches, a row whose windowed acceptance rate (accepted drafts /
# drafted) sits below the floor stops drafting — drafting must never
# cost a slow row more dispatches than plain decode buys back.
# ROUNDTABLE_SPEC_ACCEPT_FLOOR raises/lowers the floor: on a host
# where a verify dispatch's host round-trip is dearer than the
# pipelined while-loop's hidden one, a modest-acceptance row can be
# net-slower than plain decode without ever dropping below the default
# — the operator lever until the on-chip A/B settles the break-even.
SPEC_WINDOW = 16
SPEC_MIN_DISPATCHES = 6
SPEC_ACCEPT_FLOOR = 0.2

# Re-probe hysteresis (ISSUE 13 satellite): a throttled row re-drafts
# ONCE every SPEC_REPROBE_DISPATCHES committed tokens (~dispatches while
# throttled) — a row whose context BECOMES draftable (the discussion
# looped back onto quoted scaffolding, the draft head warmed up)
# recovers speculation instead of decoding 1-token for the rest of its
# turn. A successful probe (its own acceptance >= the floor) re-enables
# with a FRESH window, so one stale all-zero window cannot instantly
# re-trip; a failed probe waits a whole interval again.
SPEC_REPROBE_DISPATCHES = 16

# Re-probe back-off and pooled evidence (ISSUE 43). A throttled owner's
# probe that accepts nothing doubles the distance to its next one, up
# to SPEC_REPROBE_CEILING of its clock; a probe that accepts any
# drafted token puts the distance back to its base. And the throttle's
# evidence is pooled where a row has none of its own: a verify is ONE
# program for the whole batch, so what a non-accepting batch pays is a
# verify a TICK, however few of its rows drafted — fifteen rows that
# each find a stray n-gram match once an answer keep every tick a
# verify while none of them ever fills its own window. So the rows
# with no verdict of their own (not throttled, no draft of theirs has
# landed: RowSpec.judged) are judged together, by the same rule on the
# sum of what they drafted and accepted a verify (BatchThrottle): the
# same window, the same floor, the same re-probe with the same
# back-off, on the clock of decode steps. A row whose own drafts land
# is exempt from the batch's verdict, and a row its own window has
# throttled keeps its own re-probe.
SPEC_REPROBE_CEILING = 256


def accept_floor() -> float:
    import os
    raw = os.environ.get("ROUNDTABLE_SPEC_ACCEPT_FLOOR")
    try:
        return float(raw) if raw else SPEC_ACCEPT_FLOOR
    except ValueError:
        return SPEC_ACCEPT_FLOOR


def reprobe_interval() -> int:
    import os
    raw = os.environ.get("ROUNDTABLE_SPEC_REPROBE")
    try:
        n = int(raw) if raw else SPEC_REPROBE_DISPATCHES
    except ValueError:
        n = SPEC_REPROBE_DISPATCHES
    return max(n, 1)


def spec_enabled(flag) -> bool:
    """The speculative-decode on/off decision for a paged+ragged engine
    (explicit config wins, then the env kill-switch, then default ON —
    the prefix_cache/ragged_attn precedent: the fast path is the
    serving path, not an experiment). A dict config (ISSUE 13) decides
    through its optional "enabled" key, so `spec_decode: {drafter: ...}`
    keeps the ROUNDTABLE_SPEC_DECODE=0 kill-switch live while
    `{enabled: true, ...}` pins it on."""
    if isinstance(flag, dict):
        flag = flag.get("enabled")
    return env_flag(flag, SPEC_ENV)


class SpecOptions:
    """Resolved `spec_decode:` block (ISSUE 13). The config accepts the
    PR-9 bool OR a dict::

        spec_decode: {enabled?: bool, drafter: ngram|model|lora,
                      max_draft?: int, tree?: {branch: B, depth: D},
                      draft_checkpoint?: path, adapter?: name}

    Validation lives here so the engine constructor and from_config
    fail identically; drafter AVAILABILITY fallbacks (no lora store,
    say) are the engine's job and are recorded, not raised."""

    __slots__ = ("drafter", "tree", "max_draft", "draft_checkpoint",
                 "adapter")

    def __init__(self, drafter: str = "ngram",
                 tree: Optional[dict] = None,
                 max_draft: Optional[int] = None,
                 draft_checkpoint: Optional[str] = None,
                 adapter: Optional[str] = None):
        self.drafter = drafter
        self.tree = tree
        self.max_draft = max_draft
        self.draft_checkpoint = draft_checkpoint
        self.adapter = adapter

    @classmethod
    def resolve(cls, flag) -> "SpecOptions":
        if not isinstance(flag, dict):
            return cls()
        drafter = flag.get("drafter", "ngram")
        if drafter not in DRAFTER_KINDS:
            raise ValueError(
                f"spec_decode drafter must be one of {DRAFTER_KINDS}, "
                f"got {drafter!r}")
        tree = flag.get("tree") or None
        if tree is not None:
            if not isinstance(tree, dict):
                raise ValueError(
                    "spec_decode tree must be {branch: B, depth: D}")
            branch = int(tree.get("branch", 2))
            depth = int(tree.get("depth", 2))
            if branch < 2:
                raise ValueError(
                    f"spec_decode tree branch must be >= 2 (a 1-branch "
                    f"tree is the chain), got {branch}")
            if depth < 1:
                raise ValueError(
                    f"spec_decode tree depth must be >= 1, got {depth}")
            tree = {"branch": branch, "depth": depth}
        if drafter == "lora" and not flag.get("adapter"):
            raise ValueError(
                "spec_decode drafter 'lora' needs an `adapter:` name "
                "registered in the engine's lora: block")
        max_draft = flag.get("max_draft")
        return cls(drafter=drafter, tree=tree,
                   max_draft=(int(max_draft)
                              if max_draft is not None else None),
                   draft_checkpoint=flag.get("draft_checkpoint"),
                   adapter=flag.get("adapter"))


@runtime_checkable
class Drafter(Protocol):
    """Per-row host-side proposer (ISSUE 13). `sync_parts` brings the
    drafter's view up to the row's committed context before every
    draft; `draft` proposes one chain; `draft_paths` proposes up to
    `branch` root-distinct candidate paths for tree verify (chain
    drafters return a single-element list). NGramDrafter implements
    this directly; the model/LoRA drafters are device-batched across
    rows (DeviceDrafter below), so their per-row view is the draft
    slot the coordinator maintains."""

    kind: str

    def sync_parts(self, prompt: list[int],
                   produced: list[int]) -> None: ...

    def draft(self, max_n: int) -> list[int]: ...

    def draft_paths(self, max_n: int,
                    branch: int = 1) -> list[list[int]]: ...


# A corpus indexed WHOLE (a prompt, at a row's first index) goes
# through numpy, not the dict: a gram is packed into one int64 at
# _PACK_BITS a token, and an order's grams are sorted once. Under
# _PACK_MIN tokens the dict's loop is as quick.
_PACK_BITS = 63 // NGRAM_MAX
_PACK_MIN = 32


def _sorted_grams(toks: list[int]) -> Optional[list[tuple]]:
    """For each gram order n in 1..NGRAM_MAX: (the distinct grams of
    `toks`, packed and sorted; each one's most recent END; the END
    before that, or -1), a list to bisect and two arrays beside it —
    what the dict's loop would hold for the same tokens
    (`NGramDrafter.extend`), four times as fast at a transcript's
    length. None where a token does not pack."""
    import numpy as np
    arr = np.asarray(toks, np.int64)
    if arr.min() < 0 or arr.max() >> _PACK_BITS:
        return None
    tiers = []
    for n in range(1, NGRAM_MAX + 1):
        count = len(arr) - n + 1
        if count < 1:
            break
        keys = arr[:count]
        for i in range(1, n):
            keys = (keys << _PACK_BITS) | arr[i:count + i]
        order = np.argsort(keys, kind="stable")   # equal grams: by end
        keys, ends = keys[order], order + n
        last = np.flatnonzero(np.append(keys[1:] != keys[:-1], True))
        before = np.maximum(last - 1, 0)
        prev = np.where((last > 0) & (keys[before] == keys[last]),
                        ends[before], -1)
        tiers.append((keys[last].tolist(), ends[last], prev))
    return tiers


class NGramDrafter:
    """Hash-indexed n-gram / prompt-lookup proposer over ONE row's
    corpus: its (transcript-carrying, prefix-cache-attached) prompt plus
    every committed output token, indexed incrementally as tokens
    retire.

    For each gram order n in NGRAM_MAX..1 the index maps the token
    tuple to the END positions of its two most recent occurrences. A
    draft looks up the context's tail gram and proposes the tokens that
    FOLLOWED it last time; the second-most-recent slot exists because
    the tail gram's own occurrence is always the most recent one and
    carries no continuation.

    The tokens a drafter is BUILT on (a prompt of thousands: the host
    work of a round's joins, which must fit under the joins' own
    dispatches — scheduler._index_in_flight; PERF.md, Findings PR 57)
    are indexed as sorted arrays (`_sorted_grams`), and only what is
    appended later goes into the dict; a lookup reads both."""

    __slots__ = ("_toks", "_index", "_sorted")

    kind = "ngram"

    def __init__(self, tokens: Optional[list[int]] = None):
        self._toks: list[int] = []
        # gram tuple -> (last_end, prev_end); end = index AFTER the gram.
        self._index: dict[tuple, tuple[int, int]] = {}
        # ... of the ends past the tokens `_sorted` covers, if it does.
        self._sorted: Optional[list[tuple]] = None
        if tokens:
            self.extend(tokens)

    def __len__(self) -> int:
        return len(self._toks)

    def extend(self, tokens: list[int]) -> None:
        """Append committed tokens and index every new gram."""
        toks = self._toks
        start = len(toks)
        toks.extend(tokens)
        if not start and len(toks) >= _PACK_MIN:
            self._sorted = _sorted_grams(toks)
            if self._sorted is not None:
                return
        idx = self._index
        for end in range(start + 1, len(toks) + 1):
            for n in range(1, NGRAM_MAX + 1):
                if end < n:
                    break
                key = tuple(toks[end - n:end])
                prev = idx.get(key)
                if prev is None:
                    idx[key] = (end, -1)
                elif prev[0] != end:
                    idx[key] = (end, prev[0])

    def _ends(self, gram: tuple) -> Optional[tuple[int, int]]:
        """(most recent end, the one before or -1) of `gram`, or None."""
        late = self._index.get(gram)
        if self._sorted is None or (late is not None and late[1] != -1):
            return late
        packed = 0
        for tok in gram:
            if tok < 0 or tok >> _PACK_BITS:
                return late
            packed = (packed << _PACK_BITS) | tok
        keys, last, prev = self._sorted[len(gram) - 1]
        i = bisect.bisect_left(keys, packed)
        if i == len(keys) or keys[i] != packed:
            return late
        return ((int(last[i]), int(prev[i])) if late is None
                else (late[0], int(last[i])))

    def sync(self, context: list[int]) -> None:
        """Bring the index up to `context` (prompt + produced): extends
        with the suffix past what is already indexed, so the scheduler
        can call this before every draft regardless of which serving
        path appended the tokens."""
        if len(context) > len(self._toks):
            self.extend(context[len(self._toks):])

    def sync_parts(self, prompt: list[int], produced: list[int]) -> None:
        """sync(prompt + produced) without materializing the
        concatenation — the per-dispatch hot call (the prompt was
        indexed at construction, so only produced's tail is new)."""
        have = len(self._toks)
        need = len(prompt) + len(produced)
        if need > have:
            self.extend(produced[have - len(prompt):])

    def draft(self, max_n: int) -> list[int]:
        """Up to `max_n` candidate continuation tokens of the indexed
        context, from the most recent PRIOR occurrence of the longest
        matching tail gram; [] when nothing matches (the row then runs
        plain 1-token decode this step)."""
        paths = self.draft_paths(max_n, branch=1)
        return paths[0] if paths else []

    def draft_paths(self, max_n: int,
                    branch: int = 1) -> list[list[int]]:
        """Up to `branch` candidate continuation paths with DISTINCT
        first tokens (tree verify, ISSUE 13): the two stored
        occurrences of the longest matching tail gram propose the
        primary candidates, and shorter-gram backoff supplements extra
        branches only when the longer grams could not fill them — so
        `draft_paths(n, 1)[0]` is byte-identical to the PR-9 chain
        draft. [] when nothing matches."""
        toks = self._toks
        if max_n < 1 or not toks or branch < 1:
            return []
        paths: list[list[int]] = []
        seen_first: set[int] = set()
        for n in range(min(NGRAM_MAX, len(toks)), 0, -1):
            entry = self._ends(tuple(toks[len(toks) - n:]))
            if entry is None:
                continue
            # The tail gram itself is always the most recent occurrence;
            # a continuation needs an occurrence that ENDS before the
            # corpus does.
            for pos in entry:
                if not 0 < pos < len(toks):
                    continue
                p = list(toks[pos:pos + max_n])
                if p and p[0] not in seen_first:
                    paths.append(p)
                    seen_first.add(p[0])
                    if len(paths) >= branch:
                        return paths
            if paths and branch == 1:
                return paths
        return paths


class Throttle:
    """The adaptive throttle, for whoever's evidence it is given: the
    window of (drafted, accepted) a verify, the trip once at least
    SPEC_MIN_DISPATCHES of them read under the floor, and the re-probe
    — once an `interval()` of the owner's clock while throttled, with
    the hysteresis of ISSUE 13 (a probe whose own acceptance clears
    the floor re-enables with a FRESH window) and the back-off of
    ISSUE 43: every probe that accepts nothing doubles the interval,
    up to SPEC_REPROBE_CEILING; a probe that accepts any drafted token
    puts it back to its base. The clock is the caller's (`mark`): a
    row's committed tokens, the batch's decode steps. A `RowSpec` is
    one, the engine's `BatchThrottle` is the other."""

    __slots__ = ("recent", "disabled", "probing", "level", "_idle_mark",
                 "_base")

    def __init__(self, base: Optional[int] = None):
        # (drafted, accepted) per verify dispatch that actually drafted.
        self.recent: deque = deque(maxlen=SPEC_WINDOW)
        self.disabled = False
        # Re-probe bookkeeping: the clock's mark at throttle time —
        # pure function of the owner's state, so the probe decision is
        # idempotent across the scheduler's probe and real calls.
        self.probing = False
        self.level = 0      # probes in a row that accepted nothing
        self._idle_mark = 0
        self._base = base   # None: reprobe_interval(), read when asked

    def rate(self) -> float:
        d = sum(x for x, _ in self.recent)
        return (sum(a for _, a in self.recent) / d) if d else 0.0

    def accepting(self) -> bool:
        """Whether the window holds evidence of drafts that land: at
        least one drafted dispatch, and a rate at or above the floor."""
        return bool(self.recent) and self.rate() >= accept_floor()

    def interval(self) -> int:
        """The clock's distance to the next probe while throttled."""
        base = self._base or reprobe_interval()
        return min(base << self.level, max(self.ceiling(), base))

    def ceiling(self) -> int:
        return SPEC_REPROBE_CEILING

    def should_draft(self, mark: int) -> bool:
        """Whether the owner drafts now: always while unthrottled; once
        every `interval()` of its clock while throttled (the re-probe).
        Once a probe fires it stays armed until the next note(), so the
        scheduler's probe call and the real segment see the same
        answer."""
        if not self.disabled:
            return True
        if self.probing:
            return True
        if mark - self._idle_mark >= self.interval():
            self.probing = True
            return True
        return False

    def mark_idle(self, mark: int) -> None:
        """Restart the re-probe interval (called when a dispatch leaves
        the owner throttled)."""
        self._idle_mark = mark

    def probe_failed(self, mark: int) -> None:
        """Resolve an armed probe that never reached a verify dispatch
        (the drafter proposed NOTHING for the probing row): clear the
        arm and restart the interval — otherwise `probing` stays True
        forever and the row pays per-tick draft host work for the rest
        of its turn, exactly the overhead the throttle exists to
        remove."""
        if self.probing:
            self.probing = False
            self._idle_mark = mark
            note_spec_reprobe(recovered=False)

    def note(self, drafted: int, accepted: int) -> bool:
        """Record one verify dispatch's outcome. Returns True when THIS
        call tripped the throttle (the caller emits the one flight
        event). A throttled owner's re-probe RECOVERS here: when the
        probe's own acceptance clears the floor, it re-enables with a
        fresh window (hysteresis — the stale all-zero window must not
        immediately re-trip it)."""
        if drafted <= 0:
            return False
        if self.disabled:
            self.probing = False
            if accepted / drafted >= accept_floor():
                self.disabled = False
                self.level = 0
                self.recent.clear()
                self.recent.append((drafted, accepted))
                note_spec_reprobe(recovered=True)
            else:
                self.recent.append((drafted, accepted))
                if accepted:
                    self.level = 0
                else:
                    self.level += self.interval() < self.ceiling()
                note_spec_reprobe(recovered=False)
            return False
        self.recent.append((drafted, accepted))
        if (len(self.recent) >= SPEC_MIN_DISPATCHES
                and self.rate() < accept_floor()):
            self.disabled = True
            return True
        return False


class RowSpec(Throttle):
    """Per-row speculation state: the drafter plus the row's own
    throttle, on the clock of its committed tokens (ISSUE 13:
    drafter-aware — `kind` labels the metrics, and a throttled row
    periodically re-probes instead of staying dark for its whole
    turn)."""

    __slots__ = ("drafter", "kind", "drafted", "accepted", "ctx")

    def __init__(self, prompt_tokens: Optional[list[int]] = None,
                 kind: str = "ngram"):
        super().__init__()
        # Device-batched drafters (model/lora) keep their state in the
        # draft slots the DeviceDrafter coordinator owns; only the
        # ngram drafter lives here per row — and only once its prompt
        # is given: the scheduler admits with none and indexes the
        # prompt where that costs the device nothing.
        self.drafter = (NGramDrafter(prompt_tokens)
                        if kind == "ngram" and prompt_tokens is not None
                        else None)
        self.kind = kind
        self.drafted = 0
        self.accepted = 0
        # Device-drafter context cache (prompt + produced), extended
        # O(delta) per tick by the scheduler instead of re-concatenated
        # O(transcript) — read-only inside DeviceDrafter.propose.
        self.ctx: Optional[list[int]] = None

    def judged(self) -> bool:
        """Whether the row's own window decides if it drafts: it is
        throttled (its own re-probe runs), or its drafts land. A row
        that is neither has no verdict of its own yet and drafts when
        the batch's throttle says so."""
        return self.disabled or self.accepting()

    def note(self, drafted: int, accepted: int) -> bool:
        if drafted > 0:
            self.drafted += drafted
            self.accepted += accepted
        return super().note(drafted, accepted)


class BatchThrottle(Throttle):
    """The throttle of the rows that have no verdict of their own
    (ISSUE 43; the constants above say why their evidence is pooled).
    Its window holds what those rows drafted and accepted, summed, a
    verify; its clock is the decode steps the scheduler has run, and
    its base interval one decode segment, which is what a tick without
    a verify runs. The engine owns one — it outlives requests, sessions
    and schedulers, as the traffic's habit of accepting or not does —
    and the scheduler's loop thread is its one writer: `advance` with
    every segment's decode steps, `asks` before the unjudged rows are
    asked for drafts, `note` with what they drafted and accepted.
    `counts` are lifetime totals (describe()["spec_decode"]): `probes`
    — verifies issued for it while throttled; `probes_accepted_none` —
    those in which its rows accepted no drafted token;
    `probes_backed_off` — ticks on which its rows were not asked
    because the interval had not passed. `budget` is the shortest
    answer among the rows it was last asked for, and its ceiling: a
    row that decodes its whole budget is asked at least once."""

    __slots__ = ("steps", "budget", "counts", "_barred_tick")

    def __init__(self, segment: int):
        super().__init__(base=segment)
        self.steps = 0
        self.budget = SPEC_REPROBE_CEILING
        self.counts = {"probes": 0, "probes_accepted_none": 0,
                       "probes_backed_off": 0}
        self._barred_tick = None

    def advance(self, steps: int) -> None:
        self.steps += steps

    def ceiling(self) -> int:
        return min(SPEC_REPROBE_CEILING, self.budget)

    def asks(self, tick: int, budget: int, ahead: int = 0) -> bool:
        """Whether the unjudged rows draft now — or `ahead` steps on,
        where the loop asks with a segment of that many in flight.
        `budget`: the shortest max_new among them. A barred tick
        counts once, however often the loop asks in it."""
        self.budget = budget
        if self.should_draft(self.steps + ahead):
            return True
        if tick != self._barred_tick:
            self._barred_tick = tick
            self.counts["probes_backed_off"] += 1
        return False

    def note(self, drafted: int, accepted: int) -> bool:
        if drafted <= 0:
            return False    # a verify of judged rows alone: not its
        if self.disabled:
            self.counts["probes"] += 1
            self.counts["probes_accepted_none"] += not accepted
        tripped = super().note(drafted, accepted)
        if self.disabled:
            self.mark_idle(self.steps)
        return tripped


def accept_prefix(drafts: list[int],
                  proposals: list[int]) -> tuple[list[int], int]:
    """The chain acceptance rule: `proposals` are the device's
    per-position tokens for the run ``[last, d_0, ..., d_{k-1}]``
    (len == k+1). Returns (emit, accepted): the committed tokens
    ``t_0..t_a`` — accepted drafts plus the correction/bonus token —
    and the accepted draft count a."""
    a = 0
    while a < len(drafts) and drafts[a] == proposals[a]:
        a += 1
    return list(proposals[:a + 1]), a


def accept_tree(paths: list[list[int]],
                props: list[list[int]]) -> tuple[list[int], int, int]:
    """The tree acceptance walk (ISSUE 13): `paths[i]` is root-to-leaf
    candidate path i of the row's token tree, `props[i]` the device's
    per-position tokens for path i's run ``[last, paths[i]...]``
    (len == len(paths[i]) + 1, every position conditioned on path i's
    own prefix by the causal mask).

    Walk from the root: at depth j, emit the CURRENT path's device
    token `t = props[cur][j]` — an exact target-model token (argmax or
    sample) given the emitted prefix, so the output stream is exact no
    matter what happens next — then descend into any still-prefix-
    consistent path whose node j equals t (greedy: at most one child
    can match the argmax; sampled: matching a point-mass child is
    per-edge rejection sampling). Returns (emit, accepted_edges,
    winner_path): the committed tokens (accepted path nodes plus the
    correction/bonus token), how many tree edges were accepted, and
    the index of the path whose cells hold every accepted token's K/V
    (the page-adoption source — scheduler tentpole)."""
    emit: list[int] = []
    a, cur, j = 0, 0, 0
    alive = list(range(len(paths)))
    while True:
        t = int(props[cur][j])
        emit.append(t)
        alive = [i for i in alive
                 if len(paths[i]) > j and paths[i][j] == t]
        if not alive:
            return emit, a, cur
        cur = alive[0]
        a += 1
        j += 1


# --- device-batched drafters: draft model / LoRA draft head ---


class DraftUnavailable(RuntimeError):
    """Raised when the drafter cannot shadow the batch for a BENIGN
    capacity reason (no free slot for a draft slot, pool pressure) —
    the scheduler serves plain decode this tick with the reason on
    record. Deliberately distinct from device dispatch failures, which
    must flow into the donation-death / preempt-isolate ladder like
    any other ragged failure."""


DRAFT_SCOPE = "__spec_draft__"

# A draft run fed through the propose/extend dispatches never exceeds
# one RAGGED_BLOCK_Q tile, so the propose-variant program only ever
# compiles at the small end of the shape grid (engine.warmup warms
# exactly those shapes).
PROPOSE_RUN = 7


def draft_slot_name(row_name: str) -> str:
    """The shadow draft slot of a target row — namespaced under its own
    pseudo-session (kvcache.SESSION_SEP), so intra-session prefix
    DONATION can never move draft-model K/V into a real row (sessions
    are isolation domains and `__spec_draft__` is nobody's session).
    Draft slots are never committed, so the cross-session prefix cache
    never sees their pages either."""
    from .kvcache import SESSION_SEP
    return f"{DRAFT_SCOPE}{SESSION_SEP}{row_name}"


class DeviceDrafter:
    """Batch-level coordinator for the model/LoRA drafters (ISSUE 13
    tentpole): each target row gets a shadow DRAFT SLOT in the same
    paged pool ("extra row sets on the SAME engine"), kept in sync with
    the row's committed context and advanced autoregressively through
    ordinary ragged dispatches — a `params` override for the `model`
    kind (same pytree shapes, so no second engine and no new compiled
    programs), per-token adapter ids for the `lora` kind (drafting as a
    hot-swappable adapter on the PR-10 store).

    Per spec tick, `propose` runs:
      1. catch-up — plain ragged chunk dispatches feed each draft slot
         the target context it is missing (first tick: the whole
         prompt; steady state: the last verify's committed tokens);
         a diverged slot (a non-trunk tree path won) simply overwrites
         its stale cells in place, the established rollback contract.
      2. propose — ONE small dispatch scores every row's context tip;
         greedy argmax is the main chain's first node and, under tree
         config, `propose_width` top-k ids seed the root branches.
      3. extend — depth-1 plain 1-token dispatches grow the main chain
         through the draft model (root alternatives stay depth-1
         leaves: the draft slot's K/V follows the main chain only, and
         a verify that accepts an alternative root just makes the next
         catch-up overwrite from the divergence).

    The coordinator never commits draft slots (their pages can never
    enter the prefix cache) and keeps `slot.tokens` = REAL target
    context only — speculative extension cells beyond it are
    overwritten in place by the next catch-up, exactly like rejected
    verify drafts."""

    def __init__(self, kind: str, adapter_slot: int = 0,
                 params: Any = None):
        if kind not in ("model", "lora"):
            raise ValueError(f"DeviceDrafter kind must be model|lora, "
                             f"got {kind!r}")
        self.kind = kind
        self.adapter_slot = adapter_slot
        self.params = params  # None = the engine's own params
        self.draft_dispatches = 0

    # -- slot lifecycle --

    def end_row(self, engine, row_name: str) -> None:
        """Release the row's draft slot (scheduler retire/fail path)."""
        engine.kv.release(draft_slot_name(row_name))

    # -- the per-tick batched proposal --

    def _batch(self, engine, seqs, shape, propose_width=0):
        from .serving_loop import build_ragged_batch
        batch = build_ragged_batch(
            seqs, t_budget=shape, s_max=engine.kv.num_slots + 1,
            pages_per_seq=engine.kv.pages_per_seq,
            scratch_page=engine.kv.scratch_page(0),
            pad_id=engine.tokenizer.pad_id,
            page_size=engine.kv.page_size)
        batch["draft"] = True
        if propose_width:
            batch["propose_width"] = propose_width
        if self.params is not None:
            batch["draft_params"] = self.params
        return batch

    def propose(self, engine, rows, pinned=(),
                dispatch=None, read=None) -> dict:
        """rows: list of (key, row_name, ctx_tokens, depth, branch).
        Returns {key: [path, ...]} — the main chain plus up to
        branch-1 single-node root alternatives; every path non-empty.
        `dispatch`/`read` let the scheduler route the device calls
        through its run_dispatch/host_sync watchdog seams."""
        import numpy as np

        from .serving_loop import RAGGED_BLOCK_Q, RaggedSeq, \
            ragged_pick_shape

        if dispatch is None:
            dispatch = engine._ragged_dispatch
        if read is None:
            def read(h):
                # The propose dispatch returns (next_ids, top_k_ids)
                # when propose_width > 0; plain dispatches one array.
                if isinstance(h, tuple):
                    return tuple(np.asarray(x) for x in h)
                return np.asarray(h)
        kv = engine.kv
        temps = 0.0  # point-mass drafter: always greedy
        pinned = tuple(pinned) + tuple(
            draft_slot_name(name) for _, name, _, _, _ in rows)

        # 1. slots + capacity + catch-up plans. Capacity failures here
        # are BENIGN (the batch is too big to shadow — serve plain
        # decode, never evict live rows to draft for them) and must not
        # be confused with device dispatch failures below, which take
        # the ragged failure ladder.
        infos = []
        try:
            for key, name, ctx, depth, branch in rows:
                dname = draft_slot_name(name)
                st = kv.acquire(dname, pinned)
                common = kv.common_prefix_len(st.tokens, ctx)
                if common < len(st.tokens):
                    # Diverged (or freshly evicted): keep the common
                    # prefix, overwrite the rest in place.
                    st.tokens = st.tokens[:common]
                kv.ensure_capacity(dname, len(ctx) + depth,
                                   write_from=common, pinned=pinned)
                table = kv.table_for([dname])[0]
                infos.append({"key": key, "st": st, "ctx": list(ctx),
                              "depth": depth, "branch": branch,
                              "table": table})
        except RuntimeError as e:
            raise DraftUnavailable(str(e)) from e

        # 2. catch-up chunks until every remainder fits the propose run.
        while True:
            longs = [i for i in infos
                     if len(i["ctx"]) - len(i["st"].tokens) > PROPOSE_RUN]
            if not longs:
                break
            per_row = max((engine.ragged_tokens // len(longs))
                          // RAGGED_BLOCK_Q * RAGGED_BLOCK_Q,
                          RAGGED_BLOCK_Q)
            seqs, feeds = [], []
            for i in longs:
                done = len(i["st"].tokens)
                rem = len(i["ctx"]) - done
                take = min(rem - PROPOSE_RUN, per_row)
                if take < 1:
                    continue
                chunk = i["ctx"][done:done + take]
                seqs.append(RaggedSeq(chunk, done, i["table"],
                                      temperature=temps,
                                      adapter=self.adapter_slot))
                feeds.append((i, chunk))
            if not seqs:
                break
            want = sum(-(-len(s.tokens) // RAGGED_BLOCK_Q)
                       * RAGGED_BLOCK_Q for s in seqs)
            shape = ragged_pick_shape(engine.ragged_shapes,
                                      min(want, engine.ragged_tokens))
            read(dispatch(self._batch(engine, seqs, shape)))
            self.draft_dispatches += 1
            for i, chunk in feeds:
                i["st"].tokens = i["st"].tokens + chunk

        # 3. the propose dispatch: remainder runs (1..PROPOSE_RUN
        # tokens) score the context tip; top-k seeds the root branches.
        branch_max = max(i["branch"] for i in infos)
        seqs = []
        for i in infos:
            done = len(i["st"].tokens)
            rem = i["ctx"][done:]
            if not rem:
                # Fully caught up (a verify failed after the previous
                # propose advanced the slot): re-feed the last context
                # token — identical K/V bytes at its own position, and
                # the tip logits still come out.
                done -= 1
                rem = i["ctx"][-1:]
            assert 1 <= len(rem) <= PROPOSE_RUN
            seqs.append(RaggedSeq(rem, done, i["table"],
                                  temperature=temps,
                                  adapter=self.adapter_slot))
        shape = ragged_pick_shape(
            engine.ragged_shapes,
            min(RAGGED_BLOCK_Q * len(seqs), engine.ragged_tokens))
        out = read(dispatch(self._batch(
            engine, seqs, shape,
            propose_width=(branch_max if branch_max > 1 else 0))))
        self.draft_dispatches += 1
        if branch_max > 1:
            nxt, tops = out
        else:
            nxt, tops = out, None
        for idx, i in enumerate(infos):
            # Snapshot, never alias: the scheduler's per-row ctx cache
            # keeps growing across ticks, and an aliased st.tokens
            # growing with it would claim K/V the slot never received.
            i["st"].tokens = list(i["ctx"])
            c1 = int(nxt[idx])
            i["main"] = [c1]
            alts = []
            if tops is not None:
                for t in list(tops[idx])[:i["branch"]]:
                    t = int(t)
                    if t != c1 and t not in alts:
                        alts.append(t)
            i["alts"] = alts[:max(i["branch"] - 1, 0)]

        # 4. extend the main chain through the draft model.
        max_depth = max(i["depth"] for i in infos)
        for step in range(1, max_depth):
            seqs, growing = [], []
            for i in infos:
                if i["depth"] <= step:
                    continue
                pos = len(i["ctx"]) + step - 1
                seqs.append(RaggedSeq([i["main"][-1]], pos, i["table"],
                                      temperature=temps,
                                      adapter=self.adapter_slot))
                growing.append(i)
            if not seqs:
                break
            shape = ragged_pick_shape(
                engine.ragged_shapes,
                min(RAGGED_BLOCK_Q * len(seqs), engine.ragged_tokens))
            nxt = read(dispatch(self._batch(engine, seqs, shape)))
            self.draft_dispatches += 1
            for idx, i in enumerate(growing):
                i["main"].append(int(nxt[idx]))

        return {i["key"]: [i["main"]] + [[t] for t in i["alts"]]
                for i in infos}


# --- test-visibility counters (tests/conftest.py `spec_decode` guard) ---

_lock = threading.Lock()
_drafted = 0
_accepted = 0
_dispatches = 0
_tree_accepted_paths = 0
_tree_nodes = 0
_reprobes = 0
_reprobe_recoveries = 0


def reset_test_counters() -> None:
    global _drafted, _accepted, _dispatches, _tree_accepted_paths
    global _tree_nodes, _reprobes, _reprobe_recoveries
    with _lock:
        _drafted = _accepted = _dispatches = 0
        _tree_accepted_paths = _tree_nodes = 0
        _reprobes = _reprobe_recoveries = 0


def note_spec_dispatch(drafted: int, accepted: int) -> None:
    global _drafted, _accepted, _dispatches
    with _lock:
        _drafted += drafted
        _accepted += accepted
        _dispatches += 1


def note_tree_row(nodes: int, accepted_edges: int) -> None:
    """One multi-path (tree) row through a verify dispatch: `nodes`
    tree nodes packed, `accepted_edges` edges the walk accepted. A
    MULTI-NODE accepted path (>= 2 edges) is what the conftest
    `tree=True` guard requires — single-edge acceptance is
    indistinguishable from a lucky chain."""
    global _tree_nodes, _tree_accepted_paths
    with _lock:
        _tree_nodes += nodes
        if accepted_edges >= 2:
            _tree_accepted_paths += 1


def note_spec_reprobe(recovered: bool) -> None:
    global _reprobes, _reprobe_recoveries
    with _lock:
        _reprobes += 1
        if recovered:
            _reprobe_recoveries += 1


def drafted_seen() -> int:
    return _drafted


def accepted_seen() -> int:
    return _accepted


def dispatches_seen() -> int:
    return _dispatches


def tree_accepted_paths_seen() -> int:
    return _tree_accepted_paths


def tree_nodes_seen() -> int:
    return _tree_nodes


def reprobes_seen() -> int:
    return _reprobes


def reprobe_recoveries_seen() -> int:
    return _reprobe_recoveries


# ---------------------------------------------------------------------------
# static-analysis program registration (ISSUE 15)
# ---------------------------------------------------------------------------

from ..analysis.jaxpr_audit import (ProgramSpec, Variant,  # noqa: E402
                                    analysis_register)


@analysis_register("spec")
def _analysis_spec_programs(engine) -> list:
    """Speculative verify + propose program variants for the jaxpr
    audit — the same (score_width, s_max, copy_slots) and
    propose_width statics `_warm_ragged` compiles, traced device-free
    across the shape grid. Two verify compositions (one speculating
    row alone; speculating + plain rows mixed) share each shape label:
    acceptance drift and chain/tree mixes are VALUES, so extra
    distinct jaxprs under one label are a static-arg leak
    (RT-JAXPR-VARIANTS), and a host callback in a verify program is a
    per-verify host sync (RT-JAXPR-CALLBACK)."""
    if not getattr(engine, "spec_decode", False) \
            or not getattr(engine, "ragged_enabled", False):
        return []
    import numpy as np

    from .paged_forward import trace_ragged_batch
    from .serving_loop import RaggedSeq, build_ragged_batch
    kv = engine.kv
    scratch = kv.scratch_page(0)
    table = np.full((kv.pages_per_seq,), scratch, np.int32)
    r = engine.spec_max_draft + 1

    def batch(seqs, shape, score_width=0, s_max=None, copy_slots=0,
              propose_width=0):
        b = build_ragged_batch(
            seqs, t_budget=shape,
            s_max=s_max if s_max is not None else kv.num_slots + 1,
            pages_per_seq=kv.pages_per_seq, scratch_page=scratch,
            pad_id=engine.tokenizer.pad_id, page_size=kv.page_size,
            score_width=score_width, copy_slots=copy_slots)
        if propose_width:
            b["propose_width"] = propose_width
        return b

    def verify_variant(shape: int, mixed: bool) -> Variant:
        def thunk():
            seqs = [RaggedSeq([7] * r, 8, table, n_scores=r)]
            if mixed:
                seqs.append(RaggedSeq([9], 4, table, n_scores=1))
            return trace_ragged_batch(engine, batch(
                seqs, shape, score_width=r, s_max=engine.spec_s_max,
                copy_slots=engine.spec_copy_slots))
        return Variant(
            label=f"t{shape}", thunk=thunk,
            situation=("speculating+plain rows" if mixed
                       else "one speculating row") + f" in {shape}")

    specs = [ProgramSpec(
        name="spec_verify", phase="verify",
        variants=[verify_variant(shape, mixed)
                  for shape in engine.ragged_shapes
                  for mixed in (False, True)])]
    if engine.spec_branch > 1:
        def propose_variant(shape: int) -> Variant:
            def thunk():
                seqs = [RaggedSeq([7], 8, table),
                        RaggedSeq([9], 4, table)]
                return trace_ragged_batch(engine, batch(
                    seqs, shape, propose_width=engine.spec_branch))
            return Variant(label=f"t{shape}", thunk=thunk,
                           situation=f"propose in shape {shape}")
        specs.append(ProgramSpec(
            name="spec_propose", phase="propose",
            variants=[propose_variant(shape)
                      for shape in engine.ragged_shapes]))
    return specs
