"""Continuous-batching session scheduler — many discussions, one engine.

Everything below the adapters serves exactly ONE discussion at a time:
`generate_batch` owns the engine's serve lock end-to-end, so a second
session's round serializes behind the first even when the first is deep
in a long decode with most of its rows already at eos. Production TPU
engines get their throughput from continuous batching (RTP-LLM, arxiv
2605.29639), and Ragged Paged Attention (arxiv 2604.15464) shows mixed
prefill/decode batches are the natural TPU shape for it. The paged KV
pool is already slot-granular with copy-on-write sharing — this module
adds the missing piece: the scheduling subsystem above it.

Design, shaped by JAX's static-shape constraints (ISSUE 4 tentpole):

- **Decode batch = the live row set, bucketed, recomposed at segment
  boundaries.** One decode program runs a whole DECODE_SEGMENT
  (serving_loop); between segments the host owns every row's (last,
  valid, done, budget) state, so rows can retire and join freely there
  without touching the device programs. The batch pads to a power-of-two
  bucket (capped at max_rows) with MASKED pad rows — done from step 0,
  zero budget, writes landing on the scratch page — so the compiled
  decode shapes are {1, 2, 4, ..., max_rows} and a retire/join that
  moves occupancy within a bucket compiles nothing mid-serve.
- **Join = chunked prefill into freed capacity.** A queued turn admits at
  a segment boundary: its rows run the same reuse_plan → share_prefixes
  (intra-session cross-knight reuse) → chunked/ring prefill path as
  generate_batch — with every actively-decoding row PINNED so the
  joining batch can never evict a live slot — then its first sampled
  token enters the next decode segment alongside everyone else's rows.
- **Retire = drop out of the next segment.** A row at eos (or out of
  per-row budget) simply stops being dispatched; its session's request
  completes when all its rows are done, committing each slot's tokens
  for next-round prefix reuse. No whole-batch barrier: one session's
  long monologue never holds another session's finished rows hostage.
- **Admission queue with capacity-aware backpressure.** A request whose
  rows cannot fit the batch right now (or whose pages cannot fit the
  PagedKVCache pool next to the pinned live rows) stays queued until
  retirement frees capacity; a request that could NEVER fit this engine
  is refused outright (SchedulerRefused) instead of deadlocking the
  queue. Per-session fairness is FIFO admission with co-scheduled
  rounds: all knights of one round join together or not at all, so
  consensus rounds still fan out in one batch.
- **Sessions are isolation domains.** Slot names are session-namespaced
  (kvcache.scoped_slot — the cross-session "lancelot" collision fix),
  prefix donation never crosses sessions, and a fault in the shared
  decode dispatch degrades by PREEMPTING the batch into per-session
  dispatches: the sick session's request fails into its adapter's
  PR-1 ladder (revive → serial retry → breaker) while every other
  session's rows continue from their host-side state, byte-identical.
- **Composes with the ladders, not around them.** Admission checks the
  fleet drain gate (queued-but-unadmitted requests fail fast with
  DrainingError on drain), per-rung deadlines.Budgets thread session →
  turn → prefill/decode/segment, dispatches run through the
  run_dispatch retry/watchdog seam, and every decision (admit / queue /
  refuse / preempt, queue depth, per-segment batch occupancy) is
  recorded into GenStats.sched and engine.describe()["scheduler"] the
  same way the int4 paths are.
"""

from __future__ import annotations

import threading
import time
import weakref
from collections import Counter, deque
from dataclasses import dataclass, field
from typing import Any, Optional

import numpy as np

from ..utils import telemetry
from . import deadlines, faults, trace_hooks
from .kvcache import scoped_slot
from .sampling import SamplingParams, row_filtered, sampling_arrays
from .serving_loop import roomy_frame as _roomy_frame
from .serving_loop import (DECODE_SEGMENT, RAGGED_BLOCK_Q, RaggedSeq,
                           ReplicaGroupPlan, build_ragged_batch,
                           clamp_max_new, eos_trim, host_sync,
                           pow2_bucket, prompt_budget, run_dispatch)

# How many recent per-segment occupancy samples / decision events the
# provenance surfaces keep (describe(), fleet_health).
_OCCUPANCY_LOG_CAP = 256
_EVENT_LOG_CAP = 64
# What the loop sleeps after a flush that carried a row's FIRST tokens
# when no row is left to fill (ISSUE 57): a burst's followers get theirs
# in one flush, eight rows at once, and the streams' thread takes them
# to their sockets over several turns of its own loop, each of which
# waits for the interpreter while this thread runs on (CPython hands it
# over 5 ms at a time). Measured on Mistral's cell (my chip runs, PR 57:
# PERF.md, Findings, has the table): 0 ms — first tokens at their
# clients 4-6 ms after the dispatch that sampled them, `ttft_p90_ms`
# 108.9-112.5; 1 ms — 105.7-107.1; 2 ms — 2.5-3.7 ms after,
# 104.3-112.4; 5 ms — 105.4-106.7. Where a last flush carries two rows
# (the parent's packing) it buys nothing.
_STREAM_YIELD_S = 0.002

# The loop clock's phases (ISSUE 25; PERF.md section 3 has the table):
# every instant of the scheduler's thread belongs to exactly one.
# `dispatch`, `sync` and `admit_sync` are marked by the two seams in
# serving_loop (run_dispatch, host_sync), the rest here; inside
# admission a blocking read is `admit_sync` and issuing a program is
# admission's own host work.
LOOP_PHASES = ("wait", "health", "admit", "admit_sync", "build",
               "dispatch", "sync", "accept", "flush", "retire")
_LOOP_WITHIN = {"admit": {"sync": "admit_sync", "dispatch": "admit"}}

# The loop's own frame is a roomy one (serving_loop.roomy_frame; PERF.md,
# Findings PR 46): tracing and lowering run on this thread at every new
# shape.
# Test-visibility counter (tests/conftest.py `scheduler` marker guard):
# the maximum number of live rows any scheduler dispatched in one decode
# segment since the last reset. A guard that sees < 2 here knows the
# scheduler silently degenerated to serial serving.
_test_max_rows = 0
_test_lock = threading.Lock()


def reset_test_counters() -> None:
    global _test_max_rows
    with _test_lock:
        _test_max_rows = 0


def max_rows_seen() -> int:
    return _test_max_rows


def _note_rows(n: int) -> None:
    global _test_max_rows
    with _test_lock:
        if n > _test_max_rows:
            _test_max_rows = n


# Registry of live schedulers (weak — a dropped scheduler must not be
# kept alive by observability): fleet_health() and fleet.drain() walk it.
_registry_lock = threading.Lock()
_instances: list = []


def _register(sched: "SessionScheduler") -> None:
    with _registry_lock:
        _instances.append(weakref.ref(sched))


def schedulers() -> list["SessionScheduler"]:
    """Every live SessionScheduler (fleet_health / fleet.drain)."""
    out = []
    with _registry_lock:
        alive = []
        for ref in _instances:
            s = ref()
            if s is not None:
                alive.append(ref)
                out.append(s)
        _instances[:] = alive
    return out


class SchedulerRefused(RuntimeError):
    """The request can NEVER fit this engine (more knights than slots,
    or more pages than the whole pool) — refused at submission, not
    queued to deadlock. `reason` (ISSUE 16) is the machine-readable
    refusal tag the gateway's shed accounting keys on: the never-fits
    tags ("rows_never_fit", "adapters_never_fit", "pages_never_fit")
    or, for a submit that opted out of queueing behind a closed gate
    (queue_when_paused=False), the pause_admission reason verbatim —
    so shed vs drain vs quiesce refusals stay distinguishable at the
    HTTP boundary instead of dying inside the scheduler."""

    def __init__(self, message: str, reason: Optional[str] = None):
        super().__init__(message)
        self.reason = reason


class SchedulerClosed(RuntimeError):
    """submit() after close()."""


class DeadlineExpired(RuntimeError):
    """The request's SLO budget was already spent at submission — it
    fails fast at the queue mouth, before any prefill dispatch or slot
    acquisition (gateway deadline propagation, ISSUE 16). The message
    deliberately carries no classify_error marker words so the
    ERROR_KIND_TABLE entry ("deadline_expired") wins over the
    message-sniffing timeout ladder."""


@dataclass(eq=False)
class _Row:
    """One knight's decode row: host-side state between segments.
    Identity equality (eq=False): rows are tracked by membership in
    their request's list, and two rows can transiently hold identical
    field values."""

    name: str                    # session-scoped slot name
    tokens: list[int]            # truncated prompt ids (committed base)
    sampling: SamplingParams
    max_new: int                 # per-row token cap (<= request cap)
    produced: list[int] = field(default_factory=list)  # [first, ...]
    last: int = 0
    valid: int = 0
    done: bool = False
    # Ragged chunk-interleaved admission (ISSUE 8): prompt tokens not
    # yet prefilled — fed as chunks of the live decode segment's ragged
    # dispatches; `pos` is the next write position. A row with pending
    # tokens is FILLING, never dispatched for decode; its first sampled
    # token arrives with the dispatch consuming its last chunk. A
    # `blocked` filling row is a deferred-share LAGGARD: its chunks wait
    # until the round's leader has written the common span, at which
    # point the span aliases in and the row unblocks (_apply_share_plans).
    pending: list[int] = field(default_factory=list)
    pos: int = 0
    blocked: bool = False
    # Speculative decoding (ISSUE 9): per-row drafter + adaptive
    # throttle (engine/spec_decode.RowSpec); None on spec-off engines.
    spec: Optional[Any] = None
    # Multi-LoRA persona (ISSUE 10): this row's adapter SLOT in the
    # engine's LoraStore (0 = base). A value, never a shape: mixed-
    # adapter segments run the same compiled programs as base ones.
    adapter_slot: int = 0
    # Committed-token streaming seam (ISSUE 16): how many eos-trimmed
    # tokens of this row have already been flushed to the request's
    # on_commit callback. eos_trim is prefix-stable as `produced`
    # grows, so ids[streamed:] is exactly the new committed span —
    # tree-spec multi-token commits stream for free.
    streamed: int = 0


class _Request:
    """One session round: queued → active → done|failed."""

    __slots__ = ("session", "turns", "sampling_per_turn", "max_new",
                 "timeout_s", "budget", "event", "result", "error",
                 "enqueued", "admitted_at", "rows", "stats", "deadline",
                 "turn_budget", "dec_budget", "abandoned", "seg_count",
                 "occ_sum", "occ_max", "sess_max", "requeues",
                 "fits_below", "tele_ctx", "tele", "first_token_at",
                 "share_plans", "spec_drafted", "spec_accepted",
                 "adapters", "adapters_held", "on_commit")

    def __init__(self, session, turns, sampling_per_turn, max_new,
                 timeout_s, budget, stats, adapters=None):
        self.session = session
        self.turns = turns
        self.sampling_per_turn = sampling_per_turn
        # Per-turn LoRA persona adapter ids (ISSUE 10; None = base).
        # adapters_held flips once acquire() took residency refs, so
        # failure paths release exactly what admission took.
        self.adapters = adapters
        self.adapters_held = False
        self.max_new = max_new
        self.timeout_s = timeout_s
        self.budget = budget
        self.event = threading.Event()
        self.result = None
        self.error: Optional[BaseException] = None
        self.enqueued = time.monotonic()
        self.admitted_at: Optional[float] = None
        self.rows: list[_Row] = []
        self.stats = stats
        self.deadline = float("inf")
        self.turn_budget = None
        self.dec_budget = None
        self.abandoned = False
        self.seg_count = 0
        self.occ_sum = 0
        self.occ_max = 0
        self.sess_max = 0
        self.requeues = 0        # admissions undone on pool exhaustion
        self.fits_below = None   # re-admit only once active rows < this
        # TTFT (ISSUE 8): when the LAST of this request's rows got its
        # first sampled token — the moment every knight of the round
        # has tokens flowing. sched stats report it against `enqueued`.
        self.first_token_at: Optional[float] = None
        # Deferred leader-span share plans (ragged admission): the
        # laggards alias the common span once the leader's chunks have
        # written it. [{"leader": _Row, "hi": int,
        # "followers": [(_Row, lo), ...] — those still blocked,
        # "hand": (key, boundary) of the snapshot kept for them where
        # the model holds recurrent state, else None}]
        self.share_plans: list[dict] = []
        # Speculation provenance (ISSUE 9): this request's drafted /
        # accepted totals — lands in GenStats.sched["spec"] at retire.
        self.spec_drafted = 0
        self.spec_accepted = 0
        # Telemetry (ISSUE 5): the submitter thread's span context, so
        # this request's "turn" span parents into ITS discussion trace
        # even though the scheduler thread emits it; `tele` is that
        # span while the request is active.
        self.tele_ctx = telemetry.current_context() \
            if telemetry.ACTIVE else None
        self.tele = None
        # Committed-token streaming (ISSUE 16): called on the LOOP
        # thread with {"type": "tokens"|"retired"|"failed", ...} events
        # at segment-commit boundaries. A raising callback is disabled
        # (set to None) — a broken consumer must never wedge serving.
        self.on_commit = None


class SessionScheduler:
    """Admits concurrent discussion sessions onto one InferenceEngine
    and continuously batches their decode segments.

    One scheduler per engine: `scheduler_for(engine)` returns the
    attached instance or builds one. Threads call `submit(session,
    turns, ...)` (the TpuLlmAdapter routes through it when attached);
    a dedicated scheduler thread owns the engine's serve lock while any
    session is active, so direct generate_batch callers and fleet.drain
    still serialize correctly against scheduled work."""

    def __init__(self, engine, *, admit_hold_s: float = 0.0,
                 max_rows: Optional[int] = None,
                 idle_spill_s: Optional[float] = None,
                 journal=None):
        self.engine = engine
        self.admit_hold_s = admit_hold_s
        self.max_rows = min(max_rows or engine.kv.num_slots,
                            engine.kv.num_slots)
        # Host-RAM KV offload policy (ISSUE 7): per-session last-activity
        # drives spill decisions — under page pressure at admission an
        # idle session's KV moves to host RAM (kv_offload tier) INSTEAD
        # of the allocator destroying it by eviction; with idle_spill_s
        # set, sessions idle longer than that spill proactively each
        # tick. Spilled sessions restore transparently on their next
        # submit (engine._prepare_batch's restore seam) with no
        # re-prefill. None = pressure-driven only.
        self.idle_spill_s = idle_spill_s
        self._last_active: dict[str, float] = {}
        self.spills = 0
        self._queue: deque[_Request] = deque()
        self._active: list[_Row] = []         # rows, admission order
        self._active_reqs: list[_Request] = []
        self._row_req: dict[int, _Request] = {}  # id(row) -> request
        self._cv = threading.Condition()
        self._stop = False
        self.closed = False
        self._lock_held = False
        # The lock OBJECT actually held (ISSUE 12): a supervised engine
        # rebuild swaps self.engine mid-lifetime, and releasing
        # "self.engine._serve_lock" after a swap would release the NEW
        # engine's (unheld) lock while leaking the old one.
        self._held_lock: Optional[threading.Lock] = None
        # Admission gate (ISSUE 12): while set, queued requests stay
        # QUEUED (the supervisor's quiesce / fleet.drain) — nothing is
        # admitted and nothing is rejected; reopen_admission (or
        # fleet.resume) lifts it. A reason string, None = open.
        self._paused: Optional[str] = None
        # Thread-safe preempt mailbox (ISSUE 12): force_fail_active
        # posts an error here; the loop thread consumes it at its next
        # health check — request state stays single-writer.
        self._force_fail: Optional[BaseException] = None
        # Durable session journal (ISSUE 12): when attached, every
        # retired round appends one fsynced committed-turn record, so a
        # hard process crash resumes at the last committed turn
        # (engine/session_journal.py; serve --resume replays it).
        self._journal = journal
        # THIS scheduler's journal provenance (the journal object is
        # shared across every scheduler of a serve root — its own
        # .records/.errors are fleet-wide and would double-count when
        # describe() outputs are summed per scheduler).
        self.journal_turns = 0
        self.journal_errors = 0
        # Decision provenance (ISSUE 4: recorded like the int4 paths).
        self.admitted = 0
        self.refused = 0
        self.completed = 0
        self.failed = 0
        self.rejected_draining = 0
        self.rejected_other = 0       # close()/loop-error rejections
        self.deadline_expired = 0     # SLO-spent submits failed fast
        self.preemptions = 0          # fault-isolation preempts
        self.segments = 0
        self.max_occupancy = 0
        self.queued_peak = 0
        # Ragged chunk-interleaved admission provenance (ISSUE 8):
        # mixed dispatches issued, joins that prefilled through them,
        # and the per-phase token split of every segment (ragged AND
        # while-loop) — bumped in lockstep with their registry series
        # like every other counter here.
        self.ragged_segments = 0
        self._snaps_seen = 0        # hybrid: snapshots at the last span's end
        self._scan_seen = 0         # ... and Mamba-1 tokens x layers scanned
        self._conv_seen = 0         # ... and short-conv tokens x layers run
        self._seam_seen: dict = {}  # ... and a seam's counts (SEAM_COUNTS)
        self._hy_counting = False   # ... and whether that span was armed
        self.ragged_joins = 0
        # N-gram prompt indices by where they were built (ISSUE 30):
        # under a segment in flight, the device busy, or at the row's
        # first draft, the device waiting for it.
        self.indexed_in_flight = 0
        self.indexed_at_draft = 0
        self.segment_prefill_tokens = 0
        self.segment_decode_tokens = 0
        # What the join dispatches carried, by flat-buffer shape (ISSUE
        # 57): {shape: [dispatches, buffer tokens, real tokens]} — a
        # dispatch computes its whole buffer, so real over buffer is
        # the share of a join program's work that served a token.
        self.ragged_fill: dict[int, list[int]] = {}
        # Speculative verify dispatches issued (ISSUE 9) — bumped in
        # lockstep with its registry series like every counter here.
        self.spec_segments = 0
        self._occupancy: deque[int] = deque(maxlen=_OCCUPANCY_LOG_CAP)
        self._events: deque[dict] = deque(maxlen=_EVENT_LOG_CAP)
        # Registry label for this scheduler's series (ISSUE 5): every
        # decision counter below publishes into the shared registry in
        # LOCKSTEP (_bump), so describe() and the registry can never
        # disagree — the single-source-of-truth migration.
        self._tname = getattr(engine.cfg, "name", "engine")
        # The loop clock (ISSUE 25): which phase the loop thread is in,
        # lifetime seconds per phase (describe()["loop_seconds"], the
        # roundtable_sched_loop_seconds_total series), and — armed —
        # one `loop.<phase>` span per stretch. Its feed bit (ISSUE 37):
        # the part of each phase spent with no step program of this
        # loop's outstanding (["loop_starved_seconds"], the
        # roundtable_sched_starved_seconds_total series) — fed where a
        # runner's dispatch returns, drained where its read does.
        # Marked on the loop thread only; `_loop_published` and
        # `_starved_published` are what the two series have seen.
        self._clock = telemetry.LoopClock(
            LOOP_PHASES, "wait", within=_LOOP_WITHIN, engine=self._tname)
        self._loop_published = dict.fromkeys(LOOP_PHASES, 0.0)
        self._starved_published = dict.fromkeys(LOOP_PHASES, 0.0)
        # Replica identity (ISSUE 17): set by the session router when
        # this scheduler serves as one replica of a data-parallel
        # fleet. N replicas of one model share `_tname` (same config),
        # so every registry series this scheduler writes additionally
        # carries `replica=` once set — and the router removes the
        # labeled series when the replica retires (RT-GAUGE-LEAK).
        self.replica: Optional[str] = None
        # Attaching a scheduler ADDS compile surface (pipelined-segment
        # carries, pinned-row joins) to an engine whose warmup() may
        # already have declared steady state — reopen the warmup phase
        # so the scheduler's warm traffic compiles freely; the caller
        # re-declares via declare_warmup_complete() once covered.
        from . import compile_watch
        compile_watch.reopen_warmup(self._tname)
        # (the set-up table's last phase: from here to
        # declare_warmup_complete(), which ends it)
        compile_watch.phase("warm_traffic").begin()
        self._thread = threading.Thread(
            target=self._loop, daemon=True,
            name=f"session-scheduler-{getattr(engine.cfg, 'name', '?')}")
        engine._scheduler = self           # describe() provenance
        _register(self)
        self._thread.start()

    # ------------------------------------------------------------------
    # submission
    # ------------------------------------------------------------------

    def submit(self, session: str, turns: list[tuple[str, Any]], *,
               max_new_tokens: Optional[int] = None,
               timeout_s: float = 600.0,
               sampling_per_turn: Optional[list[SamplingParams]] = None,
               budget=None, adapters_per_turn=None):
        """Serve one session round through the shared batch. Blocks the
        calling (session) thread until the round completes; returns
        (responses, GenStats) — the generate_batch_with_stats contract,
        so the adapter ladder above is unchanged. `adapters_per_turn`
        (ISSUE 10): per-knight LoRA persona ids (None = base) —
        co-batched rows with DIFFERENT adapters share one decode
        segment on the shared base model."""
        req = self.submit_async(
            session, turns, max_new_tokens=max_new_tokens,
            timeout_s=timeout_s, sampling_per_turn=sampling_per_turn,
            budget=budget, adapters_per_turn=adapters_per_turn)
        return self.wait(req)

    def submit_async(self, session, turns, *, max_new_tokens=None,
                     timeout_s: float = 600.0, sampling_per_turn=None,
                     budget=None, adapters_per_turn=None,
                     on_commit=None,
                     queue_when_paused: bool = True) -> _Request:
        if self.closed:
            raise SchedulerClosed("scheduler is closed")
        if not turns:
            raise ValueError("submit() needs at least one turn")
        # Drain gate at the QUEUE mouth: a request that would only ever
        # wait out its budget behind a drain fails fast instead
        # (fleet.drain satellite).
        deadlines.check_admission()
        # Deadline propagation (ISSUE 16): a request whose SLO budget
        # is ALREADY spent fails fast here — before slot acquisition or
        # any prefill dispatch — with its own classified kind, instead
        # of occupying queue/batch capacity just to time out.
        if budget is not None and budget.expired:
            with self._cv:  # submitter threads race each other here
                self._bump("deadline_expired")
            self._event("deadline_expired", session=session)
            raise DeadlineExpired(
                f"session {session!r} submitted with its SLO budget "
                "already spent — refused before any prefill dispatch")
        # Gateway shed seam (ISSUE 16): callers that shed instead of
        # queueing (the HTTP front door) opt out of the pause gate's
        # wait-in-queue default; the refusal carries the pause reason
        # verbatim so drain/quiesce/shed are machine-distinguishable.
        if not queue_when_paused:
            paused = self._paused
            if paused is not None:
                with self._cv:
                    self._bump("refused")
                self._event("refuse", session=session,
                            reason=f"admission paused: {paused}")
                raise SchedulerRefused(
                    f"session {session!r} refused while admission is "
                    f"paused ({paused}) — caller sheds instead of "
                    "queueing behind a closed gate", reason=paused)
        engine = self.engine
        # Dead-engine gate (ISSUE 12): the supervisor exhausted this
        # engine's restart budget — every submit fails fast with the
        # same classified reason instead of queueing into a corpse.
        from ..core.errors import classify_error
        from .supervisor import EngineDead, engine_dead_reason
        dead = engine_dead_reason(engine)
        if dead is not None:
            # The reason string carries the terminal cause, so the
            # classified kind survives into the adapter ladder's error
            # accounting (device_lost stays device_lost).
            raise EngineDead(
                f"engine {self._tname!r} is dead: {dead}",
                kind=classify_error(RuntimeError(dead)))
        # Against max_rows, not num_slots: a request wider than the
        # scheduler's batch would pass a slots-only check, then sit at
        # the FIFO head forever (admission only examines the head) and
        # starve every later session for its whole timeout.
        if len(turns) > self.max_rows:
            with self._cv:  # submitter threads race each other here
                self._bump("refused")
            self._event("refuse", session=session,
                        reason=f"{len(turns)} rows > max_rows "
                               f"{self.max_rows}")
            raise SchedulerRefused(
                f"session {session!r} needs {len(turns)} rows but this "
                f"scheduler batches at most {self.max_rows} (num_slots "
                f"{engine.kv.num_slots}) — raise num_slots / max_rows",
                reason="rows_never_fit")
        max_new = max_new_tokens or engine.sampling.max_new_tokens
        store = getattr(engine, "lora", None)
        if store is None:
            adapters_per_turn = None
        elif adapters_per_turn is not None:
            # Validated at the QUEUE mouth (ISSUE 10): a request naming
            # more distinct personas than the store can ever hold
            # deadlocks the FIFO head if queued; unknown personas fail
            # the submitter now instead of at admission. The distinct-
            # count case is a REFUSAL (counted, like the rows/pages
            # never-fits); the rest share LoraStore.validate with the
            # direct generate path so the two cannot drift.
            distinct = {a for a in adapters_per_turn if a is not None}
            if (len(adapters_per_turn) == len(turns)
                    and len(distinct) > store.max_adapters):
                with self._cv:
                    self._bump("refused")
                self._event("refuse", session=session,
                            reason=f"{len(distinct)} adapters > store "
                                   f"{store.max_adapters}")
                raise SchedulerRefused(
                    f"session {session!r} names {len(distinct)} "
                    f"distinct lora adapters but the store holds at "
                    f"most {store.max_adapters} — raise "
                    "lora.max_adapters", reason="adapters_never_fit")
            store.validate(adapters_per_turn, len(turns))
        # Never-fits = LOWER bound (1-token prompts): a request
        # generate_batch could serve must never be refused here.
        need = self._pages_needed(turns, max_new, minimal=True)
        if need > engine.kv.usable_pages():
            with self._cv:
                self._bump("refused")
            self._event("refuse", session=session,
                        reason=f"{need} pages > pool "
                               f"{engine.kv.usable_pages()}")
            raise SchedulerRefused(
                f"session {session!r} needs at least {need} KV pages "
                f"but the pool holds {engine.kv.usable_pages()} — "
                "raise num_pages or lower max_new_tokens",
                reason="pages_never_fit")
        req = _Request(session, list(turns), sampling_per_turn, max_new,
                       timeout_s, budget, self._fresh_stats(),
                       adapters=adapters_per_turn)
        req.on_commit = on_commit
        with self._cv:
            # Re-checked under the lock: close() flips `closed` and
            # drains the queue under this same lock, so a request can
            # never land in a queue no thread will ever tick again.
            if self.closed or self._stop:
                raise SchedulerClosed("scheduler is closed")
            self._queue.append(req)
            self.queued_peak = max(self.queued_peak, len(self._queue))
            self._last_active[session] = time.monotonic()
            self._cv.notify_all()
        return req

    def wait(self, req: _Request):
        """Block until `req` resolves; re-raise its failure.

        The outer bound only catches a WEDGED scheduler, never a
        healthy one: the scheduler restarts the request's clock when
        admission begins (_start_request sets admitted_at; queue time
        is bounded separately in _admit_queued), so the waiter's
        deadline tracks admitted_at + timeout_s + grace — re-evaluated
        each slice, since admission can happen while we wait. Every
        budget/deadline failure in a healthy scheduler resolves the
        event long before this fires."""
        grace = 60.0
        while not req.event.is_set():
            base = (req.admitted_at if req.admitted_at is not None
                    else req.enqueued)
            deadline = base + req.timeout_s + grace
            slice_s = deadline - time.monotonic()
            if slice_s <= 0:
                req.abandoned = True
                with self._cv:
                    self._cv.notify_all()
                raise TimeoutError(
                    f"scheduler did not resolve session {req.session!r} "
                    f"within {req.timeout_s + grace:.0f}s of admission "
                    "(scheduler wedged?)")
            req.event.wait(timeout=min(slice_s, 5.0))
        if req.error is not None:
            raise req.error
        return req.result

    def _fresh_stats(self):
        from .engine import GenStats
        return GenStats()

    def _pages_needed(self, turns, max_new: int,
                      minimal: bool = False) -> int:
        """Page-demand estimate of a request, with max_new clamped the
        way the serving paths clamp it. `minimal=True` is the never-fits
        LOWER bound (1-token prompts — refusal must never reject what
        generate_batch would serve); otherwise prompt lengths are
        estimated from the actual inputs (exact for pre-tokenized
        lists, chars/token ratio for strings, capped at the prompt
        budget) for queue backpressure."""
        engine = self.engine
        kv = engine.kv
        max_new, max_new_padded = clamp_max_new(max_new,
                                                engine.max_seq_len)
        budget_tok = prompt_budget(engine.max_seq_len, max_new_padded)
        total = 0
        for _name, prompt in turns:
            if minimal:
                est = 1
            elif isinstance(prompt, list):
                est = min(len(prompt), budget_tok)
            else:
                cpt = max(engine.chars_per_token(), 0.25)
                est = min(int(len(prompt) / cpt * 1.25) + 1, budget_tok)
            total += -(-(est + max_new_padded) // kv.page_size)
        return total

    # ------------------------------------------------------------------
    # observability
    # ------------------------------------------------------------------

    def _series_labels(self) -> dict[str, str]:
        """Labels for this scheduler's registry series: engine-keyed as
        always, plus `replica=` when the router named this scheduler a
        fleet replica (N replicas share one engine config name)."""
        if self.replica is not None:
            return {"engine": self._tname, "replica": self.replica}
        return {"engine": self._tname}

    def set_replica(self, name: Optional[str]) -> None:
        """Name this scheduler's fleet replica (ISSUE 17). The router
        calls this once at fleet build; passing None detaches (used by
        retire, after the labeled series were removed)."""
        self.replica = name

    def _bump(self, counter: str, n: int = 1) -> None:
        """Increment a decision counter AND its registry series in one
        place — no counter can move without the registry seeing it
        (the drift test pins describe()'s keys to these series)."""
        setattr(self, counter, getattr(self, counter) + n)
        telemetry.inc(f"roundtable_sched_{counter}_total", n,
                      **self._series_labels())

    def _event(self, kind: str, **fields) -> None:
        e = {"event": kind, "at": round(time.monotonic(), 3)}
        e.update(fields)
        with self._cv:  # RLock — safe from paths already holding it
            self._events.append(e)
        # Mirror into the flight recorder (bounded ring): a hang/trip
        # dump then carries the scheduler's recent decisions alongside
        # the engine's spans — the cross-format stitching ISSUE 5 ends.
        telemetry.recorder().record(f"sched_{kind}", engine=self._tname,
                                    **{k: v for k, v in fields.items()
                                       if k not in ("kind", "at")})
        telemetry.set_gauge("roundtable_sched_queue_depth",
                            len(self._queue), **self._series_labels())
        telemetry.set_gauge("roundtable_sched_active_rows",
                            len(self._active), **self._series_labels())

    def describe(self) -> dict[str, Any]:
        """Scheduler provenance for engine.describe() / bench records —
        the decision log the int4 paths set the precedent for. The
        deque copies take the cv lock: callers poll this from
        monitoring/bench threads while the loop appends, and iterating
        a deque mid-append raises."""
        with self._cv:
            occ = list(self._occupancy)
            events = list(self._events)
        loop_seconds, loop_starved = self._clock.snapshots()
        return {
            "admitted": self.admitted,
            "refused": self.refused,
            "completed": self.completed,
            "failed": self.failed,
            "rejected_draining": self.rejected_draining,
            "rejected_other": self.rejected_other,
            "deadline_expired": self.deadline_expired,
            "preemptions": self.preemptions,
            "segments": self.segments,
            "ragged_segments": self.ragged_segments,
            "ragged_joins": self.ragged_joins,
            "indexed_in_flight": self.indexed_in_flight,
            "indexed_at_draft": self.indexed_at_draft,
            "spec_segments": self.spec_segments,
            "probe_intervals": dict(Counter(
                r.spec.interval() if r.spec.disabled else 0
                for r in list(self._active) if r.spec is not None)),
            "segment_prefill_tokens": self.segment_prefill_tokens,
            "segment_decode_tokens": self.segment_decode_tokens,
            "ragged_fill": {
                str(shape): dict(zip(
                    ("dispatches", "buffer_tokens", "real_tokens"), row))
                for shape, row in sorted(self.ragged_fill.items())},
            "queued": len(self._queue),
            "queued_peak": self.queued_peak,
            "active_rows": len(self._active),
            "max_occupancy": self.max_occupancy,
            "occupancy_mean": (round(sum(occ) / len(occ), 2)
                               if occ else 0.0),
            "occupancy_recent": occ[-32:],
            "spills": self.spills,
            "spilled_sessions": len(getattr(
                self.engine, "kv_offload", None).spilled_sessions())
            if getattr(self.engine, "kv_offload", None) is not None
            else 0,
            "paused": self._paused,
            # Machine-readable admission state (ISSUE 16): the gateway
            # and status views key shed decisions on this instead of
            # string-matching events. Nested keys ride under the one
            # bound top-level key.
            "admission": {
                "paused": self._paused,
                "open": self._paused is None and not self.closed,
                "queued": len(self._queue),
            },
            "journal_turns": self.journal_turns,
            "journal_errors": self.journal_errors,
            "loop_seconds": loop_seconds,
            "loop_starved_seconds": loop_starved,
            "events": events,
        }

    def snapshot(self) -> dict[str, Any]:
        """Cheap roll-up for fleet_health(): queue depth + per-session
        state (queued / active with live row count)."""
        sessions: dict[str, str] = {}
        with self._cv:
            for req in self._queue:
                sessions.setdefault(req.session, "queued")
        for req in list(self._active_reqs):
            live = sum(1 for r in req.rows if not r.done)
            sessions[req.session] = f"active({live} live rows)"
        # Spilled-session state only for LIVE schedulers: a closed
        # scheduler's engine may outlive it (module fixtures, the engine
        # cache), and its snapshot claiming host-RAM sessions would make
        # fleet_health point operators at a scheduler that serves
        # nothing.
        tier = getattr(self.engine, "kv_offload", None)
        if tier is not None and not self.closed:
            for s in tier.spilled_sessions():
                sessions.setdefault(s, "spilled(host RAM)")
        return {
            "engine": getattr(self.engine.cfg, "name", "?"),
            "queued": len(self._queue),
            "active_rows": len(self._active),
            "sessions": sessions,
            "paused": self._paused,
            "closed": self.closed,
        }

    def declare_warmup_complete(self) -> None:
        """Declare this scheduler's compile set closed (ISSUE 6): the
        caller has warmed every bucket composition it serves, so any
        later compile is a mid-serve recompile — counted and dumped
        always, fatal under ROUNDTABLE_RECOMPILE_STRICT=1. What warm-up
        built is then taken out of the collector's sight (_settle_heap)."""
        from . import compile_watch
        compile_watch.warmup_complete(self._tname)
        _settle_heap()

    # ------------------------------------------------------------------
    # drain / lifecycle
    # ------------------------------------------------------------------

    def reject_queued(self, error: Optional[BaseException] = None) -> int:
        """Fail every queued-but-unadmitted request immediately (the
        fleet.drain satellite: a queued session gets a clean
        DrainingError instead of waiting out its budget). Active
        requests finish their rounds normally. Returns the count.

        Provenance stays truthful: only drain rejections count as
        `rejected_draining` / event `reject_drain`; close() and
        loop-error rejections land under `rejected_other` with the
        error class named, so describe() never claims a drain that
        never happened."""
        error = error or deadlines.DrainingError(
            "fleet is draining: queued session was never admitted "
            "(fleet.resume() re-opens admission)")
        draining = isinstance(error, deadlines.DrainingError)
        rejected: list[_Request] = []
        with self._cv:
            while self._queue:
                rejected.append(self._queue.popleft())
        for req in rejected:
            req.error = error
            req.event.set()
            with self._cv:  # drain/close threads race the loop thread
                if draining:
                    self._bump("rejected_draining")
                else:
                    self._bump("rejected_other")
            if draining:
                self._event("reject_drain", session=req.session)
            else:
                self._event("reject", session=req.session,
                            reason=type(error).__name__)
        return len(rejected)

    def pause_admission(self, reason: str = "paused") -> None:
        """Close the admission gate (ISSUE 12): queued and newly
        submitted requests WAIT (nothing is rejected); active requests
        keep serving. The supervisor's quiesce and fleet.drain use
        this; reopen_admission (or fleet.resume) lifts it."""
        with self._cv:
            if self._paused is None:
                self._paused = reason
        self._event("pause_admission", reason=reason)

    def reopen_admission(self) -> None:
        """Re-open the admission gate and wake the loop — the
        fleet.resume satellite: a drained/supervised scheduler's queue
        must actually resume admitting, not just stop rejecting."""
        with self._cv:
            was = self._paused
            self._paused = None
            self._cv.notify_all()
        if was is not None:
            self._event("reopen_admission", was=was)

    @property
    def paused(self) -> Optional[str]:
        return self._paused

    def quiesce(self, timeout_s: float = 30.0) -> bool:
        """Pause admission and wait (from a non-loop thread) for every
        ACTIVE request to retire or fail — the supervisor's step 2.
        Returns True when the batch drained clean within `timeout_s`
        (queued requests stay queued; they serve after the restart)."""
        self.pause_admission("quiesce")
        deadline = time.monotonic() + timeout_s
        with self._cv:
            while self._active_reqs and time.monotonic() < deadline:
                # Retirement doesn't notify the cv — the timeout doubles
                # as the poll cadence.
                self._cv.wait(timeout=0.05)
        return not self._active_reqs

    def fail_active_requests(self, err: BaseException) -> int:
        """Fail every active request with `err` — LOOP-THREAD ONLY (the
        supervisor's crash path runs on this thread inside the failed
        dispatch's tick). Returns the count."""
        reqs = list(self._active_reqs)
        for req in reqs:
            self._fail_request(req, err)
        return len(reqs)

    def force_fail_active(self, err: BaseException,
                          timeout_s: float = 5.0) -> int:
        """Thread-safe preempt: ask the loop to fail every active
        request with `err` at its next health check, then wait for it.
        The supervisor's quiesce-timeout fallback — request state is
        single-writer (the loop thread), so an external thread must
        never mutate it directly. Returns requests failed (best
        effort: the loop may be wedged in a device wait, in which case
        the watchdog — not this call — unwedges it)."""
        with self._cv:
            n = len(self._active_reqs)
            if n == 0:
                return 0
            self._force_fail = err
            self._cv.notify_all()
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if not self._active_reqs:
                return n
            time.sleep(0.02)
        return n - len(self._active_reqs)

    def reattach_engine(self, new_engine) -> None:
        """Point this scheduler at a REBUILT engine (the supervisor's
        step 5). Caller contract: admission is paused, no requests are
        active, and the old engine's serve lock is not held by this
        scheduler. The rebuilt engine re-enters warmup (reopen_warmup:
        its compiles are sanctioned, the old engine's frozen heap goes
        back to the collector) until declare_warmup_complete() again."""
        from . import compile_watch
        self.engine = new_engine
        new_engine._scheduler = self
        self.max_rows = min(self.max_rows, new_engine.kv.num_slots)
        compile_watch.reopen_warmup(self._tname)
        _release_heap()
        self._event("reattach_engine")

    def attach_journal(self, journal) -> None:
        """Attach a durable session journal (engine/session_journal):
        every retired round appends one fsynced committed-turn record."""
        self._journal = journal

    @property
    def journal(self):
        return self._journal

    def close(self, timeout_s: float = 30.0) -> None:
        """Stop the loop: reject the queue, give active ones `timeout_s`."""
        self.closed = True
        self.reject_queued(SchedulerClosed(
            "scheduler closed before this session was admitted"))
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        self._thread.join(timeout=timeout_s)
        _release_heap()

    # ------------------------------------------------------------------
    # the scheduler loop
    # ------------------------------------------------------------------

    @_roomy_frame
    def _loop(self) -> None:
        clock = self._clock
        telemetry.bind_loop_clock(clock)
        while True:
            with self._cv:
                # A paused scheduler with only queued work sleeps: the
                # queue cannot be admitted until reopen_admission
                # notifies, and a busy-tick would spin the loop.
                while (not self._active and not self._stop
                       and not self._idle_spill_due()
                       and (not self._queue or self._paused)):
                    clock.mark("wait")
                    self._cv.wait(timeout=0.25)
                    if self._queue and self._paused:
                        # Paused with queued work: tick at the wait
                        # cadence anyway so queue-deadline sweeps still
                        # run (a request must die at ITS timeout even
                        # while admission is gated).
                        break
                if self._stop and not self._active and not self._queue:
                    break
            try:
                self._tick()
            except Exception as e:  # noqa: BLE001 — the loop must survive
                # An unexpected scheduler bug must not wedge every
                # submitter: fail all in-flight work with the error.
                self._event("loop_error", error=str(e))
                self._clock.drain()
                for req in list(self._active_reqs):
                    self._fail_request(req, e)
                self.reject_queued(e)
            if not self._active:
                self._release_engine()
        self._release_engine()

    def _tick(self) -> None:
        clock = self._clock
        clock.tick += 1
        clock.mark("health")
        if deadlines.DRAINING:
            self.reject_queued()
        if self._stop:
            self.reject_queued(SchedulerClosed("scheduler closed"))
        self._check_request_health()
        self._sweep_queue()
        self._prune_last_active()
        self._spill_idle_by_age()
        clock.mark("admit")
        self._admit_queued()
        clock.mark("build")
        live = [r for r in self._active
                if not r.done and not r.pending]
        filling = [r for r in self._active
                   if not r.done and r.pending]
        if filling:
            # Chunk-interleaved admission (ISSUE 8): while any row is
            # still prefilling, segments are RAGGED mixed dispatches —
            # every live row decodes one token while the filling rows'
            # chunks ride the same program. Steady state (no filling
            # rows) keeps the pipelined while-loop segments.
            self._run_ragged_segment(live, filling)
        elif live:
            # Speculative phase (ISSUE 9): with no fills pending and
            # drafts available, one verify dispatch advances every row
            # by 1..spec_max_draft+1 tokens; otherwise the pipelined
            # while-loop segments serve. One dispatch per tick, so
            # joins/retires recompose at every boundary — the
            # _may_speculate composition rules by construction.
            if not self._run_spec_segment(live):
                self._run_segment(live)
        clock.mark("flush")
        if self._flush_streams() and not any(
                r.pending for r in self._active):
            time.sleep(_STREAM_YIELD_S)
        clock.mark("retire")
        self._retire_finished()
        clock.mark("health")
        self._check_request_health()
        self._publish_loop_seconds()

    def _publish_loop_seconds(self) -> None:
        """Move roundtable_sched_loop_seconds_total{phase=} and
        roundtable_sched_starved_seconds_total{phase=} by what the
        loop clock gained since the last tick's end (the _bump rule:
        describe()["loop_seconds"], ["loop_starved_seconds"] and the
        two series are one store)."""
        labels = self._series_labels()
        for series, totals, seen in (
                ("roundtable_sched_loop_seconds_total",
                 self._clock.seconds, self._loop_published),
                ("roundtable_sched_starved_seconds_total",
                 self._clock.starved, self._starved_published)):
            for phase, total in totals.items():
                gained = total - seen[phase]
                if gained > 0.0:
                    seen[phase] = total
                    telemetry.inc(series, gained, phase=phase, **labels)

    def _acquire_engine(self) -> None:
        if not self._lock_held:
            lock = self.engine._serve_lock
            lock.acquire()
            self._held_lock = lock
            self._lock_held = True

    def _release_engine(self) -> None:
        if self._lock_held:
            self._lock_held = False
            lock, self._held_lock = self._held_lock, None
            lock.release()

    # --- admission ---

    def _sweep_queue(self) -> None:
        """Fail expired/abandoned requests ANYWHERE in the queue — not
        just the head: a request stuck behind a non-fitting head must
        still die at ITS deadline with an honest queue timeout, not
        escape 60s later through the waiter's anti-wedge bound."""
        now = time.monotonic()
        expired: list[_Request] = []
        abandoned: list[_Request] = []
        with self._cv:
            keep: deque[_Request] = deque()
            for req in self._queue:
                if req.abandoned:
                    # A blocking waiter is simply gone — drop silently.
                    # A STREAMING submitter (on_commit) still needs the
                    # terminal event: without it the gateway's stream
                    # state never finishes and its inflight gauge
                    # leaks (ISSUE 19 abandonment regression).
                    if req.on_commit is not None:
                        abandoned.append(req)
                    continue
                if ((req.budget is not None and req.budget.expired)
                        or now - req.enqueued > req.timeout_s):
                    expired.append(req)
                else:
                    keep.append(req)
            self._queue = keep
        for req in abandoned:
            self._fail_request(req, TimeoutError(
                f"session {req.session!r} abandoned by its waiter "
                "while queued"))
        for req in expired:
            self._fail_request(req, TimeoutError(
                f"session {req.session!r} timed out in the admission "
                "queue before any capacity freed"))

    def _admit_queued(self) -> None:
        while True:
            with self._cv:
                if not self._queue or self._paused:
                    # Paused (supervisor quiesce / fleet drain): queued
                    # requests WAIT — they are served after the gate
                    # reopens, never rejected here.
                    return
                req = self._queue[0]
                # Batch-formation hold: with an EMPTY batch, wait up to
                # admit_hold_s since the head request enqueued so
                # co-arriving sessions join the same first segment
                # (deterministic co-scheduling for tests/benches).
                if (self.admit_hold_s and not self._active):
                    remaining = (req.enqueued + self.admit_hold_s
                                 - time.monotonic())
                    if remaining > 0:
                        back = self._clock.switch("wait")
                        self._cv.wait(timeout=remaining)
                        self._clock.mark(back)
                        continue
                if not self._fits_now(req):
                    # Backpressure: keep it QUEUED — retirement frees
                    # capacity. (Never-fits was refused at submit.)
                    self._event("queue_wait", session=req.session,
                                queued=len(self._queue))
                    return
                self._queue.popleft()
            self._acquire_engine()
            try:
                if telemetry.ACTIVE:
                    # The request's `admit` span (ISSUE 25): lexical on
                    # the loop thread, caused by — and in the trace of
                    # — the request's own span; admission's dispatches
                    # parent under it.
                    with telemetry.span(
                            "admit", parent=req.tele_ctx,
                            session=req.session,
                            engine=self._tname) as admit:
                        self._start_request(req, admit)
                else:
                    self._start_request(req)
            except Exception as e:  # noqa: BLE001 — per-request contain
                self._clock.drain()     # a prologue nobody reads
                if self._requeue_on_exhaustion(req, e):
                    return
                # _prepare_batch may have acquired slots/pages before
                # raising; req.rows is still empty, so _fail_request's
                # release loop would free nothing — undo explicitly or
                # the orphans distort _fits_now until LRU pressure.
                self._release_request_slots(req)
                self._release_adapters(req)
                self._fail_request(req, e)
                # Engine-fatal triage runs on the admission path too: a
                # device_lost during the admission prefill must reach
                # the supervisor (rebuild + restore), not leave a sick
                # engine serving the remaining sessions.
                if self._supervisor_intervened(e):
                    return
                self._after_engine_failure(e)

    def _owes_index(self) -> list[_Row]:
        """Rows admitted without their n-gram drafter's prompt index."""
        return [r for r in self._active
                if r.spec is not None and r.spec.drafter is None
                and r.spec.kind == "ngram"]

    def _index_in_flight(self, handle) -> None:
        """Between a segment's dispatch and its blocking read: index
        the prompts of rows that still owe it, oldest first, a row at a
        time for as long as the segment in flight runs (`handle`, a
        device array it yields: `is_ready()`). Once it has ended every
        further millisecond here is one the device stands idle, so the
        rest waits for the next segment (or for the row's first draft,
        which builds what is still missing). Loop phase `admit` — the
        work is admission's, deferred — entered from and left to the
        runner's own. Loop-thread only (single-writer counter bumps
        need no cv)."""
        owing = self._owes_index()
        ended = getattr(handle, "is_ready", None)
        if not owing or ended is None:
            return
        from .spec_decode import NGramDrafter
        back = self._clock.switch("admit")
        for r in owing:
            if ended():
                break
            r.spec.drafter = NGramDrafter(r.tokens)   # copies them
            self._bump("indexed_in_flight")
        self._clock.mark(back)

    def _release_request_slots(self, req: _Request) -> None:
        """Undo a partial admission: release every slot this request's
        turns may have acquired (scheduler thread only — KV host state
        is single-writer by design)."""
        for name, _prompt in req.turns:
            try:
                self.engine.kv.release(scoped_slot(req.session, name))
            except Exception:  # noqa: BLE001 — best-effort undo
                pass

    def _requeue_on_exhaustion(self, req: _Request,
                               err: BaseException) -> bool:
        """The page-demand estimate under-counted (token-dense prompts)
        and admission hit real pool exhaustion while other sessions
        hold pages: that is BACKPRESSURE, not a request failure — undo
        the partial admission (release this request's slots; active
        rows are pinned and untouched) and requeue at the head, gated
        on the batch actually shrinking before the next attempt."""
        if (not self._active or req.requeues >= 8
                or not isinstance(err, RuntimeError)
                or "pool exhausted" not in str(err).lower()):
            return False
        self._release_request_slots(req)
        self._release_adapters(req)
        req.requeues += 1
        telemetry.inc("roundtable_sched_requeues_total",
                      engine=self._tname)
        req.fits_below = len(self._active)
        req.admitted_at = None
        with self._cv:
            self._queue.appendleft(req)
        self._event("requeue", session=req.session,
                    reason="page pool exhausted",
                    fits_below=req.fits_below)
        return True

    def _fits_now(self, req: _Request) -> bool:
        engine = self.engine
        if len(self._active) + len(req.turns) > self.max_rows:
            return False
        if (req.fits_below is not None
                and len(self._active) >= req.fits_below):
            # A previous admission of this request hit REAL pool
            # exhaustion at this batch size — wait for retirement to
            # actually shrink the batch before re-attempting.
            return False
        store = getattr(engine, "lora", None)
        if (store is not None and req.adapters
                and not store.can_admit(req.adapters)):
            # Adapter-residency backpressure (ISSUE 10): every store
            # slot is referenced by live rows — retirement frees refs,
            # then the LRU evicts and this request's personas load.
            return False
        if self._active:
            # Pages the live rows have pinned are untouchable; the rest
            # of the pool (free or held by idle evictable slots) is what
            # a join can claim.
            kv = engine.kv
            pinned = kv.pages_held([r.name for r in self._active])
            avail = kv.usable_pages() - pinned
            if self._pages_needed(req.turns, req.max_new) > avail:
                return False
        return True

    # --- host-RAM KV offload policy (ISSUE 7) ---

    def _spillable_sessions(self, exclude: set[str]) -> list[str]:
        """Sessions whose slots sit idle in the pool: namespaced, not
        actively decoding, not queued, not excluded — ordered least-
        recently-active first."""
        from .kvcache import session_of
        busy = {session_of(r.name) for r in self._active}
        with self._cv:
            busy |= {r.session for r in self._queue}
        busy |= exclude
        seen: dict[str, None] = {}
        for n in self.engine.kv.slot_names():
            s = session_of(n)
            if s and s not in busy:
                seen.setdefault(s)
        return sorted(seen, key=lambda s: self._last_active.get(s, 0.0))

    def _spill_sessions(self, sessions: list[str], reason: str,
                        want_pages: Optional[int] = None) -> int:
        tier = getattr(self.engine, "kv_offload", None)
        if tier is None:
            return 0
        kv = self.engine.kv
        spilled = 0
        for s in sessions:
            free0 = kv.free_pages()
            n = tier.spill_session(s)
            if n:
                spilled += 1
                with self._cv:
                    self._bump("spills")
                self._event("spill", session=s, reason=reason,
                            slots=n, pages_freed=kv.free_pages() - free0)
            if want_pages is not None and kv.free_pages() >= want_pages:
                break
        return spilled

    _LAST_ACTIVE_PRUNE_AT = 1024

    def _prune_last_active(self) -> None:
        """Bound the last-activity map: a long-lived scheduler admits a
        fresh uuid-tagged session id per discussion, and an entry per
        dead session forever is the same slow leak the per-session KV
        gauges already had to fix (PR 6's remove_gauge). Entries whose
        session holds no pool slots, no spill record, and is neither
        active nor queued are gone for good — drop them once the map
        outgrows the threshold (amortized: one sweep per ~1024 dead
        sessions, host dict math only)."""
        if len(self._last_active) <= self._LAST_ACTIVE_PRUNE_AT:
            return
        from .kvcache import session_of
        keep = {session_of(n) for n in self.engine.kv.slot_names()}
        tier = getattr(self.engine, "kv_offload", None)
        if tier is not None:
            keep |= set(tier.spilled_sessions())
        keep |= {r.session for r in self._active_reqs}
        # The sweep holds the cv: submit() threads insert new sessions
        # into this dict under the same lock, and a resize mid-iteration
        # would raise out of _tick and fail every in-flight request.
        with self._cv:
            keep |= {r.session for r in self._queue}
            for s in [s for s in self._last_active if s not in keep]:
                del self._last_active[s]

    def _idle_spill_due(self) -> bool:
        """True when the proactive idle policy has work — the loop's
        idle wait must wake for it, or an otherwise-quiet scheduler
        would never run the spill tick."""
        if (self.idle_spill_s is None or self._paused
                or getattr(self.engine, "kv_offload", None) is None):
            # Paused must mirror _spill_idle_by_age's gate: if "due"
            # stayed True while the spill tick refused to run, the idle
            # wait would never sleep and the loop would busy-spin for
            # the whole pause window.
            return False
        now = time.monotonic()
        return any(now - self._last_active.get(s, now)
                   >= self.idle_spill_s
                   for s in self._spillable_sessions(set()))

    def _spill_idle_by_age(self) -> None:
        """Proactive idle spill (idle_spill_s set): a session that has
        not submitted for idle_spill_s releases its HBM pages to host
        RAM — a consensus round can sit for minutes while humans type,
        and resident-but-idle KV is exactly the capacity ceiling this
        tier lifts."""
        if (self.idle_spill_s is None or self._paused
                or getattr(self.engine, "kv_offload", None) is None):
            # Paused: the supervisor may hold (or be about to take) the
            # serve lock for an engine swap — don't contend for it.
            return
        now = time.monotonic()
        idle = [s for s in self._spillable_sessions(set())
                if now - self._last_active.get(s, now)
                >= self.idle_spill_s]
        if not idle:
            return
        self._acquire_engine()
        try:
            self._spill_sessions(idle, reason="idle")
        finally:
            if not self._active:
                self._release_engine()

    def _spill_for_pressure(self, req: _Request) -> None:
        """Admission-time pressure valve: when the pool's FREE pages
        cannot cover the incoming request's estimate, spill idle
        sessions (least-recently-active first) BEFORE _prepare_batch
        runs — otherwise the allocator's LRU eviction would destroy
        exactly the idle caches that make those sessions' next turns
        cheap. The admission itself then proceeds instead of queueing
        behind capacity that idle sessions were hoarding."""
        engine = self.engine
        if getattr(engine, "kv_offload", None) is None:
            return
        # NEW-page demand, not the whole-prompt estimate: in steady
        # state a session's next turn is mostly its own committed
        # transcript, already paged in under its scoped slots — counting
        # those pages as demand would declare pressure on every
        # admission past ~half occupancy and churn idle sessions
        # through spill/restore for pages the turn never needed.
        scoped = [scoped_slot(req.session, n) for n, _ in req.turns]
        need = (self._pages_needed(req.turns, req.max_new)
                - engine.kv.pages_held(scoped))
        free = engine.kv.free_pages()
        if need <= free:
            return
        self._spill_sessions(
            self._spillable_sessions(exclude={req.session}),
            reason="pressure", want_pages=need)

    def _start_request(self, req: _Request, admit=None) -> None:
        """Admission: the engine's own pre-decode phase
        (InferenceEngine._prepare_batch — reuse-plan → intra-session
        prefix share → chunked prefill → first-token sample; ONE
        definition, so scheduler admission can never drift from
        generate_batch on token parity), with every live row pinned
        against eviction. Loop-thread only (single-writer counter
        bumps need no cv — RT-LOCK-BUMP contract)."""
        engine = self.engine
        sync_before = self._clock.seconds["admit_sync"]
        # Admission STARTS the request's clock (queue time is bounded
        # separately in _admit_queued): the scheduler-side deadline and
        # the waiter's anti-wedge bound both key off this moment.
        req.admitted_at = time.monotonic()
        if faults.ARMED and len(req.turns) > 1:
            # Same chaos point as the engine's batched path: a corrupt-KV
            # fault fails the fan-out before slot bookkeeping mutates,
            # so the adapter's serial-retry rung takes over per session.
            faults.maybe_inject("kv_corrupt")
        t0 = time.monotonic()
        stats = req.stats
        turn_budget = req.budget if req.budget is not None \
            else deadlines.Budget.root(req.timeout_s, rung="turn")
        deadline = min(turn_budget.deadline,
                       time.monotonic() + req.timeout_s)
        pre_budget = turn_budget.child("prefill")
        max_new, max_new_padded = clamp_max_new(req.max_new,
                                                engine.max_seq_len)

        self._spill_for_pressure(req)
        # Adapter residency (ISSUE 10): taken on the scheduler thread
        # while it holds the engine serve lock, so a load's stacked-
        # tensor swap can never race a dispatch's argument capture.
        # Refs are held for the REQUEST's lifetime (rows keep decoding
        # across segments) and released at retire/fail.
        store = getattr(engine, "lora", None)
        row_slots = None
        if store is not None:
            ads = req.adapters or [None] * len(req.turns)
            row_slots = store.acquire(ads)
            req.adapters = ads
            req.adapters_held = True
        active_names = tuple(r.name for r in self._active)
        scoped_turns = [(scoped_slot(req.session, n), p)
                        for n, p in req.turns]
        # Chunk-interleaved admission (ISSUE 8): with live rows decoding
        # and the engine's ragged path on, the prologue's chunked
        # prefill is DEFERRED — admission does only the host/aliasing
        # work, and the suffixes join the live decode segment as ragged
        # chunks. An empty batch keeps the prologue (there is no decode
        # to interleave with, and the bucketed chunks are bigger).
        # ROUNDTABLE_RAGGED_ATTN=0 restores the prologue unconditionally.
        # Defer only onto the KERNEL path: an engine whose pool the
        # kernel declined at build time (xla_ragged — the memory-heavy
        # dense fallback, "never the serving default") keeps the
        # prologue for joins; the fallback still serves fills already
        # in flight when a mid-serve degrade flips the path.
        deferred = (getattr(engine, "ragged_path", None)
                    == "pallas_ragged"
                    and (bool(self._active)
                         or getattr(engine, "joins_ragged_alone", False)))
        prep = engine._prepare_batch(
            scoped_turns, max_new_padded, deadline, pre_budget,
            req.sampling_per_turn, extra_pinned=active_names,
            defer_prefill=deferred, adapters=req.adapters)
        # The engine may resolve a WARM join back to the prologue
        # (suffix below ragged_defer_min — blocking one tiny bucket
        # dispatch beats segment-gated chunk ticks); first_np says
        # which mode actually served.
        deferred = prep["first_np"] is None
        stats.prefill_tokens = prep["prefill_tokens"]
        stats.reused_tokens = prep["reused_tokens"]
        stats.prefix_reused_tokens = prep["prefix_reused_tokens"]
        stats.prefill_seconds = time.monotonic() - t0
        if row_slots and any(row_slots) and prep["first_np"] is not None:
            engine.note_lora_tokens(sum(
                len(t) - o for t, o, sl in zip(prep["all_tokens"],
                                               prep["offsets"],
                                               row_slots) if sl))

        eos = engine.tokenizer.eos_id
        per_row = prep["per_row"]
        rows = []
        for i, scoped in enumerate(prep["names"]):
            # Only an EXPLICIT sampling_per_turn carries per-row caps —
            # the engine-default sampling's budget must not silently cap
            # the call-level request (serving_loop.row_budget_fn rule).
            row_cap = (min(per_row[i].max_new_tokens, max_new)
                       if req.sampling_per_turn else max_new)
            toks = prep["all_tokens"][i]
            if deferred:
                off = prep["offsets"][i]
                if off >= len(toks):
                    # Full-prefix cache hit: re-feed the last prompt
                    # token (identical K/V bytes at its own position)
                    # so the join still samples a first token; COW the
                    # rewritten cell out of any shared page first.
                    off = len(toks) - 1
                    engine.kv.ensure_capacity(
                        scoped, len(toks), write_from=off,
                        pinned=tuple(prep["names"]) + active_names)
                rows.append(_Row(
                    name=scoped, tokens=toks, sampling=per_row[i],
                    max_new=row_cap,
                    pending=list(toks[off:]), pos=off, valid=off,
                    adapter_slot=(row_slots[i] if row_slots else 0)))
            else:
                tok = int(prep["first_np"][i])
                rows.append(_Row(
                    name=scoped, tokens=toks,
                    sampling=per_row[i], max_new=row_cap,
                    produced=[tok],
                    last=tok, valid=len(toks),
                    done=(tok == eos),
                    adapter_slot=(row_slots[i] if row_slots else 0)))
        req.rows = rows
        if engine.spec_decode:
            # Per-row self-drafters (ISSUE 9): the corpus is the row's
            # OWN prompt — which carries the whole transcript and any
            # prefix-cache-attached context — extended incrementally as
            # output tokens commit (RowSpec.drafter.sync before every
            # draft). Indexing a prompt is host dict work, O(prompt) a
            # row and most of an admission's host time at transcript
            # lengths; nothing needs it before the row's first draft,
            # so the row is admitted WITHOUT its index (ISSUE 30): it
            # is built under a segment in flight (_index_in_flight) or,
            # if none came first, at that draft (_spec_drafts). Device
            # drafters (model/lora) keep their state in the shadow
            # draft slots and have no index at all.
            from .spec_decode import RowSpec
            kind = getattr(engine, "spec_drafter", None) or "ngram"
            for r in rows:
                r.spec = RowSpec(kind=kind)
        if deferred:
            # Deferred leader-span plans (the last prologue dispatch,
            # gone): laggard rows BLOCK until the leader's chunks write
            # the common span, then alias it in (_apply_share_plans).
            req.share_plans = [
                {"leader": rows[p["leader"]], "hi": p["hi"],
                 "followers": [(rows[i], lo) for i, lo in
                               p["followers"]],
                 "hand": p.get("hand")}
                for p in prep.get("share_plan", [])]
            for plan in req.share_plans:
                for f, _lo in plan["followers"]:
                    f.blocked = True
                if plan["hand"] is not None:
                    # A model with recurrent state: the leader leaves
                    # its state where the laggards start, kept for them
                    # from here until each has joined (_alias_due) or
                    # the request is dropped (_drop_request).
                    engine.hybrid.expect(plan["leader"].name,
                                         *plan["hand"],
                                         len(plan["followers"]))
        req.turn_budget = turn_budget
        req.dec_budget = turn_budget.child("decode")
        req.deadline = deadline
        if not deferred:
            req.first_token_at = time.monotonic()
        self._active.extend(rows)
        self._active_reqs.append(req)
        for r in rows:
            self._row_req[id(r)] = req
        self._bump("admitted")
        if deferred:
            self._bump("ragged_joins")
        if admit is not None:
            # Counts at the boundary where the work happened: the
            # prompt tokens this admission has to prefill (in its
            # prologue, or — `deferred` — as chunks of ragged segments)
            # and those it found cached, and how long it stood blocked
            # in host_sync.
            admit.attrs.update(
                rows=len(rows), deferred=deferred,
                prefill_tokens=stats.prefill_tokens,
                reused_tokens=stats.reused_tokens,
                prefix_reused_tokens=stats.prefix_reused_tokens,
                queue_wait_s=round(req.admitted_at - req.enqueued, 6),
                sync_s=round(self._clock.seconds["admit_sync"]
                             - sync_before, 6))
            sp = prep.get("state_plan")
            if sp is not None:
                # Where each row's recurrent state came from, and how
                # many tokens had pages but no state (re-scanned).
                admit.attrs.update(
                    state_from=",".join(
                        f"{k}:{sp[k]}" for k in
                        ("continue", "snapshot", "zero") if sp[k]),
                    state_continue=sp["continue"],
                    state_snapshot=sp["snapshot"],
                    state_zero=sp["zero"],
                    prompt_tokens=sp["prompt_tokens"],
                    kv_matched_tokens=sp["kv_matched_tokens"],
                    state_reused_tokens=sp["state_reused_tokens"],
                    state_copy_bytes=sp["state_copy_bytes"])
        if telemetry.ACTIVE:
            # The request's "turn" span: lives across segments (ended at
            # retire/fail), parented to the SUBMITTER's trace so spans
            # from the scheduler thread land in the right discussion.
            req.tele = telemetry.start_span(
                "turn", parent=req.tele_ctx, session=req.session,
                engine=self._tname, rows=len(rows), scheduled=True,
                queue_wait_s=round(req.admitted_at - req.enqueued, 3))
        self._event("admit", session=req.session, rows=len(rows),
                    queue_wait_s=round(req.admitted_at - req.enqueued, 3),
                    reused_tokens=stats.reused_tokens,
                    ragged_join=deferred)

    # --- the decode segment ---

    def _run_segment(self, live: list[_Row]) -> None:
        """Run one or more DECODE_SEGMENTs over the live rows,
        PIPELINED like serving_loop.decode_segments: while composition
        cannot change (no queued session, nobody waiting to retire,
        work remaining), the next segment is dispatched from the
        previous segment's DEVICE outputs BEFORE the host reads them —
        the device never idles on the per-segment host round-trip
        (material wherever the host is slow). The mini-loop exits
        whenever the batch must recompose (join pending, a request fully done,
        budgets/deadline/drain) and _tick takes over."""
        clock = self._clock
        clock.mark("build")
        pack = self._open_pack()
        ctx = self._build_batch(live)
        pack.leave()
        # The clock starts BEFORE the first dispatch (ISSUE 9 perfmodel
        # satellite): on synchronous backends the jit call itself runs
        # the compute, so starting after it attributed ~zero decode
        # seconds to every single-segment turn — and its 'tok/s' then
        # read as thousands. Dispatch-issue time is part of the
        # segment's wall on async backends too.
        t_prev = time.monotonic()
        try:
            handles = self._dispatch(ctx)
        except Exception as e:  # noqa: BLE001 — preempt-isolate ladder
            self._handle_segment_failure(live, e)
            return
        while True:
            # A segment is in flight and unread: host work that needs
            # no idle device runs under it (ISSUE 30).
            self._index_in_flight(handles[1])
            clock.mark("build")
            spec_ctx = spec_handles = spec_err = None
            if self._may_speculate(ctx):
                spec_ctx = self._advance(ctx, handles)
                try:
                    spec_handles = self._dispatch(spec_ctx)
                except Exception as e:  # noqa: BLE001 — handled below
                    # The in-flight segment is still unread; read it
                    # first so host state is consistent, THEN ladder
                    # the speculative dispatch's failure.
                    spec_err = e
            clock.mark("accept")
            alive = [r for r in ctx["rows"] if not r.done]
            counts = self._account_segment(alive)
            # Scheduler-side "segment" span (sink-less: it spans
            # SEVERAL sessions' traces, so it lands in the flight
            # recorder ring rather than any one session's JSONL). Its
            # stretch is the blocking read; the fold's counts ride it.
            seg = self._open_segment("plain", len(alive), ctx["size"])
            if pack is not telemetry.NULL_SPAN:
                # (The mini-loop's later segments are carried on the
                # device from this one's outputs: nothing is packed.)
                self._end_pack(pack, seg, "plain", len(alive), len(alive))
                pack = telemetry.NULL_SPAN
            try:
                arrays = self._sync_segment(ctx, handles)
            except Exception as e:  # noqa: BLE001 — preempt-isolate
                seg.end(f"error:{type(e).__name__}")
                self._handle_segment_failure(alive, e)
                return
            seg.leave()
            steps = self._fold_segment(ctx, arrays)
            self._end_segment(seg, steps, steps * len(alive),
                              in_flight=int(spec_handles is not None),
                              read_to=tuple(r.valid for r in alive),
                              filtered_rows=sum(
                                  row_filtered(r.sampling) for r in alive))
            now = time.monotonic()
            self._attribute_wall(counts, now - t_prev)
            # Per-phase token split (ISSUE 8): a while-loop segment is
            # pure decode — counted into the same series the ragged
            # mixed segments split, so the two paths share one ledger.
            self._note_segment_tokens(0, steps * len(alive))
            t_prev = now
            if spec_err is not None:
                still = [r for r in alive
                         if not r.done and id(r) in self._row_req]
                if still:
                    self._handle_segment_failure(still, spec_err)
                return
            if spec_handles is None:
                return
            ctx, handles = spec_ctx, spec_handles

    def _open_segment(self, kind: str, rows: int, size: int):
        """Open (and enter) a scheduler `segment` span of `kind`
        plain | ragged | spec; `size` is its program's padded batch
        (plain) or flat-buffer shape, which with the kind gives the
        compile watch's label of that program. Unarmed: the null span —
        positional arguments only, so nothing is built for it."""
        if not telemetry.ACTIVE:
            return telemetry.NULL_SPAN
        label = (f"decode[b={size},paged]" if kind == "plain"
                 else f"ragged[t={size}]")
        seg = telemetry.start_span(
            "segment", engine=self._tname, rows=rows, scheduled=True,
            kind=kind, label=label, tick=self._clock.tick)
        seg.__enter__()
        return seg          # every runner ends it (_end_segment)

    def _open_pack(self):
        """Open a `pack` span (ISSUE 37): building one segment's host
        arrays, up to its dispatch. Held, not entered — the segment
        span it packs for opens after it: `_end_pack` makes that one
        its parent. Unarmed: the null span, nothing built."""
        if not telemetry.ACTIVE:
            return telemetry.NULL_SPAN
        return telemetry.start_span("pack", engine=self._tname)

    def _end_pack(self, pack, seg, kind: str, rows: int,
                  tokens: int) -> None:
        """Emit `pack` (its stretch already left) under `seg`, with the
        kind of segment, its rows and the tokens they feed."""
        if seg is not telemetry.NULL_SPAN:
            pack.trace_id, pack.parent_id = seg.trace_id, seg.span_id
        pack.attrs.update(kind=kind, rows=rows, tokens=tokens)
        pack.end()

    def _end_segment(self, seg, steps: int, decode_tokens: int,
                     prefill_tokens: int = 0, drafted: int = 0,
                     accepted: int = 0, in_flight: int = 0,
                     read_to: tuple = (),
                     ragged: Optional[dict] = None,
                     filtered_rows: int = 0, probes: int = 0,
                     probes_accepted_none: int = 0) -> None:
        """Emit a segment span with the counts its fold produced, and
        the pool's pages in use at its end (the pool's peak over any
        stretch is the maximum over that stretch's segment spans).
        `in_flight`: segments issued after this one and not yet read.
        `read_to`: how many positions each row's attention read at the
        segment's last step (a model with latent pages counts them:
        `latent_positions`, every step's reads of every row).
        `ragged`: the dispatched ragged batch, for what its attention
        read in page visits (engine._note_page_visits); a model whose
        attention layers differ adds `page_visits_full` and
        `page_visits_window`, by layer class, and what the rows hold
        (`pages_held`, of it `pages_behind_window`), on every kind of
        segment. `filtered_rows`: the segment's rows whose top_k or
        top_p engages the sampler's candidate pool (a ragged batch
        brings its own count). `probes`: 1 on a verify that was the
        batch throttle's re-probe (spec_decode.BatchThrottle),
        `probes_accepted_none` 1 if its rows accepted no drafted
        token."""
        if ragged is not None:
            filtered_rows = ragged["filtered_rows"]
        self.engine.spec_batch.advance(steps)
        self.engine.note_sampler_segment(filtered_rows)
        latent = None
        if getattr(self.engine.cfg, "latent", False):
            latent = sum(steps * v - steps * (steps - 1) // 2
                         for v in read_to)
            self.engine.note_latent_positions(latent)
        # Attention layers that differ (a window on some): what each
        # class read, counted by the engine at the ragged dispatch or,
        # for a plain segment, here from the rows' frontiers.
        window_reads = None
        if getattr(self.engine.cfg, "attn_layers", None) is not None:
            window_reads = dict(
                ragged.get("window_reads") if ragged is not None
                else self.engine.plain_window_reads(steps, read_to),
                **self.engine.window_page_holdings(read_to))
        hy = getattr(self.engine, "hybrid", None)
        if ragged is None and hy is not None:
            # (a ragged dispatch counted its own at the seam)
            self.engine.note_plain_shared_reads(steps, read_to)
        if hy is not None:
            # This segment has been read, so every program up to it has
            # ended: its expert counts fold into host ints without a
            # wait (the one issued after it, if any, stays queued).
            hy.fold_counts(keep=in_flight)
        if seg is telemetry.NULL_SPAN:
            self._hy_counting = False
            return
        seg.attrs.update(steps=steps, decode_tokens=decode_tokens,
                         prefill_tokens=prefill_tokens, drafted=drafted,
                         accepted=accepted, filtered_rows=filtered_rows,
                         probes=probes,
                         probes_accepted_none=probes_accepted_none)
        if latent is not None:
            seg.attrs["latent_positions"] = latent
        if ragged is not None and "page_visits" in ragged:
            seg.attrs.update(
                page_visits=ragged["page_visits"],
                page_visits_by_eights=ragged["page_visits_by_eights"])
        if window_reads is not None:
            seg.attrs.update(window_reads)
        seg.attrs["pages_in_use"] = self.engine.kv.pages_in_use()
        if hy is not None:
            # What the expert layers touched since the last segment
            # span ended (a prologue's prefill in between counts with
            # the segment after it) and the snapshot store now. The
            # first span after arming only sets the base.
            delta, taken = hy.moe_delta(), hy.snapshots_taken
            scanned, conved = hy.scan_tokens, hy.conv_tokens
            seam = dict(hy.seam)
            if self._hy_counting:
                seg.attrs.update(
                    delta, snapshots_taken=taken - self._snaps_seen,
                    state_capture_bytes=(taken - self._snaps_seen)
                    * hy.bytes_per_state)
                if self.engine.cfg.mamba1_layers:
                    seg.attrs["scan_tokens"] = scanned - self._scan_seen
                if self.engine.cfg.shortconv_layers:
                    seg.attrs["conv_tokens"] = conved - self._conv_seen
                if self.engine.cfg.last_token_from is not None:
                    seg.attrs.update({k: n - self._seam_seen.get(k, 0)
                                      for k, n in seam.items()})
            self._hy_counting, self._snaps_seen = True, taken
            self._scan_seen, self._conv_seen = scanned, conved
            self._seam_seen = seam
            seg.attrs["snapshot_bytes"] = hy.snapshot_bytes()
        seg.end()

    # --- the ragged mixed segment (ISSUE 8) ---

    def _note_segment_tokens(self, prefill: int, decode: int) -> None:
        """Per-phase token split of a consumed segment — the counters
        AND their registry series move together (the _bump rule), so
        describe() and the drift lint stay honest for mixed batches."""
        if prefill:
            self.segment_prefill_tokens += prefill
            telemetry.inc("roundtable_segment_prefill_tokens_total",
                          prefill, engine=self._tname)
        if decode:
            self.segment_decode_tokens += decode
            telemetry.inc("roundtable_segment_decode_tokens_total",
                          decode, engine=self._tname)

    def _note_ragged_fill(self, seg, shape: int, want: int,
                          real: int) -> None:
        """One join dispatch's flat buffer (`shape`) and the real tokens
        it carried, into `ragged_fill`, the two series and the
        dispatch's `segment` span — which also carries `want`, what the
        rows that were due asked of the buffer in 8-row blocks before
        the budget capped it (how much a larger top shape would have
        taken)."""
        row = self.ragged_fill.setdefault(shape, [0, 0, 0])
        row[0] += 1
        row[1] += shape
        row[2] += real
        telemetry.inc("roundtable_ragged_buffer_tokens_total", shape,
                      engine=self._tname, shape=shape)
        telemetry.inc("roundtable_ragged_real_tokens_total", real,
                      engine=self._tname, shape=shape)
        if seg is not telemetry.NULL_SPAN:
            seg.attrs.update(shape=shape, real_tokens=real, want=want)

    def _apply_share_plans(self) -> None:
        """Alias deferred leader spans whose leader chunks have written
        the common span (kvcache.share_prefixes defer_span contract):
        laggards' tables take the leader's span pages (whole pages
        alias, boundary pages are copied: queued on the page cache and
        issued with every other pending copy before the segment's
        program takes the pools, ISSUE 38) and the rows unblock, their
        pending trimmed to the post-span tail at admission — or, where
        the model holds recurrent state, from the page boundary under
        the span's end at which the leader left its state
        (engine.join_laggard). Armed, a request whose plans are due gets
        a `share` span in its own trace (ISSUE 37; the pass that finds
        nothing due builds none): followers unblocked, pages aliased,
        pages copied, and with state the followers `handed` the
        leader's, the prompt tokens they were spared and the bytes
        their restores wrote."""
        for req in list(self._active_reqs):
            due = [p for p in req.share_plans
                   if p["leader"].pos >= p["hi"]]
            if not due:
                continue
            with self._open_share(req) as share:
                failed, done = self._alias_due(req, due)
                if share is not telemetry.NULL_SPAN:
                    share.attrs.update(done)
            if failed is not None:
                self._fail_request(req, failed)
                continue
            req.share_plans = [p for p in req.share_plans
                               if p["leader"].pos < p["hi"]]

    def _open_share(self, req: _Request):
        """A `share` span in the request's own trace; unarmed, the null
        span — positional arguments only, nothing built."""
        if not telemetry.ACTIVE:
            return telemetry.NULL_SPAN
        return telemetry.start_span("share", parent=req.tele_ctx,
                                    session=req.session,
                                    engine=self._tname)

    def _alias_due(self, req: _Request, due: list[dict]
                   ) -> tuple[Optional[BaseException], dict]:
        """Join each due plan's followers behind their leader. -> (the
        error that stopped it, if one did; the `share` span's counts)."""
        done = {"followers": 0, "pages_aliased": 0, "copies": 0}
        with_state = any(p["hand"] is not None for p in due)
        if with_state:
            # (`kv_matched_tokens` / `state_reused_tokens`: as an `admit`
            # span says them of the rows it plans, here of the laggards)
            done.update(handed=0, tokens_spared=0, state_copy_bytes=0,
                        kv_matched_tokens=0, state_reused_tokens=0)
        pinned = tuple(r.name for r in self._active)
        _max_new, padded = clamp_max_new(
            req.max_new, self.engine.max_seq_len)
        failed: Optional[BaseException] = None
        for plan in due:
            leader, hi = plan["leader"], plan["hi"]
            n = len(plan["followers"])
            try:
                while plan["followers"]:
                    f, lo = plan["followers"][0]
                    got = self.engine.join_laggard(
                        leader.name, f.name, f.tokens, lo, hi,
                        len(f.tokens) + padded, pinned, plan["hand"])
                    del plan["followers"][0]
                    start = got["start"]
                    if start < hi:
                        # The row's state stands under the span's end:
                        # it scans from there.
                        f.pending = list(f.tokens[start:])
                        f.pos = f.valid = start
                        req.stats.prefill_tokens += hi - start
                        req.stats.reused_tokens -= hi - start
                    f.blocked = False
                    done["followers"] += 1
                    done["pages_aliased"] += got["aliased"]
                    done["copies"] += got["copies"]
                    if with_state:
                        done["handed"] += got["handed"]
                        done["tokens_spared"] += max(start - lo, 0)
                        done["state_copy_bytes"] += got["state_copy_bytes"]
                        done["kv_matched_tokens"] += hi
                        done["state_reused_tokens"] += start
            except Exception as e:  # noqa: BLE001 — contain per req
                # Pool exhaustion mid-join (the prologue path's
                # equivalent was a requeue at admission): fail ONLY
                # this request into its adapter ladder — an escape
                # to _loop's catch-all would take every in-flight
                # session down with it.
                failed = e
                break
            self._event("share_alias", session=req.session,
                        hi=hi, followers=n)
        return failed, done

    def _run_ragged_segment(self, live: list[_Row],
                            filling: list[_Row]) -> None:
        """One RAGGED mixed dispatch: every live decode row advances one
        token while the filling rows' next prefill chunks ride the SAME
        program — the admission prologue's replacement (arxiv
        2604.15464; RTP-LLM's chunked-prefill-joins-the-decode-batch
        shape). The flat buffer is token-budgeted, not row-bucketed:
        one compiled shape serves every composition, so occupancy drift
        and chunk interleaving compile nothing. The loop runs one
        dispatch per _tick so joins/retires/admissions interleave at
        every boundary."""
        engine = self.engine
        budget_slots = engine.ragged_tokens
        self._clock.mark("build")
        # A leader that finished its span in the previous dispatch
        # unblocks its laggards BEFORE packing, so their chunks join
        # this very segment.
        self._apply_share_plans()
        # (A request that alias failed has left `_row_req`, its
        # leader — filling still, or live by now — with it.)
        live = [r for r in live if id(r) in self._row_req]
        filling = [r for r in filling if not r.done and r.pending
                   and not r.blocked and id(r) in self._row_req]
        if not filling:
            if live:
                self._run_segment(live)
            return
        # A decode row costs one RAGGED_BLOCK_Q tile; keep at least one
        # block of chunk room or the mix degenerates.
        if RAGGED_BLOCK_Q * (len(live) + 1) > budget_slots:
            # Flat buffer cannot carry every live row plus prefill work
            # — decode this segment on the compiled bucket path instead
            # (recorded; prefill continues next tick, never silently
            # stalled).
            self._event("ragged_overflow", rows=len(live))
            if live:
                self._run_segment(live)
            return
        pack = self._open_pack()
        reqs = self._reqs_of(live + filling)
        remaining = min((req.turn_budget.remaining() for req in reqs),
                        default=float("inf"))
        seg_budget = deadlines.Budget.root(
            None if remaining == float("inf") else remaining,
            rung="decode")
        deadline = min((req.deadline for req in reqs),
                       default=float("inf"))

        # Pick the smallest warmed flat-buffer shape that fits the REAL
        # work (serving_loop.ragged_shape_grid): a dispatch computes its
        # whole static buffer, so a lone decode step + tail chunk must
        # not pay the full budget's compute.
        from .serving_loop import ragged_pick_shape
        want = RAGGED_BLOCK_Q * len(live) + sum(
            -(-len(r.pending) // RAGGED_BLOCK_Q) * RAGGED_BLOCK_Q
            for r in filling)
        # A want past the top shape is two dispatches, the second with
        # a block of every active row beside the remainder: the first
        # is the shape that makes the two cheapest. (An engine of the
        # hybrid step programs keeps the parent's pick with the
        # parent's budget — ragged_token_budget has the reasons; of
        # the nine cells only Brumby's key gives such an engine a
        # second shape to pick, and its followers re-scan a page's
        # remainder, so what a second dispatch brings there is not
        # the rows' blocks.)
        shape = ragged_pick_shape(
            engine.ragged_shapes,
            want if engine.hybrid is None else min(want, budget_slots),
            carry=RAGGED_BLOCK_Q * len(self._active))
        seqs: list[RaggedSeq] = []
        rows_in: list[tuple[str, _Row, int]] = []
        for r in live:
            seqs.append(RaggedSeq(
                [r.last], r.valid, engine.kv.table_for([r.name])[0],
                temperature=r.sampling.temperature,
                top_k=r.sampling.top_k, top_p=r.sampling.top_p,
                adapter=r.adapter_slot))
            rows_in.append(("decode", r, 1))
        slots_left = shape - RAGGED_BLOCK_Q * len(live)
        for r in filling:
            if slots_left < RAGGED_BLOCK_Q:
                break
            take = min(len(r.pending), slots_left)
            seqs.append(RaggedSeq(
                list(r.pending[:take]), r.pos,
                engine.kv.table_for([r.name])[0],
                temperature=r.sampling.temperature,
                top_k=r.sampling.top_k, top_p=r.sampling.top_p,
                adapter=r.adapter_slot))
            rows_in.append(("prefill", r, take))
            slots_left -= -(-take // RAGGED_BLOCK_Q) * RAGGED_BLOCK_Q
        batch = build_ragged_batch(
            seqs, t_budget=shape, s_max=engine.kv.num_slots + 1,
            pages_per_seq=engine.kv.pages_per_seq,
            scratch_page=engine.kv.scratch_page(0),
            pad_id=engine.tokenizer.pad_id,
            page_size=engine.kv.page_size)
        # (A model with recurrent state finds each run's state by it.)
        batch["seq_names"] = [r.name for _k, r, _t in rows_in]

        pack.leave()
        t0 = time.monotonic()
        seg = self._open_segment("ragged", len(seqs), shape)
        if pack is not telemetry.NULL_SPAN:
            self._end_pack(pack, seg, "ragged", len(seqs),
                           sum(t for _k, _r, t in rows_in))
        try:
            handles = run_dispatch(
                lambda: engine._ragged_dispatch(batch),
                engine.retry, deadline, budget=seg_budget)
            fed = self._clock.feed()
            self._index_in_flight(handles)
            nxt = host_sync(lambda: np.asarray(handles), seg_budget,
                            "decode")
            self._clock.drain(fed)
        except Exception as e:  # noqa: BLE001 — preempt-isolate ladder
            seg.end(f"error:{type(e).__name__}")
            self._handle_ragged_failure(live, filling, e)
            return
        seg.leave()
        self._clock.mark("accept")
        wall = time.monotonic() - t0

        eos = engine.tokenizer.eos_id
        now = time.monotonic()
        n_prefill = n_decode = 0
        lora_toks = 0
        for i, (kind, r, take) in enumerate(rows_in):
            tok = int(nxt[i])
            req = self._row_req.get(id(r))
            if kind == "decode":
                r.produced.append(tok)
                r.last = tok
                r.valid += 1
                r.done = (tok == eos) or len(r.produced) >= r.max_new
                n_decode += 1
                if r.adapter_slot:
                    lora_toks += 1
            else:
                del r.pending[:take]
                r.pos += take
                n_prefill += take
                if r.adapter_slot:
                    lora_toks += take
                if not r.pending:
                    # Join complete: the chunk that finished the prompt
                    # also sampled the row's first token (the prologue's
                    # first_np, one dispatch earlier than it ever was).
                    r.produced = [tok]
                    r.last = tok
                    r.valid = r.pos
                    # The join token counts against the row's budget: a
                    # max_new_tokens=1 row (journal replay) is DONE here
                    # — leaving it live would hand the spec segment a
                    # zero-room row next tick.
                    r.done = (tok == eos) or len(r.produced) >= r.max_new
                    if (req is not None and req.first_token_at is None
                            and all(not rr.pending for rr in req.rows)):
                        req.first_token_at = now
                        self._event(
                            "join_complete", session=req.session,
                            ttft_s=round(now - req.enqueued, 3))

        # Provenance + attribution: the mixed dispatch splits its wall
        # by per-row token counts — decode rows' share lands in their
        # requests' decode_seconds, chunk tokens in prefill_seconds.
        engine.note_lora_tokens(lora_toks)
        self.ragged_segments += 1
        telemetry.inc("roundtable_sched_ragged_segments_total",
                      engine=self._tname)
        self._note_segment_tokens(n_prefill, n_decode)
        self._note_ragged_fill(seg, shape, want, n_prefill + n_decode)
        self._end_segment(seg, 1, n_decode, n_prefill,
                          read_to=tuple(r.pos if kind != "decode"
                                        else r.valid
                                        for kind, r, _take in rows_in),
                          ragged=batch)
        occ = len(seqs)
        self.max_occupancy = max(self.max_occupancy, occ)
        with self._cv:
            self._occupancy.append(occ)
        telemetry.set_gauge("roundtable_sched_occupancy", occ,
                            engine=self._tname)
        _note_rows(occ)
        total = max(n_prefill + n_decode, 1)
        sessions = len(reqs)
        for kind, r, take in rows_in:
            req = self._row_req.get(id(r))
            if req is None:
                continue
            share = wall * take / total
            if kind == "decode":
                req.stats.decode_seconds += share
            else:
                req.stats.prefill_seconds += share
        for req in reqs:
            req.seg_count += 1
            req.occ_sum += occ
            req.occ_max = max(req.occ_max, occ)
            req.sess_max = max(req.sess_max, sessions)
        perf = getattr(engine, "perf", None)
        if perf is not None:
            for req in reqs:
                perf.publish_session_kv(
                    req.session, sum(r.valid for r in req.rows))

    def _handle_ragged_failure(self, live: list[_Row],
                               filling: list[_Row],
                               err: BaseException) -> None:
        """A ragged mixed dispatch failed. Donation-death first (shared
        pools — everyone fails into their adapter ladders); otherwise
        PREEMPT: requests with rows mid-prefill fail alone (their pages
        hold a half-written chunk; the adapter ladder re-prefills from
        the prompt), while decode-only sessions re-dispatch through the
        compiled segment path from intact host+KV state. Loop-thread
        only (single-writer counter bumps need no cv)."""
        self._clock.drain()     # nobody reads the failed dispatch
        if self._supervisor_intervened(err):
            return
        if self._after_engine_failure(err):
            return
        self._bump("preemptions")
        self._event("preempt_isolate", error=str(err)[:200], ragged=True,
                    sessions=[req.session
                              for req in self._reqs_of(live + filling)])
        for req in self._reqs_of(live + filling):
            if req not in self._active_reqs:
                continue
            if any(r.pending for r in req.rows):
                self._fail_request(req, err)
                continue
            mine = [r for r in live if r in req.rows and not r.done]
            if not mine:
                continue
            t0 = time.monotonic()
            try:
                self._dispatch_rows(mine)
            except Exception as e:  # noqa: BLE001 — per-session contain
                if self._after_engine_failure(e):
                    return
                self._fail_request(req, e)
                continue
            req.stats.decode_seconds += time.monotonic() - t0

    # --- the speculative verify segment (ISSUE 9) ---

    def _spec_drafts(self, live: list[_Row],
                     probe: bool = False, dispatch=None,
                     read=None) -> Optional[dict]:
        """Per-row draft proposals for one verify dispatch (ISSUE 13:
        drafter-aware): each spec-enabled row that `should_draft` —
        unthrottled, or throttled-but-re-probing — proposes up to
        `branch` candidate PATHS (chain drafters: one), capped by its
        remaining token budget (a verify commits up to depth+1 tokens,
        so a row with <= 1 remaining never drafts). The ngram drafter
        proposes host-side per row; model/LoRA drafters batch all rows
        through the engine's DeviceDrafter (ordinary ragged dispatches
        against the shadow draft slots). Returns {id(row): [path,...]}
        or None when NO row drafts — the tick then serves the plain
        pipelined segments, which is exactly the 1-token-decode
        fallback the adaptive throttle promises (a non-accepting batch
        must never pay more dispatches than plain decode). Loop-thread
        only (single-writer counter bumps need no cv)."""
        engine = self.engine
        if (not getattr(engine, "spec_decode", False)
                or not engine.ragged_enabled):
            return None
        if RAGGED_BLOCK_Q * len(live) > engine.ragged_tokens:
            return None  # flat buffer cannot carry every live row
        tree = getattr(engine, "spec_tree", None)
        branch = engine.spec_branch if tree else 1
        depth = min(tree["depth"], engine.spec_max_draft) if tree \
            else engine.spec_max_draft
        dd = getattr(engine, "spec_device_drafter", None)

        pooled = None   # the batch throttle's answer, asked once

        def cap_of(r: _Row) -> int:
            nonlocal pooled
            if r.spec is None:
                return 0
            if r.spec.judged():
                ok = r.spec.should_draft(len(r.produced))
            else:
                # No verdict of its own yet: the batch's (ISSUE 43).
                # _may_speculate's probe asks with a segment in flight
                # whose steps the batch's clock has not been given.
                if pooled is None:
                    pooled = engine.spec_batch.asks(
                        self._clock.tick,
                        min(x.max_new for x in live if x.spec is not None
                            and not x.spec.judged()),
                        ahead=DECODE_SEGMENT if probe else 0)
                ok = pooled
            if not ok:
                return 0
            return min(depth, r.max_new - len(r.produced) - 1)

        if dd is not None:
            from .spec_decode import DraftUnavailable
            if probe:
                # Eligibility alone answers _may_speculate — a device
                # drafter always proposes >= 1 token for an eligible
                # row, and probing must cost neither draft dispatches
                # nor the O(transcript) context copies below.
                return ({"__probe__": True}
                        if any(cap_of(r) >= 1 for r in live) else None)
            rows = []
            for r in live:
                c = cap_of(r)
                if c >= 1:
                    # Incremental context cache: extend with the newly
                    # committed tokens only — never re-concatenate the
                    # whole transcript per tick.
                    cc = r.spec.ctx
                    if cc is None:
                        cc = r.spec.ctx = list(r.tokens)
                    need = len(r.tokens) + len(r.produced)
                    if len(cc) < need:
                        cc.extend(r.produced[len(cc) - len(r.tokens):])
                    rows.append((id(r), r.name, cc, c, branch))
            if not rows:
                return None
            pinned = tuple(r.name for r in self._active)
            try:
                proposals = dd.propose(engine, rows, pinned=pinned,
                                       dispatch=dispatch, read=read)
            except DraftUnavailable as e:
                # Slot/page pressure ONLY (the drafter's own benign
                # capacity signal): the batch is too big to shadow —
                # serve plain decode this tick (never evict live rows
                # to draft for them) with the reason on record. Device
                # dispatch failures propagate to _run_spec_segment's
                # ragged failure ladder (donation-death check included).
                self._event("spec_draft_unavailable",
                            error=str(e)[:160])
                return None
            drafts = {id(r): proposals.get(id(r), []) for r in live}
            return drafts if any(drafts.values()) else None

        drafts: dict[int, list[list[int]]] = {}
        any_draft = False
        for r in live:
            paths: list[list[int]] = []
            cap = cap_of(r)
            if cap >= 1:
                if r.spec.drafter is None:
                    # No segment in flight got to this row's index
                    # (_index_in_flight), or the engine was hot-swapped
                    # from a device drafter to ngram: build it now.
                    from .spec_decode import NGramDrafter
                    r.spec.drafter = NGramDrafter(list(r.tokens))
                    self._bump("indexed_at_draft")
                    r.spec.kind = "ngram"
                r.spec.drafter.sync_parts(r.tokens, r.produced)
                if branch > 1:
                    paths = r.spec.drafter.draft_paths(cap, branch)
                else:
                    # Chain config keeps the PR-9 seam exactly
                    # (draft_paths(n, 1)[0] is byte-identical, but
                    # draft() is the method fakes/benches intercept).
                    d = r.spec.drafter.draft(cap)
                    paths = [d] if d else []
                if not paths and not probe:
                    # The probe reached the drafter and it proposed
                    # NOTHING (context not draftable): the probe is
                    # resolved FAILED — wait a whole interval again
                    # instead of re-drafting every tick (no-op for
                    # unthrottled rows).
                    r.spec.probe_failed(len(r.produced))
            drafts[id(r)] = paths
            if paths:
                any_draft = True
                if probe:
                    # The _may_speculate caller only asks WHETHER a
                    # verify tick exists — don't compute the rest of
                    # the batch's proposals just to discard them (the
                    # real segment recomputes from fresh host state
                    # next tick anyway).
                    return drafts
        return drafts if any_draft else None

    def _run_spec_segment(self, live: list[_Row]) -> bool:
        """One speculative verify dispatch over the live rows (ISSUE 9
        tentpole, ISSUE 13 tree generalization): every speculating row
        packs its candidate paths as short multi-token runs of the PR-8
        flat buffer (throttled / draftless rows ride as plain 1-token
        runs — mixed chain/tree/no-spec widths are VALUES, not shapes),
        forward_ragged scores every draft position in one forward via
        the static score_width gather, and the host walks the accepted
        chain/tree path and commits it plus the correction/bonus token.

        Tree rows: path 0 (the main chain) writes through the row's
        REAL page table exactly like PR-9; each extra root branch
        becomes one more sequence whose table swaps the touched pages
        for pages LOANED from the free list (take_free_pages — never
        evicting resident state; a short free list degrades the row
        back to chain), with the partially-committed frontier page
        pre-COW'd in-dispatch (build_ragged_batch copy_pairs) so every
        path's causal reads see the committed cells. When the accepted
        walk ends on a non-trunk path, its loaned pages ARE the
        committed K/V — swap_in_page adopts them into the row's table
        and the trunk's rejected bytes go back to the free list; every
        other loan returns untouched. PagedKVCache.commit still
        publishes only literally-committed tokens, so the prefix cache
        can never attach a rejected branch.

        Greedy rows are byte-identical to 1-token decode by the argmax
        walk rule; sampled rows follow exact per-edge rejection
        sampling (engine/spec_decode docstring). Returns False WITHOUT
        dispatching when no row drafts; a dispatch failure is handled
        exactly like a ragged decode failure (drafts discarded, loans
        returned, the preempt-isolate ladder re-dispatches from intact
        host state)."""
        engine = self.engine
        self._clock.mark("build")
        reqs = self._reqs_of(live)
        remaining = min((req.turn_budget.remaining() for req in reqs),
                        default=float("inf"))
        seg_budget = deadlines.Budget.root(
            None if remaining == float("inf") else remaining,
            rung="decode")
        deadline = min((req.deadline for req in reqs),
                       default=float("inf"))

        def draft_dispatch(b):
            # Draft dispatches ride the SAME watchdog/retry/budget
            # seams the verify dispatch uses — a hang mid-propose must
            # hit the deadline ladder, not block the scheduler thread.
            h = run_dispatch(lambda: engine._ragged_dispatch(b),
                             engine.retry, deadline, budget=seg_budget)
            self._clock.feed()
            return h

        def draft_read(h):
            if isinstance(h, tuple):
                out = host_sync(
                    lambda: tuple(np.asarray(x) for x in h),
                    seg_budget, "decode")
            else:
                out = host_sync(lambda: np.asarray(h), seg_budget,
                                "decode")
            self._clock.drain()
            return out

        try:
            drafts_of = self._spec_drafts(live, dispatch=draft_dispatch,
                                          read=draft_read)
        except Exception as e:  # noqa: BLE001 — preempt-isolate ladder
            # A DEVICE failure during drafting is indistinguishable
            # from a decode failure (draft dispatches donate the same
            # pools): the ragged failure path's donation-death check +
            # per-session re-dispatch applies verbatim. Benign capacity
            # pressure (DraftUnavailable) was already absorbed inside
            # _spec_drafts.
            self._handle_ragged_failure(live, [], e)
            return True
        if drafts_of is None:
            return False

        pack = self._open_pack()
        from .serving_loop import ragged_pick_shape
        kv = engine.kv
        ps = kv.page_size
        # Pack main runs first (chain behavior unchanged), then extra
        # tree paths while the flat buffer, the static copy-slot block
        # and the free list allow — degradation is per-path and the
        # batch stays pure values.
        seqs: list[RaggedSeq] = []
        entries: list[dict] = []
        copy_pairs: list[tuple[int, int]] = []
        blocks_budget = engine.ragged_tokens // RAGGED_BLOCK_Q
        copy_budget = engine.spec_copy_slots
        for r in live:
            paths = drafts_of.get(id(r)) or []
            main = list(paths[0]) if paths else []
            e = {"row": r, "used": ([main] if paths else []),
                 "rows_idx": [len(seqs)], "loans": []}
            seqs.append(RaggedSeq(
                [r.last] + main, r.valid, kv.table_for([r.name])[0],
                temperature=r.sampling.temperature,
                top_k=r.sampling.top_k, top_p=r.sampling.top_p,
                n_scores=len(main) + 1, adapter=r.adapter_slot))
            entries.append(e)
        for e in entries:
            r = e["row"]
            paths = drafts_of.get(id(r)) or []
            if len(paths) <= 1:
                continue
            state = kv.acquire(r.name)
            base_table = kv.table_for([r.name])[0]
            for p in paths[1:]:
                if len(seqs) >= blocks_budget or copy_budget <= 0:
                    break
                lo = r.valid // ps
                hi = (r.valid + len(p)) // ps
                loan = kv.take_free_pages(hi - lo + 1,
                                          replica=state.replica)
                if loan is None:
                    break  # free list short: this row degrades to chain
                ptable = np.array(base_table, copy=True)
                for k, j in enumerate(range(lo, hi + 1)):
                    ptable[j] = loan[k]
                # Only the frontier page holds committed cells the
                # path's causal reads need — deeper touched pages start
                # past `valid` and are written before they are read.
                copy_pairs.append((int(base_table[lo]), loan[0]))
                copy_budget -= 1
                e["rows_idx"].append(len(seqs))
                e["loans"].append((lo, loan))
                e["used"].append(list(p))
                seqs.append(RaggedSeq(
                    [r.last] + list(p), r.valid, ptable,
                    temperature=r.sampling.temperature,
                    top_k=r.sampling.top_k, top_p=r.sampling.top_p,
                    n_scores=len(p) + 1, adapter=r.adapter_slot))

        def return_all_loans():
            for e in entries:
                for _lo, loan in e["loans"]:
                    kv.give_back_pages(loan)

        want = RAGGED_BLOCK_Q * len(seqs)
        shape = ragged_pick_shape(engine.ragged_shapes,
                                  min(want, engine.ragged_tokens))
        batch = build_ragged_batch(
            seqs, t_budget=shape, s_max=engine.spec_s_max,
            pages_per_seq=kv.pages_per_seq,
            scratch_page=kv.scratch_page(0),
            pad_id=engine.tokenizer.pad_id,
            page_size=ps,
            score_width=engine.spec_max_draft + 1,
            copy_pairs=copy_pairs,
            copy_slots=engine.spec_copy_slots)

        pack.leave()
        t0 = time.monotonic()
        seg = self._open_segment("spec", len(seqs), shape)
        if pack is not telemetry.NULL_SPAN:
            self._end_pack(pack, seg, "spec", len(seqs),
                           sum(len(q.tokens) for q in seqs))
        try:
            handles = run_dispatch(
                lambda: engine._ragged_dispatch(batch),
                engine.retry, deadline, budget=seg_budget)
            fed = self._clock.feed()
            self._index_in_flight(handles)
            nxt = host_sync(lambda: np.asarray(handles), seg_budget,
                            "decode")
            self._clock.drain(fed)
        except Exception as e:  # noqa: BLE001 — preempt-isolate ladder
            # Indistinguishable from a decode failure: host state is
            # untouched (the drafts are discarded with the dispatch and
            # the loaned pages return to the free list), so the ragged
            # failure path's donation-death check + per-session
            # re-dispatch applies verbatim.
            seg.end(f"error:{type(e).__name__}")
            return_all_loans()
            self._handle_ragged_failure(live, [], e)
            return True
        seg.leave()
        self._clock.mark("accept")
        wall = time.monotonic() - t0

        eos = engine.tokenizer.eos_id
        from .spec_decode import (accept_prefix, accept_tree,
                                  note_tree_row)
        n_emit = 0
        lora_toks = 0
        drafted_tot = 0
        accepted_tot = 0
        pooled = [0, 0]   # drafted, accepted of the unjudged rows
        tree_nodes_tot = 0
        tree_rows_tot = 0
        emits: dict[int, tuple[_Request, int]] = {}
        for e in entries:
            r = e["row"]
            used = e["used"]
            if len(used) <= 1:
                d = used[0] if used else []
                props = [int(x)
                         for x in nxt[e["rows_idx"][0], :len(d) + 1]]
                emit, a = accept_prefix(d, props)
                winner = 0
                drafted_row = len(d)
            else:
                props_list = [
                    [int(x) for x in nxt[si, :len(used[k]) + 1]]
                    for k, si in enumerate(e["rows_idx"])]
                emit, a, winner = accept_tree(used, props_list)
                drafted_row = sum(len(p) for p in used)
            # EOS inside an accepted prefix truncates exactly as
            # eos_trim does: tokens past the eos are never committed
            # (plain decode would never have produced them).
            if eos in emit:
                emit = emit[:emit.index(eos) + 1]
            room = r.max_new - len(r.produced)
            if len(emit) > room:
                emit = emit[:room]
            r.produced.extend(emit)
            if emit:
                r.last = emit[-1]
            r.valid += len(emit)
            r.done = (r.last == eos) or len(r.produced) >= r.max_new
            if r.adapter_slot:
                lora_toks += len(emit)
            # Accepted-for-accounting = drafts actually COMMITTED:
            # eos/budget truncation can drop matched drafts, and every
            # acceptance metric must equal served work (a fully-matched
            # [A, eos, B, C] draft commits 2 tokens, not 4). min(a,
            # len(emit)) also covers the eos-was-a-draft case, where
            # every emitted token is a matched draft and none is the
            # free correction — the rule holds for tree EDGES verbatim
            # (ISSUE 13 satellite: EOS inside an accepted path counts
            # only committed tokens).
            acc = min(a, len(emit))
            if e["loans"]:
                # Loan settlement: the winner path's pages covering the
                # committed span adopt into the row's table (their
                # cells hold the accepted K/V, pre-COW'd + written
                # in-dispatch); everything else returns to the free
                # list. Winner 0 is the trunk — its writes went through
                # the real table, so every loan returns.
                for m, (lo, loan) in enumerate(e["loans"]):
                    if m == winner - 1:
                        keep_hi = (r.valid - 1) // ps
                        for k, j in enumerate(range(lo, lo + len(loan))):
                            if j <= keep_hi:
                                kv.swap_in_page(r.name, j, loan[k])
                            else:
                                kv.give_back_pages([loan[k]])
                    else:
                        kv.give_back_pages(loan)
            if len(used) > 1:
                tree_nodes_tot += drafted_row
                tree_rows_tot += 1
                note_tree_row(drafted_row, acc)
            req = self._row_req.get(id(r))
            if req is not None:
                prev = emits.get(id(req))
                emits[id(req)] = (req,
                                  (prev[1] if prev else 0) + len(emit))
                if drafted_row:
                    req.spec_drafted += drafted_row
                    req.spec_accepted += acc
            n_emit += len(emit)
            if drafted_row and r.spec is not None:
                drafted_tot += drafted_row
                accepted_tot += acc
                if not r.spec.judged():
                    # It drafted on the batch throttle's word, so its
                    # outcome is that throttle's evidence (ISSUE 43).
                    pooled[0] += drafted_row
                    pooled[1] += acc
                tripped = r.spec.note(drafted_row, acc)
                if r.spec.disabled:
                    # Throttled (now or still): restart the re-probe
                    # interval from the row's current committed length
                    # (ISSUE 13 hysteresis satellite).
                    r.spec.mark_idle(len(r.produced))
                # Gauge AFTER note: the window now includes this
                # dispatch, so the first drafted dispatch reports its
                # real rate instead of a false 0.0 (and later values
                # never lag a dispatch behind).
                telemetry.set_gauge(
                    "roundtable_spec_row_acceptance_rate",
                    round(r.spec.rate(), 4),
                    engine=self._tname, row=r.name)
                if tripped:
                    # Adaptive throttle tripped: this row decodes
                    # 1-token (with periodic re-probes) from here on —
                    # one flight event, the ISSUE 9 telemetry
                    # satellite.
                    engine.note_spec_throttle()
                    telemetry.recorder().record(
                        "spec_throttle", engine=self._tname,
                        session=req.session if req else "",
                        row=r.name, rate=round(r.spec.rate(), 3))
                    self._event("spec_throttle", row=r.name,
                                rate=round(r.spec.rate(), 3))
        engine.note_lora_tokens(lora_toks)
        engine.note_spec_dispatch(drafted_tot, accepted_tot,
                                  rows=len(live),
                                  tree_nodes=tree_nodes_tot,
                                  tree_rows=tree_rows_tot)
        shared = engine.spec_batch
        probe = shared.disabled and pooled[0] > 0
        if shared.note(*pooled):
            # The rows with no verdict of their own are throttled
            # together: one flight event, as for a row's own trip.
            telemetry.recorder().record(
                "spec_throttle", engine=self._tname, session="",
                row="(batch)", rate=round(shared.rate(), 3))
            self._event("spec_throttle", row="(batch)",
                        rate=round(shared.rate(), 3))

        self.spec_segments += 1
        telemetry.inc("roundtable_sched_spec_segments_total",
                      engine=self._tname)
        self._note_segment_tokens(0, n_emit)
        self._end_segment(seg, 1, n_emit, 0, drafted_tot, accepted_tot,
                          ragged=batch, probes=int(probe),
                          probes_accepted_none=int(
                              probe and not pooled[1]))
        occ = len(seqs)
        self.max_occupancy = max(self.max_occupancy, occ)
        with self._cv:
            self._occupancy.append(occ)
        telemetry.set_gauge("roundtable_sched_occupancy", occ,
                            engine=self._tname)
        _note_rows(occ)
        sessions = len(reqs)
        for req, n in emits.values():
            req.stats.decode_seconds += wall * n / max(n_emit, 1)
        for req in reqs:
            req.seg_count += 1
            req.occ_sum += occ
            req.occ_max = max(req.occ_max, occ)
            req.sess_max = max(req.sess_max, sessions)
        perf = getattr(engine, "perf", None)
        if perf is not None:
            for req in reqs:
                perf.publish_session_kv(
                    req.session, sum(r.valid for r in req.rows))
        return True

    def _may_speculate(self, ctx: dict) -> bool:
        """Queue the next segment before reading this one ONLY when the
        composition is certain to survive it: no queued session (a join
        must not wait behind a speculative segment), no request whose
        rows are all done (retirement resolves a submitter — never
        delay it), work plausibly remaining, nothing cancelled, and the
        deadline not passed (decode_segments' own speculation rules)."""
        if self._stop or deadlines.DRAINING:
            return False
        if any(r.pending for r in self._active):
            # Ragged fills are waiting (overflow fallback segment, or a
            # blocked laggard about to unblock) — a speculative segment
            # would starve their chunks for another whole segment.
            return False
        if ctx["budgets_max"] <= DECODE_SEGMENT:
            return False  # this segment may finish everything
        if time.monotonic() >= ctx["deadline"]:
            return False
        with self._cv:
            if self._queue:
                return False
        if self._spec_drafts([r for r in ctx["rows"] if not r.done],
                             probe=True) is not None:
            # A verify tick is available (ISSUE 9): pipelining another
            # whole 64-token segment would decode past it at 1
            # token/forward — exit the mini-loop so _tick runs the
            # speculative phase at the next boundary. Probe mode: this
            # check runs per mini-loop iteration AFTER the cheap exits
            # and stops at the first draftable row.
            return False
        for req in ctx["reqs"]:
            if req not in self._active_reqs or req.abandoned:
                return False
            if req.rows and all(r.done for r in req.rows):
                return False
            if req.turn_budget.token.cancelled or req.turn_budget.expired:
                return False
        return True

    def _reqs_of(self, rows: list[_Row]) -> list[_Request]:
        seen: dict[int, _Request] = {}
        for r in rows:
            req = self._row_req.get(id(r))
            if req is not None:
                seen.setdefault(id(req), req)
        return list(seen.values())

    def _account_segment(self, alive: list[_Row]) -> dict:
        """Occupancy provenance for one consumed segment; returns the
        per-request live-row counts ({id: (req, n)}) the wall
        attribution reuses — one pass over the rows, not a rescan per
        row. Loop-thread only (single-writer counter bumps need no
        cv)."""
        counts: dict[int, tuple[_Request, int]] = {}
        for r in alive:
            req = self._row_req.get(id(r))
            if req is None:
                continue
            prev = counts.get(id(req))
            counts[id(req)] = (req, (prev[1] + 1) if prev else 1)
        occ = len(alive)
        sessions = len(counts)
        self._bump("segments")
        self.max_occupancy = max(self.max_occupancy, occ)
        with self._cv:
            self._occupancy.append(occ)
        telemetry.set_gauge("roundtable_sched_occupancy", occ,
                            engine=self._tname)
        _note_rows(occ)
        perf = getattr(self.engine, "perf", None)
        for req, _n in counts.values():
            req.seg_count += 1
            req.occ_sum += occ
            req.occ_max = max(req.occ_max, occ)
            req.sess_max = max(req.sess_max, sessions)
            if perf is not None:
                # Per-session KV-footprint series (the memory ledger's
                # session dimension): cached tokens across the
                # session's live rows, priced at KV bytes/token.
                perf.publish_session_kv(
                    req.session, sum(r.valid for r in req.rows))
        return counts

    def _attribute_wall(self, counts: dict, wall: float) -> None:
        """Attribute a segment's wall to its sessions by live-row share —
        sums over requests equal the real wall, so aggregate tok/s stays
        honest under co-scheduling."""
        total = sum(n for _req, n in counts.values())
        for req, n in counts.values():
            req.stats.decode_seconds += wall * n / max(total, 1)

    def _row_bucket(self, n: int) -> int:
        """Decode batch sizes round up to powers of two (capped at
        max_rows) so the set of compiled decode programs is
        {1, 2, 4, ..., max_rows} instead of one per exact live-row
        count — a retire/join that changes occupancy inside a bucket
        compiles nothing mid-serve (the ISSUE 4 fixed-size-bucketed
        batch with an active-row mask)."""
        return min(pow2_bucket(n), self.max_rows)

    def _dispatch_rows(self, rows: list[_Row]) -> None:
        """One unpipelined DECODE_SEGMENT over `rows` — the
        fault-isolation re-dispatch path (_handle_segment_failure runs
        each session's rows alone through this)."""
        ctx = self._build_batch(rows)
        self._read_segment(ctx, self._dispatch(ctx))

    def _build_batch(self, rows: list[_Row]) -> dict:
        """Device arrays for one DECODE_SEGMENT over `rows`.

        The batch pads to _row_bucket with MASKED pad rows (done from
        step 0, zero budget) whose whole table points at the scratch
        page (identical bytes from every pad row, so the duplicate-index
        scatter is deterministic). Under data>1 pool-direct
        the ReplicaGroupPlan already dictates the padded shape, so
        bucketing is skipped there."""
        engine = self.engine
        names = [r.name for r in rows]
        eos = engine.tokenizer.eos_id
        reqs = self._reqs_of(rows)
        remaining = min((req.turn_budget.remaining() for req in reqs),
                        default=float("inf"))
        seg_budget = deadlines.Budget.root(
            None if remaining == float("inf") else remaining,
            rung="decode")
        deadline = min((req.deadline for req in reqs),
                       default=float("inf"))

        last = np.asarray([r.last for r in rows], np.int32)
        valid = np.asarray([r.valid for r in rows], np.int32)
        done0 = np.zeros(len(rows), bool)
        budgets = np.asarray(
            [max(r.max_new - len(r.produced), 0) for r in rows], np.int32)
        temps_l = [r.sampling.temperature for r in rows]
        top_ks_l = [r.sampling.top_k for r in rows]
        top_ps_l = [r.sampling.top_p for r in rows]
        greedy = all(t <= 0.0 for t in temps_l)

        plan = None
        pad = 0
        tables_np = engine.kv.table_for(names)
        if engine.paged_direct and engine._paged_replicas > 1:
            # bucket_group: the plan's padded shape must stay on the
            # {R*1, R*2, R*4, ...} grid as occupancy drifts, or
            # every retire/join would compile a fresh decode program
            # mid-serve on exactly the multi-replica engines where
            # that stall hurts most.
            plan = ReplicaGroupPlan(
                [engine.kv.replica_of(n) for n in names],
                engine._paged_replicas, bucket_group=True)
            tables_np = plan.pad_table(tables_np,
                                       engine.kv.scratch_page)
        else:
            pad = self._row_bucket(len(rows)) - len(rows)
            if pad:
                scratch = np.full(
                    (pad, tables_np.shape[1]),
                    engine.kv.scratch_page(0), tables_np.dtype)
                tables_np = np.concatenate([tables_np, scratch])
        if pad:
            last = np.concatenate([last, np.full(pad, eos, np.int32)])
            valid = np.concatenate([valid, np.ones(pad, np.int32)])
            done0 = np.concatenate([done0, np.ones(pad, bool)])
            budgets = np.concatenate([budgets, np.zeros(pad, np.int32)])
            temps_l += [1.0] * pad
            top_ks_l += [0] * pad
            top_ps_l += [1.0] * pad
        temps, top_ks, top_ps = sampling_arrays(
            [SamplingParams(temperature=t, top_k=k, top_p=p)
             for t, k, p in zip(temps_l, top_ks_l, top_ps_l)])

        budgets_max = int(budgets.max()) if len(budgets) else 0
        if plan is not None:
            last = plan.scatter_rows(last, np.int32(eos))
            valid = plan.scatter_rows(valid, 1)
            done0 = plan.scatter_rows(done0, True)
            budgets = plan.scatter_rows(budgets, 0)
            temps = plan.scatter_rows(temps, 1.0)
            top_ks = plan.scatter_rows(top_ks, 0)
            top_ps = plan.scatter_rows(top_ps, 1.0)
        # Everything the segment's program takes from the host, under
        # dispatch_pack.decode_layout's names: it travels as one buffer
        # (engine._decode_dispatch_paged packs it), so nothing here
        # touches the device.
        fields = {"tables": tables_np, "last": last, "valid": valid,
                  "done": done0, "budgets": budgets, "temps": temps,
                  "top_ks": top_ks, "top_ps": top_ps,
                  "budget": DECODE_SEGMENT}
        if getattr(engine, "lora", None) is not None:
            # Per-row adapter slots (ISSUE 10): pad rows ride the base
            # (zero) adapter — their delta is exactly zero and their
            # outputs are masked anyway. A value, so mixed-adapter
            # recomposition compiles nothing.
            slots = [r.adapter_slot for r in rows]
            fields["lora_ids"] = engine._lora_ids(
                plan.scatter_list(slots, 0) if plan is not None
                else slots + [0] * pad)
        return {
            "rows": rows, "reqs": reqs, "plan": plan, "fields": fields,
            # What the segment before handed on, on the device (a
            # pipelined segment: _advance); None: the buffer's own.
            "carry": None, "size": len(last), "greedy": greedy,
            "seg_budget": seg_budget, "deadline": deadline,
            "budgets_max": budgets_max, "names": names,
        }

    def _dispatch(self, ctx: dict):
        """Dispatch one segment for `ctx` through the engine's shared
        decode seam (_decode_dispatch_paged — same degrade rung
        + commit_guard as generate_batch) and the run_dispatch
        retry/watchdog seam. Returns DEVICE handles; the host read
        happens in _read_segment, possibly after the next segment is
        already queued."""
        engine = self.engine

        def dispatch():
            return engine._decode_dispatch_paged(
                ctx["fields"], ctx["carry"], greedy=ctx["greedy"],
                names=ctx["names"])

        handles = run_dispatch(dispatch, engine.retry, ctx["deadline"],
                               budget=ctx["seg_budget"])
        # The device holds this segment until _sync_segment has read it
        # (the loop clock's feed bit; a pipelined next segment is a
        # ticket of its own).
        ctx["fed"] = self._clock.feed()
        return handles

    def _advance(self, ctx: dict, handles) -> dict:
        """The next segment's ctx from this segment's DEVICE outputs
        (decode_segments' pipelining carry) — no host sync and no
        program of its own: last/valid/done carry, and the per-row
        budgets less the steps actually taken are an output of the
        segment's program too."""
        nxt = dict(ctx)
        nxt["carry"] = tuple(handles[2:])
        # Host-side upper-bound estimate for _may_speculate (the device
        # value is not worth a sync): each segment consumes at most
        # DECODE_SEGMENT of every row's budget.
        nxt["budgets_max"] = ctx["budgets_max"] - DECODE_SEGMENT
        return nxt

    def _read_segment(self, ctx: dict, handles) -> int:
        """Host-read one segment's results and fold them into the rows'
        host state. Returns the steps the segment actually took (the
        roofline sample's token count)."""
        return self._fold_segment(ctx, self._sync_segment(ctx, handles))

    def _sync_segment(self, ctx: dict, handles) -> tuple:
        """The blocking half of a segment's read, through the watchdog
        seam — this is where a wedged program freezes the host, and
        where the loop clock reads `sync`."""
        out, steps, l2, v2, d2, _left = handles

        def read():
            n = int(steps)  # forces completion of the segment
            return (n, np.asarray(out)[:, :n], np.asarray(l2),
                    np.asarray(v2), np.asarray(d2))

        arrays = host_sync(read, ctx["seg_budget"], "decode")
        self._clock.drain(ctx["fed"])
        return arrays

    def _fold_segment(self, ctx: dict, arrays: tuple) -> int:
        """The host half: fold what _sync_segment read into the rows."""
        n, out_np, last_np, valid_np, done_np = arrays
        plan = ctx["plan"]
        if plan is not None:
            out_np = out_np[plan.pos]
            last_np = last_np[plan.pos]
            valid_np = valid_np[plan.pos]
            done_np = done_np[plan.pos]
        lora_toks = 0
        eos = self.engine.tokenizer.eos_id
        for i, r in enumerate(ctx["rows"]):
            if r.done:
                continue  # masked rows emit eos filler — not output
            row = [int(x) for x in out_np[i]]
            r.produced.extend(row)
            r.last = int(last_np[i])
            r.valid = int(valid_np[i])
            r.done = bool(done_np[i]) or len(r.produced) >= r.max_new
            if r.adapter_slot:
                # Count tokens up to (and including) the row's eos —
                # post-eos filler is not served work, and the direct
                # generate path counts eos-trimmed exactly; the two
                # definitions of apply_tokens must agree.
                lora_toks += (row.index(eos) + 1 if eos in row
                              else len(row))
        self.engine.note_lora_tokens(lora_toks)
        return n

    # --- failure containment ---

    def _handle_segment_failure(self, live: list[_Row],
                                err: BaseException) -> None:
        """The shared decode dispatch failed. If donation consumed the
        (shared!) KV buffers, every session's cache is gone — fail them
        all into their adapters' revive/serial-retry ladders. Otherwise
        PREEMPT the batch into per-session dispatches: the session the
        fault follows fails alone; everyone else's rows re-run their
        segment from intact host+KV state, byte-identical. Loop-thread
        only (single-writer counter bumps need no cv)."""
        self._clock.drain()     # nobody reads the failed dispatch
        if self._supervisor_intervened(err):
            return
        if self._after_engine_failure(err):
            return
        self._bump("preemptions")
        self._event("preempt_isolate", error=str(err)[:200],
                    sessions=[req.session for req in self._reqs_of(live)])
        for req in self._reqs_of(live):
            mine = [r for r in live if r in req.rows]
            t0 = time.monotonic()
            try:
                self._dispatch_rows(mine)
            except Exception as e:  # noqa: BLE001 — per-session contain
                if self._after_engine_failure(e):
                    return
                self._fail_request(req, e)
                continue
            req.stats.decode_seconds += time.monotonic() - t0

    def _supervisor_intervened(self, err: BaseException) -> bool:
        """Engine-fatal triage BEFORE the dispatch ladder (ISSUE 12):
        device_lost failures, repeated hangs past the ladder, and
        already-dead engines route to the EngineSupervisor, which tears
        the engine down, rebuilds it, and restores the evacuated
        sessions — all inline on this (the loop) thread. Returns True
        when the supervisor took over (the batch is gone: actives were
        failed into their adapter ladders as part of the quiesce);
        False lets preempt-isolate / revive handle it as before."""
        try:
            from .supervisor import supervisor
            return supervisor().handle_dispatch_failure(self, err)
        except Exception as e:  # noqa: BLE001 — triage must not mask err
            self._event("supervisor_error", error=str(e)[:200])
            return False

    def _after_engine_failure(self, err: BaseException) -> bool:
        """Donation-death check after ANY engine dispatch failure: a
        revive means every slot's bytes are gone — no per-session state
        survives, so every active request fails (their adapter ladders
        rebuild from prompts). Returns True when that happened."""
        try:
            revived = self.engine.revive_kv_if_dead()
        except Exception:  # noqa: BLE001 — the original error wins
            revived = False
        if not revived:
            return False
        self._event("revive_fail_all", error=str(err)[:200])
        for req in list(self._active_reqs):
            self._fail_request(req, err, release=False)
        return True

    def _release_adapters(self, req: _Request) -> None:
        store = getattr(self.engine, "lora", None)
        if store is not None and req.adapters_held:
            req.adapters_held = False
            store.release(req.adapters or [])

    def _fail_request(self, req: _Request, err: BaseException,
                      release: bool = True) -> None:
        """Fail one active request into its submitter. Loop-thread
        only — request state is single-writer (external threads go
        through force_fail_active's mailbox), so counter bumps here
        need no cv."""
        self._release_adapters(req)
        if release:
            for r in req.rows:
                try:
                    self.engine.kv.release(r.name)
                except Exception:  # noqa: BLE001 — the error wins
                    pass
        if req.on_commit is not None:
            from ..core.errors import classify_error
            self._stream_notify(req, {
                "type": "failed", "error": str(err)[:200],
                "kind": classify_error(err)})
        self._drop_request(req)
        self._last_active[req.session] = time.monotonic()
        req.error = err
        self._bump("failed")
        perf = getattr(self.engine, "perf", None)
        if perf is not None:
            perf.publish_session_kv(req.session, 0)
        if req.tele is not None:
            req.tele.end(status=f"error:{type(err).__name__}")
            req.tele = None
        self._event("fail", session=req.session,
                    error=str(err)[:200])
        req.event.set()

    def _drop_request(self, req: _Request) -> None:
        if req in self._active_reqs:
            self._active_reqs.remove(req)
        for plan in req.share_plans:
            # Laggards that never joined: nothing waits for the state
            # their leader was to leave.
            if plan["hand"] is not None and plan["followers"]:
                self.engine.hybrid.unpin(plan["hand"][0],
                                         len(plan["followers"]))
                self.engine.hybrid.note_declined(
                    len(plan["followers"]), "dropped")
        req.share_plans = []
        dd = getattr(self.engine, "spec_device_drafter", None)
        for r in req.rows:
            self._row_req.pop(id(r), None)
            if dd is not None:
                # The row's shadow draft slot dies with it (ISSUE 13):
                # its pages free, and a future session reusing the name
                # starts its drafter cold instead of diverged.
                try:
                    dd.end_row(self.engine, r.name)
                except Exception:  # noqa: BLE001 — cleanup best-effort
                    pass
            if r.spec is not None and r.spec.drafted:
                # Row-labeled acceptance gauges die with the row:
                # session-scoped names are uuid-tagged per serve call,
                # so a kept series per row ever served would grow the
                # registry without bound (the PR-6 remove_gauge lesson).
                telemetry.REGISTRY.remove_gauge(
                    "roundtable_spec_row_acceptance_rate",
                    engine=self._tname, row=r.name)
        self._active = [r for r in self._active if r not in req.rows]

    # --- committed-token streaming (ISSUE 16) ---

    def _stream_notify(self, req: _Request, event: dict) -> None:
        """Deliver one stream event to req.on_commit — loop-thread
        only. A raising callback is disabled for the rest of the
        request (counted + evented): a broken consumer costs ITS
        stream, never the batch."""
        cb = req.on_commit
        if cb is None:
            return
        try:
            cb(event)
        except Exception as e:  # noqa: BLE001 — consumer must not wedge serving
            req.on_commit = None
            telemetry.inc("roundtable_sched_stream_errors_total",
                          engine=self._tname)
            self._event("stream_error", session=req.session,
                        error=str(e)[:200])

    def _stream_flush(self, req: _Request) -> bool:
        """Push each row's NEW committed tokens (eos-trimmed, so the
        stream never carries post-eos filler and matches the journal's
        `produced` exactly) to the request's on_commit callback.
        -> whether a row's first tokens were among them."""
        if req.on_commit is None:
            return False
        engine = self.engine
        eos = engine.tokenizer.eos_id
        max_new, _padded = clamp_max_new(req.max_new,
                                         engine.max_seq_len)
        first = False
        for i, r in enumerate(req.rows):
            ids = eos_trim(list(r.produced), eos, max_new)
            if len(ids) <= r.streamed:
                continue
            first = first or r.streamed == 0
            new = ids[r.streamed:]
            # queue_wait_s rides every tokens event (ISSUE 20): the
            # gateway's critical-path trace carves the scheduler queue
            # wait out of its submit→first-token lump, so the TTFT
            # waterfall separates "waiting for a slot" from prefill.
            self._stream_notify(req, {
                "type": "tokens", "row": i, "knight": req.turns[i][0],
                "tokens": new, "done": r.done,
                "queue_wait_s": round(
                    (req.admitted_at or req.enqueued) - req.enqueued, 3)})
            if req.on_commit is None:
                return first  # callback died mid-flush
            r.streamed = len(ids)
        return first

    def _flush_streams(self) -> bool:
        """The streaming seam's tick hook: after every segment fold
        (ragged, spec, while-loop — all land in rows' `produced`),
        flush each streaming request's newly committed span. Tokens
        flush at SEGMENT boundaries, the same grain retirement and the
        journal observe — a streamed token is always a committed one.
        -> whether some row's first tokens went out."""
        first = False
        for req in list(self._active_reqs):
            if req.on_commit is not None:
                first = self._stream_flush(req) or first
        return first

    # --- retirement ---

    def _retire_finished(self) -> None:
        """Retire every all-done request: eos-trim, journal, stats,
        per-session gauge removal. Loop-thread only (single-writer
        counter bumps need no cv)."""
        engine = self.engine
        eos = engine.tokenizer.eos_id
        for req in list(self._active_reqs):
            if not req.rows or not all(r.done for r in req.rows):
                continue
            max_new, _padded = clamp_max_new(req.max_new,
                                             engine.max_seq_len)
            texts = []
            for r in req.rows:
                ids = eos_trim(list(r.produced), eos, max_new)
                req.stats.decode_tokens += len(ids)
                # Commit prompt + every FED token (= all but the last
                # sampled one) for next-round prefix reuse — the
                # finalize_outputs contract. Persona rows never feed
                # the cross-session prefix cache (index=False): their
                # pages hold adapter-tinted K/V (ISSUE 10).
                fed = ids[:-1] if ids else []
                engine.kv.commit(r.name, r.tokens + fed,
                                 index=not r.adapter_slot)
                hy = getattr(engine, "hybrid", None)
                if hy is not None:
                    # A row that ended on a sampled eos consumed one
                    # token more than it commits: no continuation.
                    hy.on_commit(r.name, r.tokens + fed,
                                 exact=eos not in r.produced[:max_new])
                texts.append(engine.tokenizer.decode(ids))
            # (roundtable_lora_apply_tokens_total was bumped per
            # DISPATCH as the tokens were served — retire must not
            # count them again.)
            self._release_adapters(req)
            if self._journal is not None:
                # Durable commit point (ISSUE 12): the round's results
                # are about to be handed back — journal them fsynced
                # FIRST, so the record on disk never claims less than
                # the submitter saw.
                self._journal_retired(req, eos, max_new)
            req.stats.int4_paths = engine.int4_path_report()
            req.stats.sched = {
                "queue_wait_s": round(
                    (req.admitted_at or req.enqueued) - req.enqueued, 3),
                "segments": req.seg_count,
                "occupancy_mean": (round(req.occ_sum / req.seg_count, 2)
                                   if req.seg_count else 0.0),
                "occupancy_max": req.occ_max,
                "sessions_max": req.sess_max,
            }
            if req.first_token_at is not None:
                # TTFT (ISSUE 8): submit → every row of the round has
                # its first sampled token. The offered-load bench's
                # headline percentile reads this from metrics.json.
                req.stats.sched["ttft_s"] = round(
                    req.first_token_at - req.enqueued, 3)
            if req.spec_drafted:
                # Speculation provenance (ISSUE 9): rides adapter
                # stats into metrics.json like queue_wait/ttft do.
                req.stats.sched["spec"] = {
                    "drafted": req.spec_drafted,
                    "accepted": req.spec_accepted,
                    "acceptance_rate": round(
                        req.spec_accepted / req.spec_drafted, 3),
                }
            if req.adapters and any(a is not None
                                    for a in req.adapters):
                # Persona provenance (ISSUE 10): which LoRA adapter
                # served each knight of this round.
                req.stats.sched["lora_adapters"] = list(req.adapters)
            if req.on_commit is not None:
                # Streaming epilogue (ISSUE 16): the journal record is
                # already fsynced above, so "retired" tells the gateway
                # the turn is DURABLE — safe to finalize event ids.
                self._stream_flush(req)
                self._stream_notify(req, {"type": "retired"})
            self._drop_request(req)
            self._last_active[req.session] = time.monotonic()
            req.result = (texts, req.stats)
            self._bump("completed")
            if req.tele is not None:
                req.tele.set_attr("decode_tokens",
                                  req.stats.decode_tokens)
                req.tele.set_attr("occupancy_max", req.occ_max)
                req.tele.end()
                req.tele = None
            trace_hooks.publish_gen_stats(req.stats, self._tname)
            perf = getattr(engine, "perf", None)
            if perf is not None:
                # Retired session's KV series reads empty, not stale.
                perf.publish_session_kv(req.session, 0)
            trace_hooks.publish_memory_ledger(engine)
            self._event("retire", session=req.session,
                        decode_tokens=req.stats.decode_tokens,
                        occupancy_max=req.occ_max)
            req.event.set()

    def _journal_retired(self, req: _Request, eos: int,
                         max_new: int) -> None:
        """Append this retired round's committed-turn record to the
        session journal (engine/session_journal.py). Guarded end to
        end: a journal failure costs durability, never availability —
        the round still retires and the submitter still gets its
        result (record_turn itself degrades OSErrors to a counter)."""
        try:
            ads = req.adapters or [None] * len(req.rows)
            rows = []
            for (knight, prompt), r, adapter in zip(req.turns, req.rows,
                                                    ads):
                rows.append({
                    "knight": knight,
                    "prompt": prompt,
                    "prompt_tokens": list(r.tokens),
                    "produced": eos_trim(list(r.produced), eos, max_new),
                    "adapter": adapter,
                })
            rec = self._journal.record_turn(req.session, rows,
                                            engine=self._tname,
                                            replica=self.replica)
            if rec is not None:
                self.journal_turns += 1
            elif not self._journal._suspended:
                # record_turn degraded an OSError to None (suspension
                # during replay also returns None, but that is not an
                # error).
                self.journal_errors += 1
        except Exception as e:  # noqa: BLE001 — durability < availability
            self.journal_errors += 1
            self._event("journal_error", session=req.session,
                        error=str(e)[:200])

    # --- per-request health (budgets / cancellation / abandonment) ---

    def _check_request_health(self) -> None:
        forced = self._force_fail
        if forced is not None:
            # force_fail_active's mailbox (ISSUE 12): the supervisor's
            # quiesce-timeout fallback posted an error; every active
            # request fails with it HERE, on the loop thread — request
            # state is single-writer.
            self._force_fail = None
            for req in list(self._active_reqs):
                self._fail_request(req, forced)
        now = time.monotonic()
        for req in list(self._active_reqs):
            if req.abandoned:
                self._fail_request(req, TimeoutError(
                    f"session {req.session!r} abandoned by its waiter"))
                continue
            try:
                req.turn_budget.token.check()
            except deadlines.Cancelled as e:
                self._fail_request(req, e)
                continue
            if now > req.deadline or req.turn_budget.expired:
                produced = sum(
                    max(len(r.produced) - 1, 0) for r in req.rows)
                self._fail_request(req, TimeoutError(
                    f"generation timed out after "
                    f"{req.timeout_s:.0f}s ({produced} decode tokens "
                    "across the session's rows)"))


_scheduler_for_lock = threading.Lock()


def acquire_scheduler(engine, **opts) -> tuple[SessionScheduler, bool]:
    """(scheduler, created): the engine's attached scheduler, building
    one on first use — every concurrent session sharing an engine must
    share its scheduler (two schedulers would fight over the serve lock
    and the decode batch would never actually mix sessions). The
    created flag is decided INSIDE the lock: callers that close only
    schedulers they created (serve_discussions) must not mislabel a
    concurrently-created instance as their own and close it under
    someone else's live sessions."""
    with _scheduler_for_lock:
        existing = getattr(engine, "_scheduler", None)
        if existing is not None and not existing.closed:
            return existing, False
        return SessionScheduler(engine, **opts), True


def scheduler_for(engine, **opts) -> SessionScheduler:
    """acquire_scheduler for callers that don't track ownership."""
    return acquire_scheduler(engine, **opts)[0]


def _settle_heap() -> None:
    """Collect once, then freeze what is left. Programs, parameter
    trees, modules and warm-up's own records live as long as the
    process; a full collection under traffic walked all of them with
    the loop's thread stopped — once a 45 s window, 0.22 s on the chip,
    a whole round's first tokens late by that much (PERF.md, Findings
    PR 42). Frozen objects are freed by their reference counts as ever;
    only a cycle among them is kept, until _release_heap."""
    import gc
    gc.collect()
    gc.freeze()


def _release_heap() -> None:
    """Hand the frozen objects back to the collector: the scheduler
    closes, or its engine was rebuilt and the old one is garbage."""
    import gc
    gc.unfreeze()
