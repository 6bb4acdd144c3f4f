"""The host-side serving loop of InferenceEngine (engine.py) and the
scheduler.

The device programs are the engine's; the HOST logic around them —
chunked bucketed prefill with the cache-end bucket-shrink guard, the
decode segment loop with deadline checks, and the eos-trim/commit
epilogue — lives here once. The caller passes its dispatch closure.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import telemetry
from . import deadlines, faults
from .sampling import row_filtered

PREFILL_BUCKETS = (64, 128, 256, 512, 1024, 2048)
MAX_PREFILL_CHUNK = 2048
DECODE_SEGMENT = 64  # tokens per decode program; timeout checks in between

# Ragged mixed prefill/decode dispatch (ISSUE 8): the flat token
# buffer's row granularity (the MXU sublane minimum — one decode token
# occupies one 8-row tile) and the default per-dispatch token budget.
# ONE compiled ragged program per budget serves every prefill/decode
# composition, so the budget is the whole "shape grid" on this path —
# a small fixed set of max-token shapes, not per-occupancy buckets.
RAGGED_BLOCK_Q = 8
# ... and what a slot brings to the join buffer's top shape on a plain
# decoder (ragged_token_budget): a multiple of the block.
RAGGED_SLOT_TOKENS = 96
RAGGED_FLOOR_TOKENS = 1024
RAGGED_TOKENS_ENV = "ROUNDTABLE_RAGGED_TOKENS"
RAGGED_DEFER_MIN_ENV = "ROUNDTABLE_RAGGED_DEFER_MIN"


# The slots of a roomy frame (PERF.md, Findings PR 46 and PR 53). CPython
# 3.11+ keeps a thread's interpreter frames in 16 KiB chunks and frees a
# chunk the moment its first frame returns, so a loop whose calls cross
# a chunk's end pays one mmap and one munmap A CALL. Tracing and lowering
# are such loops, hundreds of frames deep: 19 s of Laguna's 40 s warm-up
# on the chip's machine, and 22 s more once an unrelated edit made the
# frames under them ten slots smaller (PR 46, the scheduler's thread);
# 7 s of Jamba's 33 s build when the step seams' frames changed size
# (PR 53, the main thread under `_warm_ragged`). A frame this large opens
# one 512 KiB chunk that lives as long as the frame and holds every
# frame above it: the scheduler's loop has one, and so has the engine's
# build-time warm-up of the ragged grid.
FRAME_SLOTS = 40_000


def roomy_frame(fn):
    fn.__code__ = fn.__code__.replace(co_stacksize=FRAME_SLOTS)
    return fn


def ragged_token_budget(num_slots: int, asked: int = 0,
                        hybrid: bool = False) -> int:
    """Flat-buffer capacity per ragged dispatch — the TOP shape of the
    join grid (ragged_shape_grid). The rule (ISSUE 57): on a PLAIN
    decoder the top shape holds a round's leaders —
    RAGGED_SLOT_TOKENS (96) a slot, 1 536 at 16 slots. A round of five
    sessions is the first one's prologue and a burst of the other four:
    four leaders' ~320-token deltas beside the first session's decode
    blocks want 1 280-1 432 in fifteen bursts of twenty (my chip runs,
    PR 57: PERF.md, Findings, with the `segment` spans; the ledger's
    PR 57 line on `mistral-7b-int8.roundtable` bears it out or does
    not), so the leaders ride ONE dispatch, their eight followers the
    next, and the burst is two dispatches where 1 024 cut the fourth
    leader and made it three. `hybrid` — the engine serves its model
    through the hybrid step programs (`engine.hybrid`) — keeps ISSUE
    8's budget, and the reasons are those programs' (PERF.md, same
    entry): a shape is TWO of them, warmed at build (A.X-K1 at 1 536:
    +9.7 % of its warm set-up against a bound of 10 %; Jamba: +13.5 %,
    and +17 % of its median first token, since a follower there
    re-scans a page's remainder and a round's work spreads over the
    dispatches whatever the top shape), and the chip's compiler
    refuses the 1 536 step at Mellum's widths (the experts' gather of
    [12 288, 2 304]: tests/test_chip_compile.py has the case, and
    Laguna's, which compiles — ROADMAP S13 (d') is the way back for
    the expert cells). Both are floored at 1 024 — a typical cold
    join's leader span streams in ONE dispatch — and leave every
    resident row's 8-row decode block its chunk room.
    ROUNDTABLE_RAGGED_TOKENS, or else the engine config's
    `ragged_tokens` (`asked`), overrides (rounded up to a block
    multiple)."""
    import os
    forced = int(os.environ.get(RAGGED_TOKENS_ENV, "0") or 0) or asked
    if forced > 0:
        return -(-forced // RAGGED_BLOCK_Q) * RAGGED_BLOCK_Q
    if hybrid:
        return max(RAGGED_FLOOR_TOKENS, RAGGED_BLOCK_Q * num_slots + 64)
    return max(RAGGED_FLOOR_TOKENS, RAGGED_SLOT_TOKENS * num_slots)


def ragged_defer_min() -> int:
    """Suffix-token threshold below which a join keeps the PROLOGUE
    even on a ragged engine: with the prefix cache attached, a warm
    join's remaining prefill is often a few dozen tokens — blocking the
    batch for one tiny bucket dispatch is cheaper than spreading the
    same work across segment-gated ragged ticks. Only genuinely COLD
    prefills (the admission stall the ragged path exists to kill) are
    worth deferring. ROUNDTABLE_RAGGED_DEFER_MIN overrides."""
    import os
    return int(os.environ.get(RAGGED_DEFER_MIN_ENV, "256") or 256)


def ragged_shape_grid(budget: int) -> tuple[int, ...]:
    """The SMALL FIXED GRID of flat-buffer shapes (ISSUE 8): a dispatch
    compiles (and computes) its whole static buffer, pads included, so
    a lone decode step + 30-token tail chunk must not pay for the full
    budget's compute. Shapes {64, 256, 1024, budget} (deduped, capped
    at the budget) — every shape is warmed once, the dispatcher picks
    the smallest that fits the real work, and occupancy drift within a
    shape still compiles nothing. This is shape discipline by MAX-TOKEN
    grid, not per-occupancy row buckets — the grid stays this size
    regardless of max_rows."""
    return tuple(sorted({s for s in (64, 256, 1024, budget)
                         if s <= budget}))


def ragged_pick_shape(grid: tuple[int, ...], want: int,
                      carry: int = 0) -> int:
    """Smallest grid shape >= want. A want past the last shape takes a
    second dispatch whatever is picked, and a dispatch computes its
    whole buffer: the first is then the shape — of those from the
    floor of ragged_token_budget up — with which the two compute the
    fewest buffer tokens, the smaller on a tie (first tokens sooner).
    `carry`: what the second dispatch brings of its own beside the
    remainder (the join packer: a block of every active row). A caller
    that caps its want at the budget gets the last shape, as ever; so
    does a grid with one shape from the floor up."""
    for s in grid:
        if want <= s:
            return s
    firsts = [s for s in grid if s >= RAGGED_FLOOR_TOKENS] or grid[-1:]
    return min(firsts, key=lambda s: (
        s + ragged_pick_shape(grid, min(want - s + carry, grid[-1])), s))


# What the dispatch being issued has cost so far (ISSUE 53): the open
# `dispatch` span's [host_buffers, launches], on the thread that runs
# the dispatch (the caller's, or the watchdog's worker).
_issuing = threading.local()


def new_dispatch_totals() -> dict:
    """An engine's lifetime account of what it issued: `programs` (step
    programs and the prologue's sampler), `host_buffers` (host-to-device
    transfers made for them) and `launches` (device programs issued,
    the step program among them). One buffer and one launch a program
    where a dispatch is packed (engine/dispatch_pack.py); a path that
    still sends its arrays one by one shows by how much it is not."""
    return {"programs": 0, "host_buffers": 0, "launches": 0}


def note_issue(totals: dict, engine: str, *, programs: int = 1,
               host_buffers: int = 1, launches: int = 1) -> None:
    """A step seam's account of what it has just issued, into the
    engine's totals, the registry's series and the open `dispatch`
    span (run_dispatch)."""
    totals["programs"] += programs
    totals["host_buffers"] += host_buffers
    totals["launches"] += launches
    telemetry.inc("roundtable_dispatch_programs_total", programs,
                  engine=engine)
    telemetry.inc("roundtable_dispatch_host_buffers_total", host_buffers,
                  engine=engine)
    telemetry.inc("roundtable_dispatch_launches_total", launches,
                  engine=engine)
    tally = getattr(_issuing, "tally", None)
    if tally is not None:
        tally[0] += host_buffers
        tally[1] += launches


def run_dispatch(dispatch: Callable, retry, deadline: float = float("inf"),
                 budget=None, rung: str = "dispatch"):
    """One device dispatch through the shared fault-tolerance AND
    deadline seams: the dispatch-stage injection points fire first (zero
    overhead unarmed — the guard is the module-level faults.ARMED flag),
    the watchdog times the blocking part of the dispatch against its
    rung budget when armed (deadlines.ACTIVE — a wait that exceeds it
    raises HangDetected, which classifies as the non-retryable `hang`
    kind and climbs the ladder like a crash), then the retry policy
    re-runs a transiently-failed dispatch before it surfaces. Failures
    a retry can't fix (timeout/oom/hang/...) pass straight through to
    the caller's degradation rung (RetryPolicy.retryable).

    Scope: retry-in-place helps failures raised BEFORE the device
    program consumes its inputs (host-side validation, dispatch-queue
    errors, the injected faults). The engines' KV programs donate their
    cache buffers (donate_argnums), so a failure that surfaces AFTER
    donation leaves the cache references dead (and a blind re-dispatch
    would die on the same dead buffers — RetryPolicy treats deleted-array
    errors as non-retryable), so that error climbs the ladder to the
    adapter rung, whose serial retry reallocates the buffers
    (engine.revive_kv_if_dead) and re-prefills from scratch
    (tpu_llm._serial_retry)."""

    def call(tally=None):
        if faults.ARMED:
            faults.inject_dispatch_faults()
        if tally is None:
            return dispatch()
        _issuing.tally = tally
        try:
            return dispatch()
        finally:
            _issuing.tally = None

    def attempt(tally=None):
        if deadlines.ACTIVE and budget is not None:
            return deadlines.watched_wait(lambda: call(tally), budget,
                                          rung)
        return call(tally)

    def attempt_traced():
        # "dispatch" is the span tree's leaf rung (ISSUE 5), mirroring
        # the budget rung the watchdog times this wait against. The
        # compile-attribution window (ISSUE 6) is a FALLBACK: a caller
        # that already opened a precise (batch, bucket) label keeps it;
        # one that didn't still gets a rung-level label instead of
        # "unlabeled".
        from . import compile_watch
        with compile_watch.label(f"dispatch[{rung}]", fallback=True):
            if telemetry.ACTIVE:
                # (every attempt is a span of its own, with what it
                # sent and issued: ISSUE 53)
                tally = [0, 0]
                with telemetry.span("dispatch", stage=rung) as sp:
                    try:
                        return attempt(tally)
                    finally:
                        sp.set_attr("host_buffers", tally[0])
                        sp.set_attr("launches", tally[1])
            return attempt()

    # The loop clock's seam (ISSUE 25): on a clocked thread (the
    # session scheduler's) the time spent issuing is phase `dispatch`,
    # marked here and nowhere else.
    clock = telemetry.loop_clock()
    back = clock.switch("dispatch") if clock is not None else None
    try:
        if retry is None:
            return attempt_traced()
        return retry.run(attempt_traced, deadline=deadline)
    finally:
        if clock is not None:
            clock.mark(back)


def host_sync(fn: Callable, budget=None, rung: str = "decode"):
    """A blocking device→host read through the deadline seam: the read
    is where a wedged device program actually freezes the host loop
    (`int(steps)` / `float(logits[0, 0])` block until the program
    completes), so it gets the same watchdog treatment as a dispatch.
    Unarmed: a direct call behind the module-flag check."""
    def attempt():
        if deadlines.ACTIVE and budget is not None:
            return deadlines.watched_wait(fn, budget, rung)
        return fn()

    # The loop clock's seam (ISSUE 25): the host waits here because the
    # device works — phase `sync` on a clocked thread.
    clock = telemetry.loop_clock()
    back = clock.switch("sync") if clock is not None else None
    try:
        if telemetry.ACTIVE:
            with telemetry.span("dispatch", stage=rung, op="host_sync"):
                return attempt()
        return attempt()
    finally:
        if clock is not None:
            clock.mark(back)


class ReplicaGroupPlan:
    """Row permutation + padding that aligns a serving batch with the
    page pool's data-axis replicas (pool-direct paged serving under
    data>1, VERDICT r4 #4).

    shard_map splits the batch axis into contiguous blocks — block r
    lands on data-axis index r — and the per-replica page pool puts
    replica r's pages on exactly that shard. So a pool-direct batch must
    place each row inside the block of the replica that owns its slot's
    pages. The plan computes that layout once per generate_batch call:
    block r holds replica r's rows (original order preserved within the
    block), padded to the largest group size with rows whose page table
    is the replica's scratch page and whose first token is eos (they
    start done and their writes land on scratch, which is never read).

    `pos[i]` is the padded-batch position of original row i; padded
    arrays are built with scatter_rows/scatter_list/pad_table and read
    back with `padded[plan.pos]`.
    """

    def __init__(self, replicas: list[int], n_replicas: int,
                 bucket_group: bool = False):
        groups: list[list[int]] = [[] for _ in range(n_replicas)]
        for i, r in enumerate(replicas):
            groups[r].append(i)
        self.n_replicas = n_replicas
        group = max(1, max(len(g) for g in groups))
        if bucket_group:
            # Round the per-replica block up to a power of two: callers
            # whose batch COMPOSITION changes between dispatches (the
            # session scheduler's decode batch) keep the padded shape on
            # a {R*1, R*2, R*4, ...} grid instead of compiling one
            # program per exact group size. Fixed-composition callers
            # (generate_batch — one plan per call, warmup covers the
            # shapes) leave this off.
            group = pow2_bucket(group)
        self.group = group
        self.b_padded = n_replicas * self.group
        self.pos = np.empty(len(replicas), np.int64)
        pad_positions: list[int] = []
        pad_replicas: list[int] = []
        for r, rows in enumerate(groups):
            for k, i in enumerate(rows):
                self.pos[i] = r * self.group + k
            for k in range(len(rows), self.group):
                pad_positions.append(r * self.group + k)
                pad_replicas.append(r)
        self.pad_positions = np.asarray(pad_positions, np.int64)
        self.pad_replicas = pad_replicas

    def scatter_rows(self, values, pad_value) -> np.ndarray:
        """Original-order per-row host array → padded host array (it
        travels in the dispatch's packed buffer: nothing here touches
        the device)."""
        arr = np.asarray(values)
        out = np.full((self.b_padded,) + arr.shape[1:], pad_value,
                      arr.dtype)
        out[self.pos] = arr
        return out

    def scatter_list(self, items: list, pad_item) -> list:
        """Original-order per-row python values → padded list (pad rows
        share the one `pad_item` — callers treat rows as read-only)."""
        out = [pad_item] * self.b_padded
        for i, item in enumerate(items):
            out[self.pos[i]] = item
        return out

    def pad_table(self, table: np.ndarray, scratch_page) -> np.ndarray:
        """[B, pages_per_seq] page table → padded table whose pad rows
        point every entry at their replica's scratch page."""
        out = np.empty((self.b_padded, table.shape[1]), table.dtype)
        out[self.pos] = table
        for p, r in zip(self.pad_positions, self.pad_replicas):
            out[p, :] = scratch_page(r)
        return out


def pow2_bucket(n: int) -> int:
    """Smallest power of two >= n — THE bucketing grid shared by the
    session scheduler's decode batch and ReplicaGroupPlan's
    bucket_group, so the two padded-shape families can never diverge
    into mismatched compiled programs."""
    b = 1
    while b < n:
        b <<= 1
    return b


def clamp_max_new(max_new: int, max_seq_len: int) -> tuple[int, int]:
    """(clamped max_new, segment-padded decode reserve) — ONE
    definition of the decode-budget clamp for both engines and the
    session scheduler: the same value must bound row budgets at
    admission, size the page reserve, and cap eos_trim at retirement,
    or the scheduler and generate_batch drift on token parity.

    The clamp: decode can never exceed half the context (a
    misconfigured max_new_tokens would otherwise drive the prompt
    budget negative and collapse every prompt to [bos]); the reserve
    rounds up to whole DECODE_SEGMENTs because decode runs in whole
    segment programs whose surplus writes must not clamp onto committed
    cache positions."""
    m = max(1, min(max_new, max_seq_len // 2))
    return m, -(-m // DECODE_SEGMENT) * DECODE_SEGMENT


def prompt_budget(max_seq_len: int, max_new_padded: int) -> int:
    """Prompt-token budget once the padded decode reserve is set aside.

    Raises when fewer than 2 tokens remain — head-truncation keeps
    [bos] + the last (budget-1) tokens, so budget ≤ 1 would silently
    collapse every prompt to [bos]: a config error, not a serving
    condition. One definition for both engines."""
    budget = max_seq_len - max_new_padded - 1
    if budget < 2:
        raise ValueError(
            f"max_seq_len {max_seq_len} leaves no prompt room after the "
            f"{max_new_padded}-token decode reserve (segments pad to "
            f"{DECODE_SEGMENT}) — use max_seq_len > {max_new_padded + 2} "
            "or lower max_new_tokens")
    return budget


def bucket_for(n: int) -> int:
    for b in PREFILL_BUCKETS:
        if n <= b:
            return b
    return MAX_PREFILL_CHUNK


def chunked_prefill(
    dispatch: Callable[[np.ndarray, list[int], np.ndarray], jax.Array],
    token_lists: list[list[int]],
    offsets: list[int],
    max_seq_len: int,
    pad_id: int,
    deadline: float = float("inf"),
    retry=None,
    budget=None,
    note=None,
) -> jax.Array:
    """Bucketed multi-chunk prefill. Returns last-token logits [B, V].

    dispatch(chunk [B, bucket], offs, lengths) runs one device program and
    returns that chunk's last-token logits. Every row writes a bucket-wide
    block at its offset; near the cache end the bucket shrinks so no row's
    write overruns the position-aligned layout (dynamic_update_slice would
    silently clamp the offset and corrupt it). Each row's logits are kept
    from the chunk where its REAL tokens ended — later pad-only chunks
    must not clobber them.

    `budget` (engine/deadlines.py): the prefill rung's Budget. Each
    chunk's dispatch runs under the watchdog at the "dispatch" rung, and
    cooperative cancellation/deadline checks run between chunks (a
    single XLA program cannot be interrupted — the boundaries are where
    a drain or an exhausted ancestor budget takes effect). `note`: the
    caller's account of what is sent and issued (serving_loop.note_issue
    bound to its engine) — a second and later chunk's merge of the kept
    logits is a mask sent and a program of its own.
    """
    b = len(token_lists)
    if budget is not None:
        deadline = min(deadline, budget.deadline)
    offs = list(offsets)
    remaining = [list(t) for t in token_lists]
    final_logits: Optional[jax.Array] = None
    while any(remaining):
        max_len = min(max(len(r) for r in remaining), MAX_PREFILL_CHUNK)
        bucket = bucket_for(max_len)
        allowed = max_seq_len - max(offs)
        if bucket > allowed:
            smaller = [x for x in PREFILL_BUCKETS if x <= allowed]
            bucket = smaller[-1] if smaller else max(allowed, 1)
        chunk = np.full((b, bucket), pad_id, np.int32)
        lengths = np.zeros((b,), np.int32)
        takes = np.zeros((b,), np.int32)
        for i, r in enumerate(remaining):
            take = min(len(r), bucket)
            takes[i] = take
            if take:
                chunk[i, :take] = r[:take]
                del r[:take]
            # Exhausted rows feed one pad at their current offset; it stays
            # outside their committed length and decode overwrites that
            # position with the first real generated token.
            lengths[i] = max(take, 1)
        if budget is not None:
            budget.check()
        last_logits = run_dispatch(
            lambda: dispatch(chunk, offs, lengths), retry, deadline,
            budget=budget)
        # On a clocked thread the device now holds a program whose
        # result the prologue's one read waits for (LoopClock.feed; the
        # read drains every chunk's ticket with its own).
        clock = telemetry.loop_clock()
        if clock is not None:
            clock.feed()
        if final_logits is None:
            final_logits = last_logits
        else:
            final_logits = jnp.where(jnp.asarray(takes > 0)[:, None],
                                     last_logits, final_logits)
            if note is not None:
                note(programs=0)
        for i in range(b):
            offs[i] += int(takes[i])
        if time.monotonic() > deadline and any(remaining):
            raise TimeoutError("prefill timed out")
    return final_logits


def row_budget_fn(per_row, sampling_per_turn, max_new: int) -> np.ndarray:
    """The rows' token budgets as decode begins (host int32 [B]).

    Only an EXPLICIT sampling_per_turn carries per-row max_new_tokens
    budgets (capped by the call-level max_new) — otherwise the call
    level wins uniformly: the engine-default sampling's budget must not
    silently cap an explicit call request. The prefill-sampled first
    token has already consumed one token of every row's budget, hence
    the -1. Across segments the decode program itself hands on what is
    left (`max(budgets - steps, 0)`), so the pipelined segment queue
    never forces a host sync."""
    if sampling_per_turn:
        totals = np.asarray(
            [min(p.max_new_tokens, max_new) for p in per_row], np.int32)
    else:
        totals = np.full(len(per_row), max_new, np.int32)
    return np.maximum(totals - 1, 0)


def decode_segments(
    dispatch: Callable,
    rows: int,
    max_new: int,
    deadline: float,
    timeout_s: float,
    retry=None,
    budget=None,
    filtered_rows: int = 0,
) -> np.ndarray:
    """Segmented decode: one device program per DECODE_SEGMENT tokens with
    host-side timeout/early-exit checks in between (a single XLA program
    cannot be interrupted, so this is how the adapter's per-turn timeout
    contract is honored). The segment size is ALWAYS DECODE_SEGMENT — a
    variable tail would compile a fresh program per distinct length.

    dispatch(budget, carry) → (out, steps, last, valid, done, budgets)
    runs one segment over `rows` rows: `budget` is the tokens still
    wanted, `carry` the (last, valid, done, budgets) of the segment
    before, on the device — None on the first, whose rows' state the
    caller's dispatch packs itself. The done mask is carried ACROSS
    segments (rows at eos / their row budget skip further decode).
    Returns the concatenated token matrix [B, produced].
    `filtered_rows`: how many of the rows engage the sampler's filters
    (sampling.row_filtered), for the spans.

    PIPELINED: the next segment is queued from the previous segment's
    DEVICE outputs BEFORE the host reads steps/out/done — so the device
    never idles for the host round-trips between segments (material
    wherever the host is slow to turn around). Its `budget` is a host
    number all the same, max_new less DECODE_SEGMENT a segment issued:
    a segment is queued only while that is positive, so every segment
    before it had more than DECODE_SEGMENT to go and either took all
    its steps (the number is exact) or stopped with every row done —
    and then the queued segment's while_loop condition is false on
    entry whatever its budget; it costs microseconds and its results
    are discarded.
    """
    if budget is not None:
        deadline = min(deadline, budget.deadline)
    segments: list[np.ndarray] = []
    produced = 0
    cur = run_dispatch(lambda: dispatch(max_new, None), retry, deadline,
                       budget=budget)
    seg_idx = 0
    while True:
        # "segment" span (ISSUE 5): one per consumed decode segment —
        # the null-span singleton when telemetry is disarmed, so the
        # hot loop pays one module-flag check inside span().
        with telemetry.span("segment", index=seg_idx, rows=rows,
                            filtered_rows=filtered_rows):
            out, steps, *carry = cur
            done = carry[2]
            # Speculative queue while the device results are still in
            # flight — but never past the deadline (the host clock is
            # already known; queuing after it would run a whole wasted
            # segment the timeout then waits on). `produced` lags the
            # just-computed segment, so the bound is an upper bound on
            # "more work possible"; the discard case skips the loop body
            # via the carried done mask (and the gather/scatter around
            # it via the engines' all-done cond), costing microseconds.
            timed_out = time.monotonic() > deadline
            cancelled = budget is not None and budget.token.cancelled
            nxt = (run_dispatch(
                lambda: dispatch(
                    max_new - DECODE_SEGMENT * (seg_idx + 1), tuple(carry)),
                retry, deadline, budget=budget)
                if produced + DECODE_SEGMENT < max_new and not timed_out
                and not cancelled
                else None)

            # The segment's host sync is the blocking wait a wedged
            # device program freezes — it goes through the watchdog
            # seam, not a raw np.asarray (the deadline-seam contract for
            # every blocking device wait in the serving paths).
            def read_segment(steps=steps, out=out, done=done):
                n = int(steps)  # forces completion of the segment
                return (n, np.asarray(out)[:, :n],
                        bool(np.all(np.asarray(done))))

            steps_n, seg, all_done = host_sync(read_segment, budget,
                                               "decode")
            segments.append(seg)
            produced += steps_n
        seg_idx += 1
        if produced >= max_new or all_done:
            break
        if cancelled:
            budget.check()  # raises Cancelled with the drain/abort reason
        if timed_out:
            raise TimeoutError(
                f"generation timed out after {timeout_s:.0f}s "
                f"({produced}/{max_new} tokens)")
        cur = nxt
    return (np.concatenate(segments, axis=1) if segments
            else np.zeros((rows, 0), np.int32))


class RaggedSeq:
    """One sequence's slice of a ragged dispatch: the tokens it feeds
    this call (a prefill chunk, the single last-sampled token of a
    decode row, or a speculative ``[last, drafts...]`` verify run), the
    absolute position of the first one, its page table row, and its
    sampling params. `n_scores` is how many TRAILING token rows the
    dispatch must score (ISSUE 9): 1 for plain rows (the last-token
    sample), drafts+1 for a verify run. Host-side description only —
    build_ragged_batch turns a list of these into device inputs."""

    __slots__ = ("tokens", "pos", "table", "temperature", "top_k",
                 "top_p", "n_scores", "adapter")

    def __init__(self, tokens: list[int], pos: int, table: np.ndarray,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, n_scores: int = 1,
                 adapter: int = 0):
        self.tokens = tokens
        self.pos = pos
        self.table = table
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.n_scores = n_scores
        # LoRA adapter SLOT of this sequence (ISSUE 10, 0 = base): the
        # flat buffer mixes sequences with different adapters in one
        # dispatch, so identity rides per TOKEN (token_adapter below) -
        # a value, never a shape.
        self.adapter = adapter


def build_ragged_batch(seqs: list[RaggedSeq], *, t_budget: int,
                       s_max: int, pages_per_seq: int, scratch_page: int,
                       pad_id: int, page_size: int,
                       score_width: int = 0,
                       copy_pairs: Optional[list] = None,
                       copy_slots: int = 0) -> dict:
    """Device inputs for one ragged mixed prefill/decode dispatch.

    Every array has a STATIC shape derived from (t_budget, s_max) alone
    — the composition (how many sequences, how the budget splits
    between prefill chunks and decode tokens) lives entirely in the
    VALUES, so occupancy drift and chunk interleaving never compile a
    new program (the property that retires the pow2 row buckets on this
    path). Each sequence occupies a RAGGED_BLOCK_Q-aligned run of the
    flat buffer; the last slot of s_max is the INERT sequence every pad
    row/block points at (kv_valid=1 over the scratch page, one page of
    throwaway compute per unused block). Pad tokens scatter their K/V
    to the scratch page, which no real sequence ever reads.

    Returns the dict the engine's _ragged_dispatch consumes: flat
    tokens/positions/token_pages/token_offs/token_seq [t_budget],
    per-block seq_of_block/block_qstart [t_budget/8], per-seq
    tables/query_offsets/kv_valid/last_rows/temps/top_ks/top_ps
    [s_max, ...], `greedy`, and the accounting fields n_seqs/n_tokens/
    filtered_rows (sequences whose top_k or top_p engages the sampler's
    candidate pool).

    `score_width` > 0 (ISSUE 9, the speculative verify): the dict also
    carries `sample_rows` [s_max, score_width] — for each sequence, the
    flat-buffer rows of its LAST n_scores tokens (pad columns repeat
    the last row; their scores are computed and discarded). The shape
    is a function of (s_max, score_width) alone — score_width is the
    STATIC spec_max_draft+1, so acceptance drift and per-row throttle
    flips change only values, never the compiled program.

    `copy_slots` > 0 (ISSUE 13, tree verify): the dict also carries
    `copy_src`/`copy_dst` [copy_slots] — page pairs the dispatch must
    device-copy BEFORE its K/V scatter (forward_ragged does it per
    layer). A tree row's candidate paths are separate sequences whose
    tables alias private frontier pages, and the partially-committed
    frontier page's committed cells must exist in each private copy —
    a pre-COW folded into the dispatch. The arrays are padded with
    scratch->scratch self-copies, so how many tree rows (0 included)
    actually need copies is a VALUE; copy_slots is static from engine
    config alone (num_slots), so chain/tree/no-spec mixes never
    compile a new program.
    """
    bq = RAGGED_BLOCK_Q
    if t_budget % bq:
        raise ValueError(f"t_budget {t_budget} not a multiple of {bq}")
    nb = t_budget // bq
    inert = s_max - 1
    if len(seqs) > inert:
        raise ValueError(
            f"{len(seqs)} sequences > {inert} (one slot is the inert "
            "pad sequence)")
    tokens = np.full(t_budget, pad_id, np.int32)
    positions = np.zeros(t_budget, np.int32)
    token_pages = np.full(t_budget, scratch_page, np.int32)
    token_offs = np.zeros(t_budget, np.int32)
    token_seq = np.full(t_budget, inert, np.int32)
    seq_of_block = np.full(nb, inert, np.int32)
    block_qstart = np.zeros(nb, np.int32)
    tables = np.full((s_max, pages_per_seq), scratch_page, np.int32)
    query_offsets = np.zeros(s_max, np.int32)
    kv_valid = np.ones(s_max, np.int32)
    last_rows = np.zeros(s_max, np.int32)
    token_adapter = np.zeros(t_budget, np.int32)
    temps = np.ones(s_max, np.float32)
    top_ks = np.zeros(s_max, np.int32)
    top_ps = np.ones(s_max, np.float32)
    sample_rows = (np.zeros((s_max, score_width), np.int32)
                   if score_width > 0 else None)
    copy_src = copy_dst = None
    if copy_slots > 0:
        pairs = list(copy_pairs or [])
        if len(pairs) > copy_slots:
            raise ValueError(
                f"{len(pairs)} copy pairs > copy_slots {copy_slots}")
        copy_src = np.full(copy_slots, scratch_page, np.int32)
        copy_dst = np.full(copy_slots, scratch_page, np.int32)
        for k, (src, dst) in enumerate(pairs):
            copy_src[k] = src
            copy_dst[k] = dst
    elif copy_pairs:
        raise ValueError("copy_pairs given without copy_slots")

    row = 0
    n_tokens = 0
    for i, s in enumerate(seqs):
        n = len(s.tokens)
        if n < 1:
            raise ValueError("RaggedSeq needs at least one token")
        if s.n_scores < 1 or s.n_scores > n:
            raise ValueError(
                f"n_scores {s.n_scores} outside 1..{n} (run length)")
        if score_width and s.n_scores > score_width:
            raise ValueError(
                f"n_scores {s.n_scores} > score_width {score_width}")
        span = -(-n // bq) * bq
        if row + span > t_budget:
            raise ValueError(
                f"sequences overflow the {t_budget}-token budget")
        tokens[row:row + n] = s.tokens
        # Pad rows inside the span continue the position run — their
        # outputs are dropped, the positions only steer (harmless)
        # causal frontiers.
        positions[row:row + span] = s.pos + np.arange(span)
        pos_n = s.pos + np.arange(n)
        token_pages[row:row + n] = s.table[pos_n // page_size]
        token_offs[row:row + n] = pos_n % page_size
        token_seq[row:row + span] = i
        b0 = row // bq
        for k in range(span // bq):
            seq_of_block[b0 + k] = i
            block_qstart[b0 + k] = k * bq
        tables[i] = s.table
        # Pad rows inside the span keep adapter 0: their K/V lands on
        # the scratch page and their outputs are dropped, so the base
        # (zero) delta is both correct and the cheapest.
        token_adapter[row:row + n] = s.adapter
        query_offsets[i] = s.pos
        kv_valid[i] = s.pos + n
        last_rows[i] = row + n - 1
        if sample_rows is not None:
            first = row + n - s.n_scores
            for j in range(score_width):
                sample_rows[i, j] = min(first + j, row + n - 1)
        temps[i] = s.temperature
        top_ks[i] = s.top_k
        top_ps[i] = s.top_p
        row += span
        n_tokens += n
    return {
        "tokens": tokens, "positions": positions,
        "token_pages": token_pages, "token_offs": token_offs,
        "token_seq": token_seq, "seq_of_block": seq_of_block,
        "block_qstart": block_qstart, "tables": tables,
        "query_offsets": query_offsets, "kv_valid": kv_valid,
        "last_rows": last_rows, "temps": temps, "top_ks": top_ks,
        "top_ps": top_ps, "token_adapter": token_adapter,
        "greedy": all(s.temperature <= 0.0 for s in seqs),
        # (sequences that take the sampler's pool: the segment span's)
        "filtered_rows": sum(row_filtered(s) for s in seqs),
        "n_seqs": len(seqs), "n_tokens": n_tokens,
        "score_width": score_width,
        **({"sample_rows": sample_rows} if sample_rows is not None
           else {}),
        **({"copy_src": copy_src, "copy_dst": copy_dst}
           if copy_src is not None else {}),
    }


def eos_trim(ids: list[int], eos_id: int, max_new: int) -> list[int]:
    """Canonical per-row output epilogue: cut at the first eos, cap at
    max_new. ONE definition shared by finalize_outputs and the session
    scheduler's row retirement so a scheduled row's token stream is
    byte-identical to the same row served by generate_batch."""
    if eos_id in ids:
        ids = ids[:ids.index(eos_id)]
    return ids[:max_new]


def finalize_outputs(turns, first_np: np.ndarray, out_np: np.ndarray,
                     all_tokens: list[list[int]], max_new: int,
                     eos_id: int, commit: Callable[[str, list[int]], None],
                     decode: Callable[[list[int]], str],
                     stats) -> list[str]:
    """Eos-trim each row, commit prompt+fed ids for next-turn prefix
    reuse, detokenize, and account decode tokens into stats."""
    results = []
    for i, (name, _) in enumerate(turns):
        ids = eos_trim([int(first_np[i])] + [int(x) for x in out_np[i]],
                       eos_id, max_new)
        stats.decode_tokens += len(ids)
        # cache now holds prompt + every fed token (= all but the last
        # sampled one); commit exactly that for next-turn prefix reuse
        fed = ids[:-1] if ids else []
        commit(name, all_tokens[i] + fed)
        results.append(decode(ids))
    return results
