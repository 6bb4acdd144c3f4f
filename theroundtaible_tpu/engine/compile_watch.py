"""Compile observatory — what JAX does to a program before it first runs
(trace, lower, fetch from the persistent cache or compile) heard stage
by stage, a table of the set-up those stages add up to, and a
steady-state recompile sentinel that turns "pow2 buckets compile nothing
mid-serve" from a convention into an enforced, observable guarantee
(ISSUE 6, 54).

The scheduler's core invariant (engine/scheduler.py: occupancy drift
inside a bucket compiles nothing mid-serve) had zero runtime detection:
a recompile regression would show up only as mysterious tail latency.
This module listens to `jax.monitoring` (the supported seam) for five
events — `jaxpr_trace_duration` and `jaxpr_to_mlir_module_duration` as
time spans with JAX's `fun_name` (and their openings, which JAX reports
as scalars: a per-thread depth tells the outermost interval from the
hundreds traced inside it), `backend_compile_duration` and
`cache_retrieval_time_sec` as durations (a fetch from the persistent
cache stalls the serving loop as a fresh compile does, so both count as
a compile, once), `compile_time_saved_sec` on a hit — and records them
into the PR-5 telemetry spine:

- registry counters `roundtable_compiles_total{label=...}` /
  `roundtable_compile_seconds_total` /
  `roundtable_compile_cache_{hits,misses}_total`, a flight-recorder
  `compile` event per compile, `trace`, `lower` and `compile` spans
  (label, fun_name, duration) while telemetry is armed, and a bounded
  in-process history ring (`history()` — what `status --perf` renders);
- **program labels** via `label(...)`: engine dispatch seams wrap
  their device calls in a thread-local attribution window
  (`prefill[b=2,bucket=128]`, `decode[b=4]`), so a compile is
  attributable to the program that triggered it — compiles outside
  any window record as "unlabeled" (engine construction, eager ops);
- the **set-up table** (`setup_report()`, `summary()["setup"]`), always
  on and bounded: from `install()` until `warmup_complete` closes it,
  thread-seconds by stage (per thread the outermost interval owns its
  seconds), one row a lowered program (a program lowered twice is two
  rows with one `fun_name`), cache hits and misses by
  label, the layer bodies a program's trace called — how many of the
  calls traced the body (`bodies_traced`) and how many found it in
  JAX's trace cache (`bodies_reused`: `note_body`, bumped by
  `models/common.layer_body`) — and the wall seconds of the build's
  own steps, marked with `phase(...)` — so "why did this start take
  four minutes" is answered by the process itself
  (`roundtable_setup_seconds_total{stage=...}`,
  `roundtable_setup_programs_total{outcome=...}`,
  `roundtable_setup_bodies_total{outcome=...}`);
- the **steady-state sentinel**: `warmup_complete(label)` (called by
  both engines' warmup() and by SessionScheduler.declare_warmup_
  complete()) declares the compile set closed. Any compile after that
  increments `roundtable_steady_state_compiles_total{label=...}`,
  records a `steady_state_compile` flight event, ships ONE flight
  dump per steady period, and — under `ROUNDTABLE_RECOMPILE_STRICT=1`
  (armed for every `scheduler`-marked test by conftest) — raises
  `RecompileInSteadyState` from the compiling call site, failing the
  serving path LOUD instead of letting a mid-serve compile hide in
  the latency tail;
- the **collector's pauses**: one `gc.callbacks` hook, installed with
  the compile hooks, sums every collection's seconds by generation
  (`gc_report()`, `roundtable_gc_pause_seconds_total{generation=...}`)
  and, while telemetry is armed, puts a pause of 1 ms or more on the
  span timeline as `gc`, on the thread it stopped. It changes nothing
  about when the collector runs.

Every hook fires on a compile event or a collection only: a steady-state
step pays nothing. Host-only at import (no jax until `install()`), same
contract as the rest of the telemetry spine.
"""

from __future__ import annotations

import gc
import os
import threading
import time
from collections import deque
from typing import Any, Optional

from ..utils import telemetry

STRICT_ENV = "ROUNDTABLE_RECOMPILE_STRICT"
_HISTORY_CAP = 256

# Monitoring event names (jax 0.9.0): backend_compile_duration times the
# whole compile-or-get-cached call, so it fires for a fresh compile AND
# for a persistent-cache hit — the hit fires cache_retrieval_time_sec
# first, from inside it, on the same thread. BOTH kinds are mid-serve
# compilation work from the serving loop's point of view, so both
# count, once each: a retrieval marks its thread, and the enclosing
# backend_compile_duration that follows is then not counted again.
# The two stages before it report a time span (start and end on
# time.time()) with the traced function's or the module's `fun_name`
# when they end, and a scalar (the start) when they open.
_COMPILE_EVENT = "backend_compile_duration"
_RETRIEVAL_EVENT = "cache_retrieval_time_sec"
_SAVED_EVENT = "compile_time_saved_sec"
_CACHE_HIT_EVENT = "cache_hits"
_CACHE_MISS_EVENT = "cache_misses"
_SPAN_STAGES = {"jaxpr_trace_duration": "trace",
                "jaxpr_to_mlir_module_duration": "lower"}

# The set-up table's vocabulary: thread-seconds by stage, wall seconds
# by phase. The phases follow one another, so they tile `wall_s`; the
# stages lie inside them (`staged`: most inside `warm_programs` and
# `warm_traffic`, the build's eager operations inside `init` and
# `quantize`).
STAGES = ("trace", "lower", "retrieve", "compile")
PHASES = ("init", "quantize", "pools", "warm_programs", "warm_traffic")
_SETUP_ROWS_CAP = 128
_SETUP_MISSES_CAP = 32      # labels in `misses`, names in `twice`
_SETUP_SLOWEST = 8
# Outermost intervals a row keeps for the span timeline.
_ROW_SPANS_CAP = 8
# A collection shorter than this is counted and not drawn.
GC_SPAN_FLOOR_S = 1e-3


class RecompileInSteadyState(RuntimeError):
    """A program compiled after warmup was declared complete while
    ROUNDTABLE_RECOMPILE_STRICT=1 — the no-mid-serve-recompile
    invariant was violated by the raising call site."""


_state_lock = threading.Lock()
_installed_mode: Optional[str] = None
_history: deque = deque(maxlen=_HISTORY_CAP)
_compiles = 0
_cache_hits = 0
_cache_misses = 0
_steady_labels: set[str] = set()
_steady_compiles = 0
# Engines whose CURRENT steady period already shipped its one flight
# dump — per label, so engine B's first violation still gets its
# postmortem after engine A already dumped.
_steady_dumped: set[str] = set()
# Calls of a jitted layer body (models/common.layer_body) since the
# process began: those that traced it, and those that did not have to.
_bodies_traced = 0
_bodies_reused = 0
_tls = threading.local()


class _Setup:
    """The set-up table: what was heard and marked while it was open.
    Written under `_state_lock`, on compile events and phase marks
    only."""

    def __init__(self) -> None:
        self.t0: Optional[float] = None          # install(), monotonic
        self.closed_at: Optional[float] = None
        self.closed_by: Optional[str] = None
        self.stages = dict.fromkeys(STAGES, 0.0)
        self.phases = dict.fromkeys(PHASES, 0.0)
        # stage seconds heard while each phase was open
        self.staged = dict.fromkeys(PHASES, 0.0)
        self.phase_open: Optional[tuple[str, float]] = None
        self.programs = 0
        self.bodies_traced = 0
        self.bodies_reused = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self.saved_s = 0.0
        self.rows: list[dict[str, Any]] = []
        self.rows_dropped = 0
        self.lowered: dict[str, int] = {}        # fun_name -> lowerings
        self.misses: dict[str, int] = {}         # label -> fresh compiles

    @property
    def open(self) -> bool:
        return self.t0 is not None and self.closed_at is None


_setup = _Setup()

# The collector's pauses by generation. The callback runs between any
# two bytecodes of whichever thread the collection stops — perhaps
# inside a critical section of the registry or the span buffer — so it
# takes no lock and calls nothing that does: it adds to these lists, and
# `gc_report()` publishes the series from them.
_gc_pauses = [0, 0, 0]
_gc_seconds = [0.0, 0.0, 0.0]
_gc_longest = [0.0, 0.0, 0.0]
_gc_published = [[0, 0.0] for _ in range(3)]
_gc_started = 0.0
_gc_late: deque = deque(maxlen=64)   # spans the armed buffer was busy for


def strict_armed() -> bool:
    """Read the env each call so tests can monkeypatch it."""
    return bool(os.environ.get(STRICT_ENV))


class label:
    """Thread-local compile-attribution window: compiles observed while
    the window is open record under `text`. Reentrant (inner windows
    shadow outer); cost is two attribute writes per dispatch.
    `fallback=True` yields to an already-open window — the shared
    run_dispatch seam uses it so its rung-level label never clobbers
    an engine's precise (batch, bucket) one."""

    __slots__ = ("text", "attrs", "_prev", "_skip")

    def __init__(self, text: str, fallback: bool = False, **attrs):
        self.text = text
        self.attrs = attrs
        self._prev = None
        self._skip = fallback

    def __enter__(self) -> "label":
        self._prev = getattr(_tls, "label", None)
        if self._skip and self._prev is not None:
            return self
        self._skip = False
        _tls.label = (self.text, self.attrs)
        return self

    def __exit__(self, *exc) -> bool:
        if not self._skip:
            _tls.label = self._prev
        return False


def current_label() -> tuple[str, dict]:
    cur = getattr(_tls, "label", None)
    return cur if cur is not None else ("unlabeled", {})


class phase:
    """Always-on wall-clock mark around one of the build's own steps
    (PHASES; a handful of calls a process): its seconds go to the
    set-up table's `phases`. The outermost open mark owns the seconds —
    one entered inside another is silent, so the phases tile the
    set-up's wall time. A mark on a closed table opens it again (a
    second engine is being built). `begin()` alone leaves the mark open
    until the table closes: the scheduler's warm traffic ends where its
    owner declares it over."""

    __slots__ = ("name", "_mine")

    def __init__(self, name: str):
        self.name = name
        self._mine = False

    def begin(self) -> "phase":
        now = time.monotonic()
        with _state_lock:
            if _setup.t0 is not None and _setup.phase_open is None:
                _setup.closed_at = _setup.closed_by = None
                _setup.phase_open = (self.name, now)
                self._mine = True
        return self

    __enter__ = begin

    def __exit__(self, *exc) -> bool:
        if self._mine:
            self._mine = False
            with _state_lock:
                _end_phase(time.monotonic())
        return False


def _end_phase(now: float) -> None:
    """(under `_state_lock`)"""
    if _setup.phase_open is None:
        return
    name, began = _setup.phase_open
    _setup.phase_open = None
    _setup.phases[name] = _setup.phases.get(name, 0.0) + (now - began)
    telemetry.inc("roundtable_setup_seconds_total", now - began,
                  stage=name)


def install() -> str:
    """Register the compile hooks and the collector's (idempotent;
    returns the mode, "monitoring"), and start the set-up table's
    clock. Called from both engines' constructors so any serving
    process observes its compiles. A registration that fails is an
    error: an observatory silently off would let every no-recompile
    guarantee go unwatched."""
    global _installed_mode
    with _state_lock:
        if _installed_mode is not None:
            return _installed_mode
        import jax.monitoring as monitoring
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_time_span_listener(_on_time_span)
        monitoring.register_scalar_listener(_on_scalar)
        monitoring.register_event_listener(_on_event)
        gc.callbacks.append(_on_gc)
        _setup.t0 = time.monotonic()
        _installed_mode = "monitoring"
    telemetry.set_gauge("roundtable_compile_observatory", 1.0)
    return _installed_mode


def _on_duration(event: str, duration: float, **kw) -> None:
    if event.endswith(_RETRIEVAL_EVENT):
        _tls.retrieved = True
        _record_compile(duration, cache_hit=True)
    elif event.endswith(_COMPILE_EVENT):
        if getattr(_tls, "retrieved", False):
            _tls.retrieved = False   # the hit just counted, enclosed
            return
        _record_compile(duration, cache_hit=False,
                        fun_name=kw.get("fun_name"))
    elif event.endswith(_SAVED_EVENT):
        with _state_lock:
            if _setup.open:
                _setup.saved_s += duration


def _on_event(event: str, **_kw) -> None:
    global _cache_hits, _cache_misses
    if event.endswith(_CACHE_HIT_EVENT):
        with _state_lock:
            _cache_hits += 1
        telemetry.inc("roundtable_compile_cache_hits_total")
    elif event.endswith(_CACHE_MISS_EVENT):
        with _state_lock:
            _cache_misses += 1
        telemetry.inc("roundtable_compile_cache_misses_total")


# --- the stages before a compile, and the set-up table's rows ---


def _new_row(lbl: str, attrs: dict, fun_name: Optional[str],
             wall_start: float) -> dict[str, Any]:
    row: dict[str, Any] = {
        "label": lbl, "fun_name": fun_name or "", "trace_s": 0.0,
        "lower_s": 0.0, "bodies_traced": 0, "bodies_reused": 0,
        "cache_hit": None,
        "thread": threading.current_thread().name,
        # the interval's start, moved from the wall clock to
        # time.monotonic() (the span buffer's clock)
        "t0": time.monotonic() - (time.time() - wall_start),
        "lowered": False, "listed": False, "spans": [],
    }
    for k in ("batch", "bucket", "shape"):
        if k in attrs:
            row[k] = attrs[k]
    return row


def _in_setup(attrs: dict) -> bool:
    """(under `_state_lock`) Does what this thread just heard belong to
    the set-up? Not once the table is closed, and never where the
    engine it is attributed to has declared steady state."""
    return _setup.open and attrs.get("engine") not in _steady_labels


def _add_stage(stage: str, seconds: float) -> None:
    """(under `_state_lock`) Thread-seconds to a stage of the table,
    and to the phase that is open."""
    _setup.stages[stage] += seconds
    if _setup.phase_open is not None:
        _setup.staged[_setup.phase_open[0]] += seconds


def _list_row(row: dict[str, Any]) -> None:
    """(under `_state_lock`) A row enters `by_program` when it is known
    to be a program: at its lowering, or at a compile heard without
    one."""
    row["listed"] = True
    if len(_setup.rows) < _SETUP_ROWS_CAP:
        _setup.rows.append(row)
    else:
        _setup.rows_dropped += 1


def note_body(traced: bool) -> None:
    """A call of a jitted layer body (models/common.layer_body) has
    returned on this thread: it traced the body, or found it in JAX's
    trace cache. Counted only under a program's trace or lowering (a
    body called eagerly is its own program); the thread's outermost
    interval takes the tally when it ends: `bodies_traced` and
    `bodies_reused` of that program's row."""
    if getattr(_tls, "depth", 0) <= 0:
        return
    t, r = getattr(_tls, "bodies", (0, 0))
    _tls.bodies = (t + bool(traced), r + (not traced))


def _take_bodies(row: dict[str, Any], counted: bool) -> None:
    """(under `_state_lock`) The body calls this thread made inside the
    outermost interval that just ended, to its row and the totals."""
    global _bodies_traced, _bodies_reused
    traced, reused = getattr(_tls, "bodies", (0, 0))
    if not (traced or reused):
        return
    _tls.bodies = (0, 0)
    row["bodies_traced"] += traced
    row["bodies_reused"] += reused
    _bodies_traced += traced
    _bodies_reused += reused
    if counted:
        _setup.bodies_traced += traced
        _setup.bodies_reused += reused
        telemetry.inc("roundtable_setup_bodies_total", traced,
                      outcome="traced")
        telemetry.inc("roundtable_setup_bodies_total", reused,
                      outcome="reused")


def _on_scalar(event: str, _value: float, **_kw) -> None:
    """An interval of a stage opens on this thread (JAX reports the
    start of every timed section as a scalar): one level deeper."""
    if event.rsplit("/", 1)[-1] in _SPAN_STAGES:
        _tls.depth = getattr(_tls, "depth", 0) + 1


def _on_time_span(event: str, start: float, end: float, **kw) -> None:
    stage = _SPAN_STAGES.get(event.rsplit("/", 1)[-1])
    if stage is None:
        return
    # A thread is in one stage at a time and the OUTERMOST interval
    # owns its seconds: a jitted function traced inside another (every
    # jnp call is one, hundreds a step program), or traced inside a
    # lowering, reports an interval of its own from within. (An
    # interval whose opening was not heard counts as outermost.)
    depth = getattr(_tls, "depth", 1) - 1
    _tls.depth = max(depth, 0)
    if depth > 0:
        return
    dur = max(end - start, 0.0)
    fun_name = kw.get("fun_name") or ""
    lbl, attrs = current_label()
    row = getattr(_tls, "row", None)
    if (row is None or row["label"] != lbl or row["cache_hit"] is not None
            or row["lowered"]):
        row = _tls.row = _new_row(lbl, attrs, fun_name, start)
    if len(row["spans"]) < _ROW_SPANS_CAP:
        row["spans"].append((start, dur, stage, fun_name))
    with _state_lock:
        counted = _in_setup(attrs)
        row[stage + "_s"] += dur
        row["fun_name"] = fun_name or row["fun_name"]
        if counted:
            _add_stage(stage, dur)
        _take_bodies(row, counted)
        if stage == "lower":
            row["lowered"] = True
            if counted:
                _setup.programs += 1
                _setup.lowered[fun_name] = _setup.lowered.get(
                    fun_name, 0) + 1
                _list_row(row)
    if counted:
        telemetry.inc("roundtable_setup_seconds_total", dur, stage=stage)


def _close_row(duration: float, cache_hit: bool,
               fun_name: Optional[str]) -> dict[str, Any]:
    """The compile (or the fetch) that ends this thread's open row; a
    compile heard with no lowering before it is a row of its own. While
    armed, what the thread spent before it goes on the span timeline."""
    lbl, attrs = current_label()
    row = getattr(_tls, "row", None)
    if row is None or row["label"] != lbl or row["cache_hit"] is not None:
        row = _new_row(lbl, attrs, fun_name, time.time() - duration)
    _tls.row = None
    stage = "retrieve" if cache_hit else "compile"
    with _state_lock:
        row["cache_hit"] = cache_hit
        row[stage + "_s"] = duration
        row["fun_name"] = row["fun_name"] or fun_name or ""
        counted = _in_setup(attrs)
        if counted:
            _add_stage(stage, duration)
            if cache_hit:
                _setup.cache_hits += 1
            else:
                _setup.cache_misses += 1
                if (lbl in _setup.misses
                        or len(_setup.misses) < _SETUP_MISSES_CAP):
                    _setup.misses[lbl] = _setup.misses.get(lbl, 0) + 1
            if not row["listed"]:
                _list_row(row)
    if counted:
        telemetry.inc("roundtable_setup_seconds_total", duration,
                      stage=stage)
        telemetry.inc("roundtable_setup_programs_total",
                      outcome="hit" if cache_hit else "miss")
    if telemetry.ACTIVE:
        # On the clock of the slice's ends, inside the dispatch they
        # stalled (JAX reports an interval on time.time()).
        now_wall, now = time.time(), time.monotonic()
        for start, dur, st, name in row["spans"]:
            if dur > 0.0:
                telemetry.emit_span_at(
                    st, now - (now_wall - start), dur, label=lbl,
                    fun_name=name)
    return row


def _record_compile(duration: float, cache_hit: bool,
                    fun_name: Optional[str] = None) -> None:
    global _compiles, _steady_compiles
    lbl, attrs = current_label()
    row = _close_row(duration, cache_hit, fun_name)
    entry: dict[str, Any] = {
        "label": lbl, "dur_s": round(duration, 4),
        "at": round(time.time(), 3), "cache_hit": cache_hit,
    }
    if row["fun_name"]:
        entry["fun_name"] = row["fun_name"]
    for k, v in attrs.items():
        entry.setdefault(k, v)
    dump_now = False
    with _state_lock:
        _compiles += 1
        # Violation = the compile is attributable to an engine that
        # DECLARED steady state (the attribution window's engine attr
        # vs that engine's label). Per-engine, not process-global: in
        # a multi-engine process (warmup_cmd loops adapters), engine
        # 1's declaration must not classify engine 2's construction
        # and warmup compiles as violations. The cost: compiles with
        # no engine attribution (eager ops, construction) are never
        # violations — the labeled prefill/decode dispatch that any
        # real mid-serve shape change also triggers is what trips.
        eng = attrs.get("engine")
        steady = eng in _steady_labels
        entry["steady_state"] = steady
        _history.append(entry)
        if steady:
            _steady_compiles += 1
            if eng not in _steady_dumped:
                _steady_dumped.add(eng)
                dump_now = True
    telemetry.inc("roundtable_compiles_total", label=lbl)
    telemetry.inc("roundtable_compile_seconds_total", duration)
    telemetry.recorder().record("compile", **entry)
    if telemetry.ACTIVE:
        # On the span timeline too (ISSUE 25): the hook fires on the
        # compiling thread as the compile ends, so the span lies inside
        # the dispatch — and the scheduler tick — it stalled.
        telemetry.emit_span("compile", duration, label=lbl,
                            cache_hit=cache_hit)
    if not entry["steady_state"]:
        return
    telemetry.inc("roundtable_steady_state_compiles_total", label=lbl)
    if dump_now:
        # One postmortem per steady period — a recompile-per-segment
        # pathology must not turn the dump dir into its own incident.
        telemetry.flight_dump("steady_state_compile",
                              extra={"label": lbl, "entry": entry})
    if strict_armed():
        raise RecompileInSteadyState(
            f"compile of {lbl!r} ({'cache retrieval' if cache_hit else 'backend compile'}, "
            f"{duration:.3f}s) after warmup was declared complete for "
            f"{sorted(_steady_labels)} — the no-mid-serve-recompile "
            "invariant is violated (unset ROUNDTABLE_RECOMPILE_STRICT "
            "or warm the missing shape)")


# --- the collector's pauses ---


def _on_gc(when: str, info: dict) -> None:
    """`gc.callbacks` hook: two clock reads a collection. No lock is
    taken and nothing is called that waits for one (see `_gc_pauses`)."""
    global _gc_started
    if when == "start":
        _gc_started = time.perf_counter()
        return
    dur = time.perf_counter() - _gc_started
    gen = info.get("generation", 2)
    _gc_pauses[gen] += 1
    _gc_seconds[gen] += dur
    if dur > _gc_longest[gen]:
        _gc_longest[gen] = dur
    if telemetry.ACTIVE and dur >= GC_SPAN_FLOOR_S:
        _gc_late.append((time.monotonic() - dur, dur, gen,
                         info.get("collected", 0),
                         threading.current_thread().name,
                         telemetry.current_span_ids()))
    if _gc_late:
        _flush_gc_spans()


def _flush_gc_spans() -> None:
    """Pauses drawn as `gc` spans, each under the span its thread was
    in; one that finds the armed buffer busy waits for the next
    collection (or `gc_report()`)."""
    while _gc_late:
        t0, dur, gen, collected, thread, ids = _gc_late[0]
        if not telemetry.emit_span_at(
                "gc", t0, dur, wait=False, parent=ids, generation=gen,
                collected=collected, thread=thread):
            return
        _gc_late.popleft()


def gc_report() -> dict[str, Any]:
    """describe()["gc"]: the collector's pauses since install(), by
    generation. Publishes the two series from the callback's sums."""
    _flush_gc_spans()
    out: dict[str, Any] = {"pauses": {}, "seconds": {}, "longest_s": {}}
    with _state_lock:
        for gen in range(3):
            n, s = _gc_pauses[gen], _gc_seconds[gen]
            seen = _gc_published[gen]
            if n > seen[0]:
                telemetry.inc("roundtable_gc_collections_total",
                              n - seen[0], generation=gen)
                telemetry.inc("roundtable_gc_pause_seconds_total",
                              s - seen[1], generation=gen)
                seen[0], seen[1] = n, s
            out["pauses"][str(gen)] = n
            out["seconds"][str(gen)] = round(s, 6)
            out["longest_s"][str(gen)] = round(_gc_longest[gen], 6)
    return out


# --- steady-state declaration ---


def warmup_complete(label_name: str = "engine") -> None:
    """Declare this engine/scheduler's compile set closed: every later
    compile is a steady-state violation (counted always, fatal under
    ROUNDTABLE_RECOMPILE_STRICT=1). The set-up table closes with it."""
    now = time.monotonic()
    with _state_lock:
        _steady_labels.add(label_name)
        if _setup.open:
            _end_phase(now)
            _setup.closed_at, _setup.closed_by = now, label_name
    telemetry.set_gauge("roundtable_steady_state", 1.0,
                        engine=label_name)
    telemetry.recorder().record("warmup_complete", engine=label_name)


def reopen_warmup(label_name: str) -> None:
    """Re-enter the warmup phase for ONE label: a new compile surface
    appeared on an already-warm engine (a SessionScheduler attached —
    its pipelined-segment carries and pinned-row joins trace shapes
    direct warmup never touches), so compiles are expected again until
    the owner re-declares. The sanctioned production escape; without
    it, engine.warmup()'s auto-declaration would classify the
    scheduler's warm traffic as steady-state violations. The set-up
    table opens again with it."""
    with _state_lock:
        _steady_labels.discard(label_name)
        _steady_dumped.discard(label_name)
        _setup.closed_at = _setup.closed_by = None
        telemetry.set_gauge("roundtable_steady_state", 0.0,
                            engine=label_name)


def reset_steady_state() -> None:
    """Leave steady state (tests; a deliberate re-warm after a config
    change). Also zeroes the module-level violation counter so test
    assertions read per-test deltas."""
    global _steady_compiles
    with _state_lock:
        for name in _steady_labels:
            telemetry.set_gauge("roundtable_steady_state", 0.0,
                                engine=name)
        _steady_labels.clear()
        _steady_dumped.clear()
        _steady_compiles = 0


def steady_state_labels() -> tuple[str, ...]:
    with _state_lock:
        return tuple(sorted(_steady_labels))


# --- introspection ---


def compiles_seen() -> int:
    return _compiles


def steady_state_compiles() -> int:
    return _steady_compiles


def history() -> list[dict]:
    with _state_lock:
        return list(_history)


def _row_out(row: dict[str, Any]) -> dict[str, Any]:
    return {k: (round(v, 4) if k.endswith("_s") else v)
            for k, v in row.items()
            if k not in ("lowered", "listed", "spans")}


def setup_report() -> dict[str, Any]:
    """The set-up table. `stages` are thread-seconds; `phases` are wall
    seconds and tile `wall_s` (install() to the close, or to now while
    it is open); `staged` says how many of the stages' seconds were
    heard inside each phase (a phase CONTAINS them: two threads
    bringing programs up at once can stage more than its wall);
    `by_program` holds one row a lowered program, in the order they
    were lowered."""
    now = time.monotonic()
    with _state_lock:
        t = _setup
        phases = dict(t.phases)
        if t.phase_open is not None:
            name, began = t.phase_open
            phases[name] = phases.get(name, 0.0) + (now - began)
        end = t.closed_at if t.closed_at is not None else now
        return {
            "closed": t.t0 is not None and t.closed_at is not None,
            "closed_by": t.closed_by,
            "wall_s": round(end - t.t0, 4) if t.t0 is not None else 0.0,
            "stages": {k: round(v, 4) for k, v in t.stages.items()},
            "phases": {k: round(v, 4) for k, v in phases.items()},
            "staged": {k: round(v, 4) for k, v in t.staged.items()},
            "programs": t.programs,
            "bodies_traced": t.bodies_traced,
            "bodies_reused": t.bodies_reused,
            "cache_hits": t.cache_hits,
            "cache_misses": t.cache_misses,
            "saved_s": round(t.saved_s, 4),
            "misses": dict(t.misses),
            "twice": dict(sorted(
                ((k, n) for k, n in t.lowered.items() if n > 1),
                key=lambda kn: -kn[1])[:_SETUP_MISSES_CAP]),
            "by_program": [_row_out(r) for r in t.rows],
            "rows_dropped": t.rows_dropped,
        }


def _row_seconds(row: dict[str, Any]) -> float:
    return sum(row.get(s + "_s", 0.0) for s in STAGES)


def _setup_summary() -> dict[str, Any]:
    """summary()["setup"]: the table without its rows, but for the
    eight that took longest."""
    out = setup_report()
    rows = out.pop("by_program")
    del out["closed_by"], out["rows_dropped"]
    out["slowest"] = sorted(rows, key=_row_seconds,
                            reverse=True)[:_SETUP_SLOWEST]
    return out


def summary(recent: int = 0) -> dict[str, Any]:
    """The describe()/status/attribution embed."""
    setup = _setup_summary()
    with _state_lock:
        out: dict[str, Any] = {
            "mode": _installed_mode or "uninstalled",
            "compiles": _compiles,
            "cache_hits": _cache_hits,
            "cache_misses": _cache_misses,
            "bodies_traced": _bodies_traced,
            "bodies_reused": _bodies_reused,
            "steady_state": sorted(_steady_labels),
            "steady_state_compiles": _steady_compiles,
            "strict": strict_armed(),
            "setup": setup,
        }
        if recent:
            out["recent"] = list(_history)[-recent:]
    return out
