"""Compile observatory — every XLA compile recorded, and a steady-state
recompile sentinel that turns "pow2 buckets compile nothing mid-serve"
from a convention into an enforced, observable guarantee (ISSUE 6).

The scheduler's core invariant (engine/scheduler.py: occupancy drift
inside a bucket compiles nothing mid-serve) had zero runtime detection:
a recompile regression would show up only as mysterious tail latency.
This module hooks JAX compilation via `jax.monitoring` events (the
supported seam — fires for both fresh backend compiles and persistent-
cache retrievals, which ALSO stall the serving loop) and records every
compile into the PR-5 telemetry spine:

- registry counters `roundtable_compiles_total{label=...}` /
  `roundtable_compile_seconds_total` /
  `roundtable_compile_cache_{hits,misses}_total`, a flight-recorder
  `compile` event per observation, a `compile` span (label, cache_hit,
  duration) while telemetry is armed, and a bounded in-process history
  ring (`history()` — what `status --perf` renders);
- **program labels** via `label(...)`: engine dispatch seams wrap
  their device calls in a thread-local attribution window
  (`prefill[b=2,bucket=128]`, `decode[b=4]`), so a compile is
  attributable to the program that triggered it — compiles outside
  any window record as "unlabeled" (engine construction, eager ops);
- the **steady-state sentinel**: `warmup_complete(label)` (called by
  both engines' warmup() and by SessionScheduler.declare_warmup_
  complete()) declares the compile set closed. Any compile after that
  increments `roundtable_steady_state_compiles_total{label=...}`,
  records a `steady_state_compile` flight event, ships ONE flight
  dump per steady period, and — under `ROUNDTABLE_RECOMPILE_STRICT=1`
  (armed for every `scheduler`-marked test by conftest) — raises
  `RecompileInSteadyState` from the compiling call site, failing the
  serving path LOUD instead of letting a mid-serve compile hide in
  the latency tail.

Host-only at import (no jax until `install()`), same contract as the
rest of the telemetry spine.
"""

from __future__ import annotations

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
_COMPILE_EVENT = "backend_compile_duration"
_RETRIEVAL_EVENT = "cache_retrieval_time_sec"
_CACHE_HIT_EVENT = "cache_hits"
_CACHE_MISS_EVENT = "cache_misses"


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
_tls = threading.local()


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


def install() -> str:
    """Register the compile hooks (idempotent; returns the mode,
    "monitoring"). Called from both engines' constructors so any
    serving process observes its compiles. A registration that fails is
    an error: an observatory silently off would let every
    no-recompile guarantee go unwatched."""
    global _installed_mode
    with _state_lock:
        if _installed_mode is not None:
            return _installed_mode
        import jax.monitoring as monitoring
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _installed_mode = "monitoring"
    telemetry.set_gauge("roundtable_compile_observatory", 1.0)
    return _installed_mode


def _on_duration(event: str, duration: float, **_kw) -> None:
    if event.endswith(_RETRIEVAL_EVENT):
        _tls.retrieved = True
        _record_compile(duration, cache_hit=True)
    elif event.endswith(_COMPILE_EVENT):
        if getattr(_tls, "retrieved", False):
            _tls.retrieved = False   # the hit just counted, enclosed
            return
        _record_compile(duration, cache_hit=False)


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


def _record_compile(duration: float, cache_hit: bool) -> None:
    global _compiles, _steady_compiles
    lbl, attrs = current_label()
    entry: dict[str, Any] = {
        "label": lbl, "dur_s": round(duration, 4),
        "at": round(time.time(), 3), "cache_hit": cache_hit,
    }
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


# --- steady-state declaration ---


def warmup_complete(label_name: str = "engine") -> None:
    """Declare this engine/scheduler's compile set closed: every later
    compile is a steady-state violation (counted always, fatal under
    ROUNDTABLE_RECOMPILE_STRICT=1)."""
    with _state_lock:
        _steady_labels.add(label_name)
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
    scheduler's warm traffic as steady-state violations."""
    with _state_lock:
        _steady_labels.discard(label_name)
        _steady_dumped.discard(label_name)
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


def cache_hits_seen() -> int:
    return _cache_hits


def steady_state_compiles() -> int:
    return _steady_compiles


def history() -> list[dict]:
    with _state_lock:
        return list(_history)


def summary(recent: int = 0) -> dict[str, Any]:
    """The describe()/status/attribution embed."""
    with _state_lock:
        out: dict[str, Any] = {
            "mode": _installed_mode or "uninstalled",
            "compiles": _compiles,
            "cache_hits": _cache_hits,
            "cache_misses": _cache_misses,
            "steady_state": sorted(_steady_labels),
            "steady_state_compiles": _steady_compiles,
            "strict": strict_armed(),
        }
        if recent:
            out["recent"] = list(_history)[-recent:]
    return out
