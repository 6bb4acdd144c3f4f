"""Unified telemetry — span tracer, metrics registry, flight recorder.

The stack grew four private observability surfaces (PRs 1–4):
`describe()["int4_paths"]`, the scheduler's event log + occupancy
history, `fleet_health()`'s hang/breaker counts, and per-session
`metrics.json` — four formats an operator stitches by hand during an
incident. Production TPU serving engines treat tracing/metrics as ONE
first-class subsystem feeding live dashboards and postmortems alike
(RTP-LLM, arxiv 2605.29639), and TPU perf work is only credible with
xprof-aligned annotations (arxiv 2605.25645). This module is that
spine; the existing surfaces publish through it and become views.

Four pieces:

- **Span tracer** — explicit spans mirroring the PR-2 Budget tree
  (`discussion → round → turn → prefill|decode → segment → dispatch`)
  carrying session/knight/engine attributes. Spans nest via a
  thread-local stack; cross-thread hops (orchestrator batch pools, the
  scheduler thread) hand a `current_context()` dict across and attach
  it with `attached(ctx)`. Finished spans append to the per-session
  JSONL sink riding the span tree (root spans carry it; children
  inherit), into the flight recorder, and into the armed span buffer
  (`spans_between`: records carry `t0` on time.monotonic(), the clock
  a benchmark reads its window on); while a jax profiler trace is
  armed (`maybe_profile` → `set_profiling`), each LEXICAL span also
  opens a `jax.profiler.TraceAnnotation` so xprof timelines and JSONL
  spans line up on the same names (held spans do not: see `Span`).
  Disarmed, `span()` returns a no-op singleton behind the same
  module-flag pattern as `deadlines.ACTIVE` / `faults.ARMED` — hot
  call sites additionally pre-guard with `if telemetry.ACTIVE:`.
- **Loop clock** — `LoopClock`: which phase a loop thread (the session
  scheduler's) is in at every instant; lifetime seconds per phase
  always, one `loop.<phase>` span per stretch while armed.
- **Metrics registry** — process-wide counters/gauges/histograms
  (decode tok/s, queue wait, batch occupancy, pages held, breaker
  state, hang/fault/fallback counts) with `snapshot()` for embedding
  in bench/flight records and `prometheus_text()` for the
  `<session>/telemetry/metrics.prom` file `roundtable status
  --telemetry` renders. Counters are cheap (one lock + dict add) and
  stay on regardless of ACTIVE: they fire per EVENT (admission, trip,
  hang), never per token.
- **Flight recorder** — a bounded ring of recent spans/events per
  named recorder. `flight_dump(trigger)` writes ring + registry
  snapshot to a JSON file and returns its path; deadlines (hang),
  faults (breaker trip), tpu_llm (ladder escalation) and fleet (drain)
  call it automatically, so every failure ships its own postmortem.

Host-only by design (no jax import at module load): deadlines/faults
import this without touching a backend, and the types stay usable in
pure-unit tests. Arming: `arm()` in-process or `ROUNDTABLE_TELEMETRY=1`
in the environment; `ROUNDTABLE_TELEMETRY_DIR` overrides where flight
dumps land.
"""

from __future__ import annotations

import json
import os
import tempfile
import threading
import time
import uuid
from collections import deque
from typing import Any, Optional

# Module-level guard — the ONLY thing unarmed hot paths touch (one
# attribute load + branch, same contract as deadlines.ACTIVE).
ACTIVE = False

# True while a jax profiler trace is running (utils/metrics.maybe_profile
# flips it): armed spans then mirror into jax.profiler.TraceAnnotation.
_PROFILING = False

# The span rungs, outermost first — the Budget tree (deadlines.RUNGS)
# plus the two sub-turn seams budgets don't name ("segment" sits between
# decode and dispatch; "profile" is maybe_profile's root). ISSUE 20
# adds the serving layer above the engine tree: "request" roots a
# gateway stream leg, "resume" roots a reconnect/restore leg joined to
# the original trace id (utils/tracing.py).
TRACE_RUNGS = ("profile", "request", "resume", "discussion", "round",
               "turn", "prefill", "decode", "segment", "dispatch")

# What a round's start does on the host (ISSUE 37) — children of
# `admit` and `segment`, records of the armed buffer (and the ring and
# sinks, as any span) that never mirror into the profiler's trace: an
# `rt:pack` there would take `rt:loop.build`'s idle gaps in the trace's
# reduction, whose names readers and the ledger's rows are built on.
UNMIRRORED_RUNGS = frozenset(("plan", "page_copy", "share", "pack"))

_INF = float("inf")

# While armed, every finished span also lands in one in-memory buffer,
# on the clock a benchmark reads its window on (`t0` is
# time.monotonic()): `spans_between(t_a, t_b)` is how a per-layer reader
# takes a traced slice's spans without a snapshot of any lifetime
# total. Bounded — the oldest record goes first and is counted.
SPAN_BUFFER_CAPACITY = 65536
_span_buffer: deque = deque(maxlen=SPAN_BUFFER_CAPACITY)
_span_buffer_dropped = 0


def arm() -> None:
    """Arm the span tracer. Going from disarmed to armed opens a FRESH
    span buffer (the last armed stretch's records stay readable only
    until then); arming twice keeps what the first arming gathered."""
    global ACTIVE, _span_buffer, _span_buffer_dropped
    if not ACTIVE:
        with _spans_lock:
            _span_buffer = deque(maxlen=SPAN_BUFFER_CAPACITY)
            _span_buffer_dropped = 0
    ACTIVE = True


def disarm() -> None:
    """Stop tracing. The span buffer stays readable until the next
    arm() — a benchmark disarms first and reads afterwards."""
    global ACTIVE
    ACTIVE = False


def set_profiling(on: bool) -> None:
    """maybe_profile's seam: while True, armed spans mirror into
    jax.profiler.TraceAnnotation so xprof and the JSONL tree share
    names (and, via the root span maybe_profile opens, one trace id)."""
    global _PROFILING
    _PROFILING = bool(on)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

# Wall-clock-ish histogram buckets (seconds): sub-10ms dispatches up to
# multi-minute turns. Fixed buckets keep observe() one bisect + add.
HIST_BUCKETS = (0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0, 30.0,
                60.0, 120.0, 300.0)


def _label_key(labels: dict[str, Any]) -> tuple:
    return tuple(sorted((k, str(v)) for k, v in labels.items()))


class MetricsRegistry:
    """Process-wide counters/gauges/histograms, label-aware.

    One instance (`REGISTRY`) serves the whole process: schedulers,
    engines and adapters label their series (engine=..., point=...,
    rung=...) instead of owning private stores — the single-source-of-
    truth migration the four PR-1..4 surfaces converge on."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: dict[tuple[str, tuple], float] = {}
        self._gauges: dict[tuple[str, tuple], float] = {}
        self._hists: dict[tuple[str, tuple], dict] = {}

    # --- writes ---

    def inc(self, name: str, value: float = 1.0, **labels) -> None:
        key = (name, _label_key(labels))
        with self._lock:
            self._counters[key] = self._counters.get(key, 0.0) + value

    def set_gauge(self, name: str, value: float, **labels) -> None:
        key = (name, _label_key(labels))
        with self._lock:
            self._gauges[key] = float(value)

    def remove_gauge(self, name: str, **labels) -> None:
        """Drop one labeled gauge series. For per-entity series whose
        entities RETIRE (per-session KV footprints): a long-lived
        serving process must not accumulate one dead series per
        session ever served — zeroing would keep the label set (and
        the metrics.prom export) growing without bound."""
        key = (name, _label_key(labels))
        with self._lock:
            self._gauges.pop(key, None)

    def observe(self, name: str, value: float,
                exemplar: Optional[str] = None, **labels) -> None:
        """One histogram sample. `exemplar` (ISSUE 20) attaches a
        trace id to the bucket the sample lands in — last writer wins
        per bucket — so a bad p95/p99 bucket links to a CONCRETE trace
        instead of an anonymous count. Exemplars ride snapshot() and
        the exposition's bucket lines (OpenMetrics `# {...}` syntax,
        which the metrics.prom overlay parser already skips)."""
        key = (name, _label_key(labels))
        with self._lock:
            h = self._hists.get(key)
            if h is None:
                h = self._hists[key] = {
                    "counts": [0] * (len(HIST_BUCKETS) + 1),
                    "sum": 0.0, "count": 0}
            for i, b in enumerate(HIST_BUCKETS):
                if value <= b:
                    h["counts"][i] += 1
                    bucket = i
                    break
            else:
                h["counts"][-1] += 1
                bucket = len(HIST_BUCKETS)
            h["sum"] += value
            h["count"] += 1
            if exemplar:
                ex = h.get("exemplars")
                if ex is None:
                    ex = h["exemplars"] = {}
                ex[bucket] = {"trace_id": str(exemplar),
                              "value": round(float(value), 6)}

    # --- reads ---

    def counter_total(self, name: str, **labels) -> float:
        """Sum of a counter across label sets (or the one labeled set
        when labels are given)."""
        with self._lock:
            if labels:
                return self._counters.get((name, _label_key(labels)), 0.0)
            return sum(v for (n, _l), v in self._counters.items()
                       if n == name)

    def gauge_value(self, name: str, **labels) -> Optional[float]:
        with self._lock:
            return self._gauges.get((name, _label_key(labels)))

    def exemplars(self, name: str, **labels) -> dict[int, dict]:
        """bucket index → {"trace_id", "value"} for one histogram
        series (the trace-exemplar read side: `roundtable trace`
        links a slow bucket to its retained trace)."""
        with self._lock:
            h = self._hists.get((name, _label_key(labels)))
            if h is None:
                return {}
            return {int(k): dict(v)
                    for k, v in h.get("exemplars", {}).items()}

    def snapshot(self) -> dict[str, Any]:
        """Full structured snapshot (flight dumps, tests)."""

        def flat(store):
            out = {}
            for (name, lkey), v in sorted(store.items()):
                label = ",".join(f"{k}={val}" for k, val in lkey)
                out[f"{name}{{{label}}}" if label else name] = v
            return out

        with self._lock:
            return {
                "counters": flat(self._counters),
                "gauges": flat(self._gauges),
                "histograms": {
                    key: {
                        "sum": round(h["sum"], 6), "count": h["count"],
                        **({"exemplars": {
                            str(b): dict(e)
                            for b, e in h["exemplars"].items()}}
                           if h.get("exemplars") else {}),
                    }
                    for key, h in flat(self._hists).items()},
            }

    def snapshot_compact(self) -> dict[str, float]:
        """Counters + gauges as one flat dict — the bench-record embed
        (BENCH_r*.json carries occupancy/fallback/hang counters the way
        int4_paths rides today)."""
        snap = self.snapshot()
        out = dict(snap["counters"])
        out.update(snap["gauges"])
        return out

    def prometheus_text(self) -> str:
        """Prometheus exposition-format snapshot (the metrics.prom
        writer behind `roundtable status --telemetry`)."""
        lines: list[str] = []

        def fmt_labels(lkey):
            if not lkey:
                return ""
            body = ",".join(f'{k}="{v}"' for k, v in lkey)
            return "{" + body + "}"

        with self._lock:
            counters = sorted(self._counters.items())
            gauges = sorted(self._gauges.items())
            hists = sorted(self._hists.items())
        seen: set[str] = set()
        for (name, lkey), v in counters:
            if name not in seen:
                lines.append(f"# TYPE {name} counter")
                seen.add(name)
            lines.append(f"{name}{fmt_labels(lkey)} {v:g}")
        for (name, lkey), v in gauges:
            if name not in seen:
                lines.append(f"# TYPE {name} gauge")
                seen.add(name)
            lines.append(f"{name}{fmt_labels(lkey)} {v:g}")
        for (name, lkey), h in hists:
            if name not in seen:
                lines.append(f"# TYPE {name} histogram")
                seen.add(name)
            def ex_suffix(bucket: int) -> str:
                # OpenMetrics exemplar on the bucket line; the
                # metrics.prom overlay parser skips _bucket lines, so
                # this never perturbs `status --perf/--kv` series.
                e = h.get("exemplars", {}).get(bucket)
                if not e:
                    return ""
                return (f' # {{trace_id="{e["trace_id"]}"}} '
                        f'{e["value"]:g}')

            cum = 0
            for i, b in enumerate(HIST_BUCKETS):
                cum += h["counts"][i]
                le = (("le", f"{b:g}"),)
                lines.append(
                    f"{name}_bucket{fmt_labels(lkey + le)} {cum}"
                    f"{ex_suffix(i)}")
            cum += h["counts"][-1]
            lines.append(
                f'{name}_bucket{fmt_labels(lkey + (("le", "+Inf"),))} '
                f"{cum}{ex_suffix(len(HIST_BUCKETS))}")
            lines.append(f"{name}_sum{fmt_labels(lkey)} {h['sum']:g}")
            lines.append(f"{name}_count{fmt_labels(lkey)} {h['count']}")
        return "\n".join(lines) + ("\n" if lines else "")

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()


REGISTRY = MetricsRegistry()

# Module-level shorthands (call sites read better; one shared registry).
inc = REGISTRY.inc
set_gauge = REGISTRY.set_gauge
remove_gauge = REGISTRY.remove_gauge
observe = REGISTRY.observe
counter_total = REGISTRY.counter_total


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

_FLIGHT_CAPACITY = int(os.environ.get("ROUNDTABLE_FLIGHT_CAPACITY",
                                      "512"))


# Dumps kept on disk per process lifetime of pruning calls: each dump()
# trims the dump dir to this many newest files, so a crash-looping
# serve can't fill the disk with postmortems of the same incident.
_DUMP_KEEP = int(os.environ.get("ROUNDTABLE_FLIGHT_DUMPS_KEEP", "64"))


class FlightRecorder:
    """Bounded rings of recent events and spans; `dump()` ships both +
    a registry snapshot to disk so a hang/trip/drain carries its own
    postmortem. Recording is a lock + deque append — cheap enough to
    stay on for EVENT-rate callers (admissions, trips, retirements);
    per-token paths never record. Spans ride a SEPARATE ring from
    decision events: an armed long decode emits hundreds of span
    records, and they must not evict the sched_admit/preempt/breaker
    history the dump exists to preserve."""

    def __init__(self, name: str = "process",
                 capacity: int = _FLIGHT_CAPACITY):
        self.name = name
        self._ring: deque[dict] = deque(maxlen=max(capacity, 8))
        self._spans: deque[dict] = deque(maxlen=max(capacity, 8))
        self._lock = threading.Lock()
        self.dumps = 0          # SUCCESSFUL dumps only (health surfaces)
        self._seq = 0           # filename counter (attempts, unique)
        self.last_dump_path: str = ""

    def record(self, kind: str, **fields) -> None:
        entry = {"kind": kind, "at": round(time.time(), 3)}
        entry.update(fields)
        with self._lock:
            if kind == "span":
                self._spans.append(entry)
            else:
                self._ring.append(entry)

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._ring)

    def span_events(self) -> list[dict]:
        with self._lock:
            return list(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._spans.clear()

    def dump(self, trigger: str,
             extra: Optional[dict] = None) -> str:
        """Write both rings + a registry snapshot to the dump dir;
        returns the file path ('' when the write itself fails — a
        postmortem must never add a second failure on top of the
        first, and a failed write is NOT counted in `dumps`)."""
        payload = {
            "trigger": trigger,
            "recorder": self.name,
            "at": time.time(),
            "pid": os.getpid(),
            "events": self.events(),
            "spans": self.span_events(),
            "metrics": REGISTRY.snapshot(),
        }
        if extra:
            payload["extra"] = extra
        try:
            # Perf-attribution block (ISSUE 6): roofline/memory series,
            # span overheads, compile-observatory summary. Lazy import —
            # telemetry stays importable standalone, and an attribution
            # failure must never cost the postmortem its write.
            from . import perfmodel
            payload["perf"] = perfmodel.attribution_snapshot()
        except Exception:  # noqa: BLE001 — the dump itself comes first
            pass
        with self._lock:
            self._seq += 1
            seq = self._seq
        try:
            d = dump_dir()
            os.makedirs(d, exist_ok=True)
            path = os.path.join(
                d, f"flight-{trigger}-{os.getpid()}-{seq:03d}.json")
            with open(path, "w", encoding="utf-8") as f:
                json.dump(payload, f, indent=2, default=str)
            _prune_dumps(d)
        except OSError:
            return ""
        with self._lock:
            self.dumps += 1
            self.last_dump_path = path
        inc("roundtable_flight_dumps_total", trigger=trigger)
        return path


def _prune_dumps(d: str) -> None:
    """Keep only the newest _DUMP_KEEP flight dumps in `d` — every dump
    call pays one listdir so the dir can never grow without bound."""
    try:
        files = sorted(
            (p for p in os.listdir(d)
             if p.startswith("flight-") and p.endswith(".json")),
            key=lambda p: os.path.getmtime(os.path.join(d, p)))
        for p in files[:-_DUMP_KEEP] if _DUMP_KEEP > 0 else []:
            os.unlink(os.path.join(d, p))
    except OSError:
        pass  # pruning is best-effort; the dump already landed


_recorders: dict[str, FlightRecorder] = {}
_recorders_lock = threading.Lock()


def recorder(name: str = "process") -> FlightRecorder:
    """Get-or-create a named flight recorder ("process" is the shared
    default; engines may key their own by engine name)."""
    with _recorders_lock:
        rec = _recorders.get(name)
        if rec is None:
            rec = _recorders[name] = FlightRecorder(name)
        return rec


def flight_dump(trigger: str, name: str = "process",
                extra: Optional[dict] = None) -> str:
    """Dump a named recorder (default the process one); returns path."""
    return recorder(name).dump(trigger, extra=extra)


def last_dump_path() -> str:
    return recorder().last_dump_path


def dump_dir() -> str:
    """Where flight dumps land: ROUNDTABLE_TELEMETRY_DIR, else a
    uid-suffixed dir under the system tempdir (a hang must produce a
    dump even with no session directory in sight; the uid suffix keeps
    two users on one host from fighting over directory ownership —
    without it the second user's every dump would die on
    PermissionError and be silently swallowed)."""
    configured = os.environ.get("ROUNDTABLE_TELEMETRY_DIR")
    if configured:
        return configured
    uid = os.getuid() if hasattr(os, "getuid") else "na"
    return os.path.join(tempfile.gettempdir(),
                        f"roundtable-telemetry-{uid}")


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------

_tls = threading.local()

# Emitted-span counter (tests/conftest.py `telemetry` marker guard: a
# marked test that claims span coverage must actually emit spans).
_spans_emitted = 0
_spans_lock = threading.Lock()


def spans_emitted() -> int:
    return _spans_emitted


def reset_spans_emitted() -> None:
    global _spans_emitted
    with _spans_lock:
        _spans_emitted = 0


def _keep_span(record: dict) -> None:
    """A finished span's record into the armed buffer, and the count of
    spans emitted — one critical section for both."""
    global _spans_emitted, _span_buffer_dropped
    with _spans_lock:
        _spans_emitted += 1
        if len(_span_buffer) == _span_buffer.maxlen:
            _span_buffer_dropped += 1
        _span_buffer.append(record)


def spans_between(t_a: float, t_b: float) -> list[dict]:
    """The buffered records of spans that STARTED in [t_a, t_b), both
    read on time.monotonic() (a record's `t0`). A span is buffered when
    it ends, so one still open is not there yet."""
    with _spans_lock:
        records = list(_span_buffer)
    return [r for r in records if t_a <= r["t0"] < t_b]


def spans_dropped() -> int:
    """Records the bounded buffer has pushed out since it was opened:
    a reader that finds this above zero does not have every span."""
    return _span_buffer_dropped


def _stack() -> list:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


class SpanSink:
    """Append-only JSONL span sink (one per session: the root span
    carries it and children inherit — per-session files work across the
    thread hops the serving stack makes)."""

    def __init__(self, path: str):
        self.path = str(path)
        self._lock = threading.Lock()

    def write(self, record: dict) -> None:
        try:
            with self._lock:
                os.makedirs(os.path.dirname(self.path), exist_ok=True)
                with open(self.path, "a", encoding="utf-8") as f:
                    f.write(json.dumps(record, default=str) + "\n")
        except OSError:
            pass  # telemetry must never kill serving


def session_sink(session_path) -> SpanSink:
    """The per-session spans file: <session>/telemetry/spans.jsonl."""
    return SpanSink(os.path.join(str(session_path), "telemetry",
                                 "spans.jsonl"))


def _open_annotation(name: str):
    """Mirror a span into the device profile as `rt:<name>`: xprof rows
    named like the JSONL rungs. Lazy import; any failure silently drops
    the mirror (profiling is best-effort by standing contract)."""
    try:
        import jax
        annotation = jax.profiler.TraceAnnotation(f"rt:{name}")
        annotation.__enter__()
        return annotation
    except Exception:  # noqa: BLE001 — mirror is best-effort
        return None


def _close_annotation(annotation) -> None:
    try:
        annotation.__exit__(None, None, None)
    except Exception:  # noqa: BLE001
        pass


def _span_record(trace_id: str, span_id: str, parent_id: str, rung: str,
                 wall0: float, t0: float, dur_s: float, status: str,
                 attrs: dict) -> dict:
    """A finished span as it is written everywhere: `start` on the
    wall clock, `t0` on time.monotonic()."""
    record = {
        "trace_id": trace_id, "span_id": span_id, "parent_id": parent_id,
        "rung": rung, "start": round(wall0, 6), "t0": t0,
        "dur_s": round(dur_s, 6), "status": status,
    }
    if attrs:
        record["attrs"] = attrs
    return record


class Span:
    """One span of the trace tree. Context manager for the common
    same-thread case; `start_span()`/`.end()` for holders that outlive
    a lexical scope (the scheduler's per-request turn spans).

    Only a LEXICAL span (entered with `with`) mirrors into the device
    profile, and none of `UNMIRRORED_RUNGS` does: a profiler annotation
    is meant to nest on its thread, and
    a span held across scheduler ticks (`request`, `resume`, `turn`)
    would lie open over every instant in which any row is live and win
    every idle gap no inner span covers. Held spans keep their records,
    ring entries and ids."""

    __slots__ = ("rung", "trace_id", "span_id", "parent_id", "attrs",
                 "sink", "t0", "_wall0", "status", "_annotation",
                 "_on_stack", "_dur")

    def __init__(self, rung: str, trace_id: str, parent_id: str,
                 sink: Optional[SpanSink], attrs: dict[str, Any]):
        self.rung = rung
        self.trace_id = trace_id
        self.span_id = uuid.uuid4().hex[:12]
        self.parent_id = parent_id
        self.attrs = attrs
        self.sink = sink
        self.t0 = time.monotonic()
        self._wall0 = time.time()
        self.status = "ok"
        self._annotation = None
        self._on_stack = False
        self._dur: Optional[float] = None

    def set_attr(self, key: str, value: Any) -> None:
        self.attrs[key] = value

    # --- context-manager protocol (same-thread nesting) ---

    def __enter__(self) -> "Span":
        _stack().append(self)
        self._on_stack = True
        if _PROFILING and self.rung not in UNMIRRORED_RUNGS:
            self._annotation = _open_annotation(self.rung)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc is not None:
            self.status = f"error:{type(exc).__name__}"
        self.end()
        return False

    def leave(self) -> None:
        """End the span's STRETCH — off the stack, the profiler mirror
        closed, the duration fixed — without emitting it yet: a later
        end() writes the record, so counts that are only known after
        the timed stretch (what a segment's accept walk committed) ride
        the span they belong to without stretching it over that work."""
        if self._dur is not None:
            return
        self._dur = time.monotonic() - self.t0
        if self._on_stack:
            stack = _stack()
            if stack and stack[-1] is self:
                stack.pop()
            elif self in stack:  # unbalanced exit: drop it anyway
                stack.remove(self)
            self._on_stack = False
        if self._annotation is not None:
            _close_annotation(self._annotation)
            self._annotation = None

    def end(self, status: Optional[str] = None) -> None:
        if status is not None:
            self.status = status
        self.leave()
        record = _span_record(self.trace_id, self.span_id, self.parent_id,
                              self.rung, self._wall0, self.t0, self._dur,
                              self.status, self.attrs)
        if self.sink is not None:
            self.sink.write(record)
        ring = {"rung": self.rung, "trace_id": self.trace_id,
                "span_id": self.span_id, "parent_id": self.parent_id,
                "dur_s": record["dur_s"], "status": self.status}
        for k, v in self.attrs.items():
            if k not in ("kind", "at") and isinstance(
                    v, (str, int, float, bool)):
                ring.setdefault(k, v)
        recorder().record("span", **ring)
        _keep_span(record)


class _NullSpan:
    """The disarmed singleton: every operation a no-op, reentrant and
    thread-safe because it holds no state."""

    __slots__ = ()
    rung = ""
    trace_id = span_id = parent_id = ""
    sink = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_attr(self, key, value):
        pass

    def leave(self):
        pass

    def end(self, status=None):
        pass


NULL_SPAN = _NullSpan()


class _AttachedContext:
    """A foreign span context installed on this thread's stack so spans
    opened here parent correctly across a thread hop (orchestrator
    batch pools, scheduler submitters). Not emitted on exit — the real
    span lives on its own thread."""

    __slots__ = ("trace_id", "span_id", "sink", "rung")

    def __init__(self, ctx: dict):
        self.trace_id = ctx.get("trace_id", "")
        self.span_id = ctx.get("span_id", "")
        self.rung = ctx.get("rung", "")
        sink = ctx.get("sink")
        self.sink = sink if isinstance(sink, SpanSink) else None


def current_context() -> Optional[dict]:
    """A picklable-ish handle to the innermost span, for handing across
    threads: `ctx = telemetry.current_context()` on the parent thread,
    `with telemetry.attached(ctx):` on the worker."""
    stack = _stack()
    if not stack:
        return None
    top = stack[-1]
    return {"trace_id": top.trace_id, "span_id": top.span_id,
            "rung": top.rung, "sink": top.sink}


class attached:
    """Context manager installing a foreign span context as this
    thread's parent. A None ctx is a no-op (callers pass
    current_context()'s result straight through)."""

    def __init__(self, ctx: Optional[dict]):
        self._ctx = ctx
        self._pushed = None

    def __enter__(self):
        if ACTIVE and self._ctx:
            self._pushed = _AttachedContext(self._ctx)
            _stack().append(self._pushed)
        return self

    def __exit__(self, *exc):
        if self._pushed is not None:
            stack = _stack()
            if stack and stack[-1] is self._pushed:
                stack.pop()
            elif self._pushed in stack:
                stack.remove(self._pushed)
            self._pushed = None
        return False


def span(rung: str, sink: Optional[SpanSink] = None,
         parent: Optional[dict] = None, **attrs):
    """Open a span at `rung`. Disarmed: the no-op singleton (call sites
    on hot paths additionally pre-guard with `if telemetry.ACTIVE:`).
    Armed: parented to `parent` (a current_context() dict) when given,
    else this thread's innermost span; roots mint a fresh trace id.
    `sink` overrides the inherited JSONL sink (roots set it)."""
    if not ACTIVE:
        return NULL_SPAN
    return start_span(rung, sink=sink, parent=parent, **attrs)


def start_span(rung: str, sink: Optional[SpanSink] = None,
               parent: Optional[dict] = None, **attrs) -> Span:
    """Like span() but always real (callers that hold a span across
    ticks and end() it manually — check ACTIVE yourself)."""
    if parent is not None:
        trace_id = parent.get("trace_id") or uuid.uuid4().hex[:16]
        parent_id = parent.get("span_id", "")
        psink = parent.get("sink")
        inherited = psink if isinstance(psink, SpanSink) else None
    else:
        stack = _stack()
        top = stack[-1] if stack else None
        trace_id = top.trace_id if top else uuid.uuid4().hex[:16]
        parent_id = top.span_id if top else ""
        inherited = top.sink if top else None
    return Span(rung, trace_id, parent_id,
                sink if sink is not None else inherited, attrs)


def emit_span(rung: str, dur_s: float, **attrs) -> None:
    """Record a span that has just ENDED and took `dur_s` — for work
    that is only known once it is over (a compile, reported by JAX's
    monitoring hook with its duration). Parented to this thread's
    innermost span; it goes to that span's sink and to the armed
    buffer, not to the flight ring (its caller records the event
    there) and not to the profiler, which has its own rows for such
    work. Call sites pre-guard with `if telemetry.ACTIVE:`."""
    emit_span_at(rung, time.monotonic() - dur_s, dur_s, **attrs)


def current_span_ids() -> Optional[tuple[str, str]]:
    """(trace id, span id) of this thread's innermost open span, for a
    caller that records under it later (`emit_span_at(parent=...)`)."""
    stack = getattr(_tls, "stack", None)
    return (stack[-1].trace_id, stack[-1].span_id) if stack else None


def emit_span_at(rung: str, t0: float, dur_s: float, wait: bool = True,
                 parent: Optional[tuple[str, str]] = None,
                 **attrs) -> bool:
    """`emit_span` for a span that began at `t0` (time.monotonic()) and
    is over: one whose extent is known only some time after its end
    (the outermost `trace` and `lower` intervals of a program, known
    when it compiles). `wait=False` is for a caller that may not wait
    for a lock — the collector's callback runs between any two
    bytecodes of the thread it stopped, perhaps inside `_keep_span`
    itself: the record then goes to the armed buffer alone, under
    `parent`, and False means the buffer's lock was held and nothing
    was recorded."""
    if not wait and _spans_lock.locked():
        # Perhaps by the very frame the collector stopped: waiting
        # could be waiting for oneself. (Free now means this thread
        # does not hold it, so _keep_span below cannot.)
        return False
    stack = _stack() if wait else ()
    top = stack[-1] if stack else None
    if top is not None:
        parent = (top.trace_id, top.span_id)
    trace_id, parent_id = parent or (uuid.uuid4().hex[:16], "")
    record = _span_record(
        trace_id, uuid.uuid4().hex[:12], parent_id, rung,
        time.time() - (time.monotonic() - t0), t0, dur_s, "ok", attrs)
    if top is not None and top.sink is not None:
        top.sink.write(record)
    _keep_span(record)
    return True


# ---------------------------------------------------------------------------
# loop clock
# ---------------------------------------------------------------------------


class LoopClock:
    """Which phase one loop thread is in, at every instant — and whether
    the device has work of this loop's outstanding.

    The idiom of `tracing.RequestTrace.stage()`: a mark attributes the
    time since the previous mark, so the phases telescope to the
    thread's wall by construction — no instant lies under two phases
    or under none. The owner (the session scheduler) marks its own
    phases with `mark()`; the two seams every dispatch and every
    blocking read pass through (`serving_loop.run_dispatch`,
    `host_sync`) find the clock of their thread with `loop_clock()`,
    `switch()` to their phase and `mark()` back, so they need to know
    nothing of the loop that called them. `within` renames a seam's
    phase by the phase it is entered from (a blocking read inside
    admission is `admit_sync`, not `sync`).

    The feed bit (ISSUE 37): the loop tells the clock where it holds
    the handles. `feed()` when a dispatch that issued a step program
    has returned (→ that program's ticket), `drain(ticket)` when the
    blocking read of its result has returned. The device runs one
    loop's programs in the order they were issued, so a read drains
    every ticket up to its own; the clock is `fed` while a ticket is
    outstanding — a count of handles, not a flag: a pipelined loop
    issues the next segment before it reads the current one, and that
    read leaves it fed. A program nothing reads back (a page copy)
    takes no ticket. Unfed time is time the device can only have spent
    idle for want of work from this loop.

    Always on: `seconds`, per-phase lifetime totals, and `starved`,
    the part of each spent unfed (a clock read and a float add or two
    per mark; one clock read more where the bit flips). Armed
    (`ACTIVE`): each stretch of a phase is also a span record
    `loop.<phase>` carrying the tick's index and `fed` (0 | 1) — a
    stretch also ends where the bit flips, so each is wholly one or
    the other — in the armed buffer only: ten a tick would push the
    flight ring's request and segment spans out — and, while a profile
    is taken, an `rt:loop.<phase>` annotation on the loop's thread.
    The stretches lie end to end, each starting on the clock read that
    ended the one before, so clipped to any stretch of time they sum
    to it."""

    __slots__ = ("seconds", "starved", "phase", "tick", "fed", "_issued",
                 "_drained", "_within", "_last", "_attrs", "_trace_id",
                 "_open")

    def __init__(self, phases: tuple[str, ...], start: str,
                 within: Optional[dict[str, dict[str, str]]] = None,
                 **attrs) -> None:
        self.seconds: dict[str, float] = dict.fromkeys(phases, 0.0)
        self.starved: dict[str, float] = dict.fromkeys(phases, 0.0)
        self.phase = start
        self.tick = 0
        self.fed = False
        self._issued = self._drained = 0
        self._within = within or {}
        self._last = time.monotonic()
        self._attrs = attrs
        self._trace_id = uuid.uuid4().hex[:16]
        # The open stretch's span: (phase, tick, fed, t0, wall0, mirror).
        self._open: Optional[tuple] = None

    def _lap(self) -> float:
        """Attribute the time since the last clock read to the open
        phase (and to its starved part while unfed); → now."""
        now = time.monotonic()
        gained = now - self._last
        self.seconds[self.phase] += gained
        if not self.fed:
            self.starved[self.phase] += gained
        self._last = now
        return now

    def mark(self, phase: str) -> None:
        """Everything since the last mark was the phase that was open;
        from here on it is `phase`."""
        now = self._lap()
        changed = phase != self.phase
        self.phase = phase
        if self._open is None:
            if ACTIVE:              # armed since the last mark, or new
                self._respan(now)
        elif changed or not ACTIVE:
            self._respan(now)

    def switch(self, phase: str) -> str:
        """A seam's mark: enter `phase` (as `within` renames it for the
        phase it is entered from); → the phase to mark() back to."""
        prev = self.phase
        renames = self._within.get(prev)
        self.mark(renames.get(phase, phase) if renames else phase)
        return prev

    def feed(self) -> int:
        """A dispatch that issued a step program has returned, and this
        loop will read the program's result: → its ticket."""
        self._issued += 1
        if not self.fed:
            self._flip(True)
        return self._issued

    def drain(self, ticket: Optional[int] = None) -> None:
        """The blocking read of `ticket`'s result has returned (None:
        of the last one issued — also what a failed dispatch's handler
        calls, whose handles nobody will read). Programs end in the
        order they were issued, so every earlier ticket is drained
        with it."""
        if ticket is None or ticket > self._drained:
            self._drained = self._issued if ticket is None else ticket
        if self.fed and self._drained >= self._issued:
            self._flip(False)

    def _flip(self, fed: bool) -> None:
        now = self._lap()
        self.fed = fed
        if self._open is not None or ACTIVE:
            self._respan(now)

    def snapshots(self) -> tuple[dict[str, float], dict[str, float]]:
        """(`seconds`, `starved`) by phase, lifetime, the open phase's
        counted up to now — read from other threads (describe()); a
        read that races a mark is off by that one lap at most. One
        clock read serves both and `starved` is copied first, so for
        every phase starved <= seconds."""
        starved = dict(self.starved)
        seconds = dict(self.seconds)
        phase, fed = self.phase, self.fed
        open_s = max(time.monotonic() - self._last, 0.0)
        seconds[phase] += open_s
        if not fed:
            starved[phase] += open_s
        return ({k: round(v, 6) for k, v in seconds.items()},
                {k: round(min(v, seconds[k]), 6)
                 for k, v in starved.items()})

    def snapshot(self) -> dict[str, float]:
        """Per-phase lifetime seconds (`snapshots()[0]`)."""
        return self.snapshots()[0]

    def _respan(self, now: float) -> None:
        """Close the open stretch's span at `now` and, while armed,
        open the next one (the phase and feed bit as they stand) at the
        same instant."""
        if self._open is not None:
            phase, tick, fed, t0, wall0, mirror = self._open
            self._open = None
            if mirror is not None:
                _close_annotation(mirror)
            _keep_span(_span_record(
                self._trace_id, uuid.uuid4().hex[:12], "",
                "loop." + phase, wall0, t0, now - t0, "ok",
                dict(self._attrs, tick=tick, fed=fed)))
        if ACTIVE:
            self._open = (
                self.phase, self.tick, int(self.fed), now, time.time(),
                _open_annotation("loop." + self.phase)
                if _PROFILING else None)


def bind_loop_clock(clock: Optional[LoopClock]) -> None:
    """Make `clock` the calling thread's loop clock (None unbinds)."""
    _tls.clock = clock


def loop_clock() -> Optional[LoopClock]:
    """The calling thread's loop clock, if it runs a clocked loop."""
    return getattr(_tls, "clock", None)


# ---------------------------------------------------------------------------
# observability-surface bindings (single-source-of-truth drift lint)
# ---------------------------------------------------------------------------

# Every key an observability surface exposes maps to the registry
# series (or derivation) that backs it. The drift test
# (tests/test_telemetry.py) asserts the ACTUAL keys of fleet_health()
# and SessionScheduler.describe() are a subset of these — adding a new
# surface key without declaring how the registry sees it fails CI, so
# the four stores can never quietly fork again. The static analyzer
# enforces the same contract at parse time with file/line findings
# (`roundtable lint`, rule RT-SURFACE-DRIFT — it reads this dict
# LITERAL, so keep it a plain literal of string keys).
SURFACE_BINDINGS: dict[str, dict[str, str]] = {
    "fleet_health": {
        "engines": "roundtable_breaker_failures_total{engine=...} "
                   "(per-breaker snapshots; trips under "
                   "roundtable_breaker_trips_total)",
        "total": "len(engines)",
        "open": "roundtable_breaker_open{engine=...} gauge",
        "degraded": "derived from breaker snapshots",
        "draining": "roundtable_draining gauge",
        "hangs": "roundtable_hangs_total",
        "schedulers": "roundtable_sched_* series, engine-labeled",
        "queued_sessions": "roundtable_sched_queue_depth gauge sum",
        "telemetry": "registry snapshot view (this module)",
        "perf": "roundtable_compiles_total / "
                "roundtable_steady_state_compiles_total series "
                "(engine/compile_watch summary roll-up)",
        # ISSUE 12: the supervisor's restart history roll-up —
        # counters move in lockstep with EngineSupervisor._finish /
        # _mark_dead (the single writers for both stores).
        "supervisor": "roundtable_engine_restarts_total{reason=...} / "
                      "roundtable_engine_restart_seconds / "
                      "roundtable_sessions_recovered_total / "
                      "roundtable_sessions_lost_total / "
                      "roundtable_engine_dead gauge "
                      "(engine/supervisor snapshot)",
        # ISSUE 17: the session router's fleet view (None without a
        # router) — assignment counts + migration/failover/roll
        # counters, replica-labeled, dropped at retire.
        "router": "roundtable_router_sessions{replica=...} gauge / "
                  "roundtable_router_migrations_total / "
                  "roundtable_router_failovers_total / "
                  "roundtable_router_rolls_total "
                  "(router/core SessionRouter.describe)",
    },
    "scheduler_describe": {
        "admitted": "roundtable_sched_admitted_total",
        "refused": "roundtable_sched_refused_total",
        "completed": "roundtable_sched_completed_total",
        "failed": "roundtable_sched_failed_total",
        "rejected_draining": "roundtable_sched_rejected_draining_total",
        "rejected_other": "roundtable_sched_rejected_other_total",
        "deadline_expired": "roundtable_sched_deadline_expired_total",
        "preemptions": "roundtable_sched_preemptions_total",
        "segments": "roundtable_sched_segments_total",
        "ragged_segments": "roundtable_sched_ragged_segments_total",
        "ragged_joins": "roundtable_sched_ragged_joins_total",
        "indexed_in_flight": "roundtable_sched_indexed_in_flight_total",
        "indexed_at_draft": "roundtable_sched_indexed_at_draft_total",
        "spec_segments": "roundtable_sched_spec_segments_total",
        "probe_intervals": "derived (the live rows' own re-probe "
                           "intervals, committed tokens -> rows; 0 = "
                           "not throttled; describe-only)",
        "segment_prefill_tokens":
            "roundtable_segment_prefill_tokens_total",
        "segment_decode_tokens":
            "roundtable_segment_decode_tokens_total",
        # ISSUE 57: the join dispatches by flat-buffer shape
        # (scheduler._note_ragged_fill is the one writer).
        "ragged_fill": "roundtable_ragged_buffer_tokens_total{shape=...} "
                       "/ roundtable_ragged_real_tokens_total{shape=...} "
                       "(dispatches: describe-only)",
        "requeues": "roundtable_sched_requeues_total",
        "queued": "roundtable_sched_queue_depth gauge",
        "queued_peak": "max over roundtable_sched_queue_depth",
        "active_rows": "roundtable_sched_active_rows gauge",
        "max_occupancy": "max over roundtable_sched_occupancy gauge",
        "occupancy_mean": "mean over roundtable_sched_occupancy gauge",
        "occupancy_recent": "ring view (flight recorder carries events)",
        "spills": "roundtable_sched_spills_total",
        "spilled_sessions": "roundtable_kv_spilled_sessions gauge "
                            "(kv_offload tier)",
        # ISSUE 12: admission-gate + durable-journal provenance.
        "paused": "pause_admission/reopen_admission flight events "
                  "(gate reason string; None = open)",
        # ISSUE 16: machine-readable admission state for the gateway's
        # shed ladder (nested dict; the pause reason + queue depth).
        "admission": "derived (paused reason + "
                     "roundtable_sched_queue_depth gauge)",
        "journal_turns": "roundtable_journal_turns_total "
                         "(counter is fleet-wide; the describe key is "
                         "THIS scheduler's share)",
        "journal_errors": "roundtable_journal_errors_total "
                          "(same per-scheduler split)",
        # ISSUE 25: the loop clock — which phase the scheduler's thread
        # was in, lifetime seconds per phase; the series moves at the
        # end of every tick by what the clock gained since the last.
        "loop_seconds": "roundtable_sched_loop_seconds_total"
                        "{phase=...}",
        # ISSUE 37: the part of each phase the loop spent with no step
        # program of its own outstanding on the device (the clock's
        # feed bit); moves with loop_seconds, by the same rule.
        "loop_starved_seconds":
            "roundtable_sched_starved_seconds_total{phase=...}",
        "events": "flight recorder ring (sched_* kinds)",
    },
    # engine.describe()["spec_decode"] (ISSUE 9 + 13): the speculation
    # provenance sink's registry bindings — drafted/accepted/rejected
    # counters move in lockstep with the describe() totals
    # (engine.note_spec_dispatch is the one writer for both). ISSUE 13:
    # every counter/gauge carries a `drafter` label (ngram|model|lora)
    # so dashboards attribute an acceptance collapse to the PROPOSER,
    # not the throttle; the active drafter + tree shape ride describe()
    # so a snapshot says which proposer produced the numbers.
    "engine_spec_decode": {
        "drafter": "label value on every roundtable_spec_* series",
        "drafter_reason": "derived (drafter-availability fallback; "
                          "describe-only)",
        "tree": "static config (branch x depth); labels "
                "roundtable_spec_tree_nodes_total",
        "drafted_tokens": "roundtable_spec_drafted_tokens_total"
                          "{drafter=...}",
        "accepted_tokens": "roundtable_spec_accepted_tokens_total"
                           "{drafter=...}",
        "rejected_tokens": "roundtable_spec_rejected_tokens_total"
                           "{drafter=...}",
        "acceptance_rate": "roundtable_spec_acceptance_rate gauge "
                           "(per-drafter: labeled with the drafter "
                           "whose dispatches moved it)",
        "by_drafter": "per-drafter split of the drafted/accepted "
                      "counters (same writer)",
        "throttled_rows": "spec_throttle flight events (one per trip)",
        "probes": "derived (verifies that were the batch throttle's "
                  "re-probe; `probes` on the spec `segment` spans)",
        "probes_accepted_none": "derived (probes in which its rows "
                                "accepted no drafted token; "
                                "`probes_accepted_none` on the spec "
                                "`segment` spans)",
        "probes_backed_off": "derived (ticks on which the batch "
                             "throttle kept its rows from being asked "
                             "for drafts; describe-only)",
        "probe_interval": "derived (decode steps between the batch "
                          "throttle's probes, 0 = not throttled; "
                          "describe-only)",
        "tree_nodes": "roundtable_spec_tree_nodes_total{drafter=...}",
        "tree_rows": "derived (tree-row share of verify dispatches)",
        "draft_dispatches": "ragged provenance ring entries with "
                            "draft=True (DeviceDrafter counter)",
        "verify_dispatches": "roundtable_sched_spec_segments_total "
                             "(+ warmup dispatches)",
    },
    # engine.describe()["lora"] (ISSUE 10): the multi-LoRA persona
    # provenance sink's registry bindings — residency/swap counters
    # move in lockstep with the store's describe() totals (LoraStore
    # load/evict and engine.note_lora_tokens are the single writers).
    "engine_lora": {
        "apply_tokens": "roundtable_lora_apply_tokens_total",
        "swaps": "roundtable_lora_swaps_total",
        "resident": "roundtable_lora_resident_adapters gauge",
        "adapter_bytes": "roundtable_lora_adapter_bytes{adapter=...} "
                         "gauge (REMOVED at evict)",
        "stack_bytes": "roundtable_lora_stack_bytes gauge "
                       "(memory-ledger publish)",
        "share_suppressed": "derived (engine counter; lora_describe)",
    },
    # engine.describe()["hybrid_state"] (ISSUE 27): recurrent state
    # beside the pools — slot states and the snapshot store
    # (engine/hybrid_state.HybridStateStore is the one writer of both
    # the describe() totals and the series). No per-session series:
    # nothing to remove at retire.
    "engine_hybrid_state": {
        "slots": "derived (state rows in use; describe-only)",
        "slot_rows": "static config (num_slots)",
        "bytes_per_state": "static (model sizes)",
        "snapshots": "roundtable_state_snapshots gauge",
        "snapshot_capacity": "static (state_snapshot_bytes / state)",
        "bytes": "roundtable_state_snapshot_bytes gauge",
        "budget": "static config (state_snapshot_bytes)",
        "hits": "roundtable_state_admissions_total"
                "{source=continue|snapshot}",
        "misses": "roundtable_state_admissions_total{source=zero}",
        "evictions": "roundtable_state_snapshot_evictions_total",
        "snapshots_taken": "roundtable_state_snapshots_total",
        "continued_tokens": "derived (plan totals; describe-only)",
        "reused_tokens": "derived (plan totals; describe-only)",
        "rescanned_tokens": "roundtable_state_rescanned_tokens_total "
                            "(over roundtable_state_prompt_tokens_total)",
        # ISSUE 48: the leader pass of a model with state — laggards
        # that started from the state their leader handed on at a page
        # boundary, and those that scanned the span themselves.
        "share_handed": "roundtable_state_share_handed_total",
        "share_declined": "roundtable_state_share_declined_total"
                          "{reason=prologue|no-state-to-hand|"
                          "state-not-left|dropped}",
        # ISSUE 42: whole states written into slot rows by a restore
        # and into the store by the programs' captures, in bytes
        # (HybridStateStore._note_copy is the one writer; an `admit`
        # span carries its own restore, a `segment` span the captures
        # of the programs it covers).
        "restore_bytes": "roundtable_state_copy_bytes_total"
                         "{cause=restore}",
        "capture_bytes": "roundtable_state_copy_bytes_total"
                         "{cause=capture}",
        "deduped_pages": "derived (prefix_cache.insert: re-written "
                         "pages given back for the index's; "
                         "describe-only)",
    },
    # engine.describe()["mamba1"] (ISSUE 47): the Mamba-1 layers of a
    # model that has them (models/mamba1.py), their scanned runs, and
    # what the join programs scanned (HybridStateStore.note_scan is the
    # one writer; a `segment` span carries `scan_tokens` for the
    # programs it covers).
    "engine_mamba1": {
        "layers": "static (Mamba-1 layers)",
        "d_inner": "static (model sizes)",
        "d_state": "static (model sizes)",
        "bytes_per_state": "static (a layer's state and conv tail)",
        "state_layout": "static (the state leaf of a scanned run)",
        "kernel": "static (mamba1_scan, or jnp where it declines)",
        "scan_runs": "static (lengths of the scanned runs)",
        "scan_tokens": "roundtable_mamba1_scan_tokens_total",
    },
    # engine.describe()["seam"] (ISSUE 56): a model whose upper layers
    # keep nothing (`ModelConfig.last_token_from`). What its join
    # programs ran below the seam (tokens) and above it (rows: each
    # sequence's last token), the rows of the memory carried across, and
    # the positions the cross layers read of the pool they do not own —
    # joins and decode steps both (HybridStateStore.note_join and
    # note_shared_reads are the writers; a `segment` span carries each
    # count for the programs it covers).
    "engine_seam": {
        "from_layer": "static (the first layer above the seam)",
        "layers_above": "static (layers that keep nothing)",
        "cross_layers": "static (layers that read another's pages)",
        "memory_layer": "static (the layer whose scan output rides up)",
        "lower_tokens": "roundtable_seam_lower_tokens_total",
        "upper_rows": "roundtable_seam_upper_rows_total",
        "memory_rows": "roundtable_seam_memory_rows_total",
        "shared_pool_positions":
            "roundtable_seam_shared_pool_positions_total",
    },
    # engine.describe()["shortconv"] (ISSUE 52): the gated
    # short-convolution layers of a model that has them
    # (models/shortconv.py) and the tokens the join programs ran through
    # them (HybridStateStore.note_scan is the one writer; a `segment`
    # span carries `conv_tokens` for the programs it covers).
    "engine_shortconv": {
        "layers": "static (short-convolution layers)",
        "channels": "static (model sizes)",
        "taps": "static (model sizes)",
        "bytes_per_state": "static (a layer's tail: taps - 1 rows)",
        "state_dtype": "static (the dtype the tail is kept in)",
        "conv_tokens": "roundtable_shortconv_tokens_total",
    },
    # engine.describe()["moe"] (ISSUE 27): the chip's share of the
    # routed experts and what the steps touched (each step program
    # returns its counts; HybridStateStore.fold_counts adds them to
    # host ints and these series on the dispatching thread).
    "engine_moe": {
        "held": "static (experts held here)",
        "published": "static (experts the router scores)",
        "offset": "static (first held expert's published id)",
        "top_k": "static (experts a token)",
        "router_rule": "static (models/hybrid.py: route)",
        "shared_expert": "static (an expert every token takes, or none)",
        "expert_layers": "static (layer pattern)",
        "experts_hit": "roundtable_moe_experts_hit_total",
        "local_assignments": "roundtable_moe_local_assignments_total",
        "expert_layer_steps": "roundtable_moe_expert_layer_steps_total",
        # ISSUE 36: the routed experts multiply the rows that chose
        # them. Which form serves (the Pallas grouped matmul, or
        # lax.ragged_dot with the reason in describe()["declines"]),
        # and the rows it multiplied beside the rows a loop over every
        # held expert would have (tokens x held, pads included).
        "grouped_product": "static (kernel | ragged_dot; "
                           "declines.grouped_product says why)",
        "rows_multiplied": "roundtable_moe_rows_multiplied_total",
        "rows_dense": "roundtable_moe_rows_dense_total",
    },
    # engine.describe()["paging"] (ISSUE 38): the page cache's own
    # counts. A page copy is queued on the cache and issued with
    # whatever else is pending before the next program that takes the
    # pools (PagedKVCache._run_page_copy is the one writer of the
    # copies and their series, _issue_pending of the programs and
    # theirs); copies over programs says how much a flush gathers.
    "engine_paging": {
        "pages_allocated": "describe-only (pages handed out, lifetime; "
                           "a `plan` span carries its admission's)",
        "page_copies": "roundtable_page_copies_total (every cause)",
        "page_copies_by_cause":
            "roundtable_page_copies_total{cause=alias|share|cow}",
        "page_copy_programs": "roundtable_page_copy_programs_total "
                              "(every path)",
        "page_copy_path": "static (\"dma\": pallas/page_copy.py; or why "
                          "that declined and XLA's gather and scatter "
                          "runs — describe()[\"declines\"][\"page_copy\"])",
        "page_copy_programs_by_path":
            "roundtable_page_copy_programs_total{path=...}",
        "copy_widths": "static (paging.COPY_WIDTHS, each compiled in "
                       "warmup)",
    },
    # engine.describe()["dispatch"] (ISSUE 53): what the step seams
    # sent and issued (serving_loop.note_issue is the one writer of the
    # totals and the series; a `dispatch` span carries its own
    # `host_buffers` and `launches`). One buffer and one launch a
    # program where a dispatch is packed (engine/dispatch_pack.py).
    "engine_dispatch": {
        "programs": "roundtable_dispatch_programs_total (step programs "
                    "and the prologue's sampler)",
        "host_buffers": "roundtable_dispatch_host_buffers_total "
                        "(host-to-device transfers made for them)",
        "launches": "roundtable_dispatch_launches_total (device "
                    "programs issued, the step program among them)",
    },
    # engine.describe()["compile_observatory"]["setup"] (ISSUE 54): the
    # set-up table of engine/compile_watch.py, which is the one writer
    # of both series — thread-seconds by stage (trace, lower, retrieve,
    # compile) as JAX's monitoring events report them, wall seconds by
    # phase (init, quantize, pools, warm_programs, warm_traffic) as
    # `compile_watch.phase` marks them, from install() to the close.
    "engine_setup": {
        "closed": "derived (warmup_complete closed the table; "
                  "describe-only)",
        "wall_s": "derived (install() to the close, or to now; "
                  "describe-only)",
        "stages": "roundtable_setup_seconds_total{stage=trace|lower|"
                  "retrieve|compile}",
        "phases": "roundtable_setup_seconds_total{stage=init|quantize|"
                  "pools|warm_programs|warm_traffic}",
        "staged": "derived (the stages' seconds heard inside each "
                  "phase; describe-only)",
        "programs": "derived (lowerings heard; describe-only: "
                    "hits + misses + lowerings never compiled)",
        "bodies_traced": "roundtable_setup_bodies_total{outcome=traced} "
                         "(calls of a jitted layer body that traced it: "
                         "models/common.layer_body)",
        "bodies_reused": "roundtable_setup_bodies_total{outcome=reused} "
                         "(calls that found it in JAX's trace cache)",
        "cache_hits": "roundtable_setup_programs_total{outcome=hit}",
        "cache_misses": "roundtable_setup_programs_total{outcome=miss}",
        "saved_s": "derived (jax's compile_time_saved_sec summed; "
                   "describe-only)",
        "misses": "derived (labels compiled fresh, at most 32; "
                  "describe-only)",
        "twice": "derived (fun_names lowered more than once; "
                 "describe-only)",
        "slowest": "derived (the eight rows with most seconds; "
                   "describe-only)",
    },
    # engine.describe()["gc"] (ISSUE 54): the collector's pauses by
    # generation since install() — compile_watch's gc.callbacks hook
    # sums them lock-free, gc_report() publishes both series.
    "engine_gc": {
        "pauses": "roundtable_gc_collections_total{generation=...}",
        "seconds": "roundtable_gc_pause_seconds_total{generation=...}",
        "longest_s": "derived (the longest single collection; "
                     "describe-only)",
    },
    # engine.describe()["mla"] (ISSUE 31): latent pages — the second
    # page shape (engine/paging.py) — and the kernels that read them.
    # Static but for the positions read, which the scheduler's segment
    # fold counts (engine.note_latent_positions is the one writer of the
    # total and the series; the segment span carries its own share).
    "engine_mla": {
        "pool_shape": "static (one pool a layer: [P, ps, page_width])",
        "pools_per_layer": "static (1: no value pool)",
        "layers": "static (attention layers)",
        "entry_width": "static (kv_lora_rank + qk_rope_head_dim)",
        "page_width": "static (entry_width in whole lane rows)",
        "bytes_per_position_published": "static (entry_width cells)",
        "bytes_per_position_stored": "static (page_width cells)",
        "form": "static (absorbed wherever pages are read)",
        "prologue_form": "static (absorbed)",
        "paged_decode": "static (the kernel's name, or gather-view)",
        "paged_prefill": "static (the kernel's name, or gather-view)",
        "ragged": "static (the kernel's name, or the fallback path)",
        "decode_decline": "static (paged_decode_decline_reason)",
        "ragged_decline": "static (engine.ragged_fallback_reason)",
        "latent_positions": "roundtable_mla_latent_positions_total",
    },
    # engine.describe()["attention"] (ISSUE 33): attention layers that
    # differ from one another over one page pool. Static but for what
    # the segments' attention read by layer class, in page visits
    # (engine._note_window_reads is the one writer of the two totals
    # and their series; every `segment` span carries its own share).
    "engine_attention": {
        "kv_heads": "static (the pools' heads, every layer's)",
        "head_dim": "static",
        "gate": "static (a logit a head, or none)",
        "layers": "static (each attention layer's heads, window and "
                  "rotary table)",
        "classes": "static (distinct (heads, window): layers, and the "
                   "kernels' decline reasons at that group)",
        "page_visits_full": "roundtable_window_page_visits_full_total",
        "page_visits_window":
            "roundtable_window_page_visits_window_total",
        # ISSUE 40: what the segments' rows held, in pages x attention
        # layers, and how much of it lay wholly behind a window layer's
        # window (engine.window_page_holdings; the same one writer).
        "pages_held": "roundtable_window_pages_held_total",
        "pages_behind_window": "roundtable_window_pages_behind_total",
    },
    # engine.describe()["sampler"] (ISSUE 41): how often the sampler's
    # candidate pool engages. The step programs draw it only when a
    # sampled row of the batch set top_k or top_p (sampling.
    # sample_token_batch); the scheduler counts such rows on the host
    # as it ends a segment (engine.note_sampler_segment is the one
    # writer of the totals and the series; every `segment` span
    # carries its own `filtered_rows`).
    "engine_sampler": {
        "segments": "derived (scheduled segments of every kind; "
                    "describe-only)",
        "filtered_segments": "derived (segments with a filtered row; "
                             "describe-only)",
        "filtered_rows": "roundtable_sampler_filtered_rows_total",
    },
    # engine.describe()["ragged"] (ISSUE 8, 32): the ragged seam's
    # provenance. Static but for the dispatch counts and what the
    # dispatches' attention read, in page visits — as the kernel that
    # served them blocks a run's rows, and at the packing's 8-row
    # blocks (engine._note_page_visits is the one writer of both
    # totals and both series; a ragged `segment` span carries its own
    # dispatch's share).
    "engine_ragged": {
        "enabled": "static (the seam is on)",
        "path": "static (pallas_ragged | xla_ragged)",
        "reason": "static (why the seam is off)",
        "fallback_reason": "static (ragged_decline_reason)",
        "tokens_budget": "static (flat-buffer rows a dispatch)",
        "shapes": "static (the flat-buffer shape grid)",
        "defer_min_tokens": "static (joins below it keep the prologue)",
        "dispatches": "roundtable_sched_ragged_segments_total / "
                      "roundtable_sched_spec_segments_total "
                      "(+ warmup dispatches), split by path",
        "page_visits": "roundtable_ragged_page_visits_total",
        "page_visits_by_eights":
            "roundtable_ragged_page_visits_by_eights_total",
        "recent": "ring view (the last dispatches' provenance)",
    },
    # Gateway.describe() (ISSUE 16): the HTTP front door's admission /
    # shed / stream provenance — counters move in lockstep with the
    # registry series (AdmissionController._count is the one writer).
    "gateway": {
        "admitted": "roundtable_gateway_admitted_total{reason=...}",
        "shed": "roundtable_gateway_shed_total{reason=...}",
        "queued": "roundtable_gateway_queued_total{reason=...}",
        "expired": "roundtable_gateway_expired_total{reason=...}",
        "inflight": "roundtable_gateway_inflight_streams gauge "
                    "(request-labeled; REMOVED per-stream at close)",
        "draining": "roundtable_draining gauge (fleet drain state "
                    "mirrored at the HTTP boundary)",
        "resumed_streams": "roundtable_gateway_resumed_streams_total",
        "dropped_events": "roundtable_gateway_dropped_events_total "
                          "(slow-consumer drop-to-summary)",
        "sessions": "derived (live stream table size)",
        "host": "static config (bind address)",
        "port": "static config (bind port)",
        # ISSUE 17: router fleets only — per-replica roll-up; the
        # underlying series carry a `replica=` label and are REMOVED
        # when SessionRouter.retire drops the replica.
        "replicas": "roundtable_router_sessions{replica=...} gauge / "
                    "roundtable_router_migrations_total / "
                    "roundtable_router_failovers_total / "
                    "roundtable_router_rolls_total{replica=...}",
        # ISSUE 20: the SLO burn-rate monitor's live state — the gauge
        # moves in lockstep with SloBurnMonitor._note (one writer).
        "slo": "roundtable_slo_burn_rate{window=fast|slow} gauge / "
               "roundtable_slo_breaches_total "
               "(utils/tracing SloBurnMonitor.describe)",
        # ISSUE 20: end-to-end tracing provenance — retained-trace
        # counter plus the TTFT stage decomposition the traces carry.
        "tracing": "roundtable_traces_retained_total{outcome=...} / "
                   "roundtable_gateway_ttft_seconds histogram "
                   "(trace-id exemplars; utils/tracing store)",
    },
    # `roundtable status --capacity` (ISSUE 19): the measured
    # capacity frontier (CAPACITY_r19.json / the record behind
    # ROUNDTABLE_GATEWAY_CAPACITY_FILE) joined with the live gateway
    # ledger — commands/status.py capacity_surface() is the one
    # builder of this shape.
    "capacity_status": {
        "record_path": "static (which frontier record was loaded)",
        "knee_rate": "frontier record knee.rate (file-based; the "
                     "sweep that produced it ran the registry live)",
        "knee_ttft_p95_s": "frontier record knee.ttft_p95_s",
        "measured_tok_s": "frontier record knee.accepted_tok_s",
        "predicted_tok_s": "frontier record predicted."
                           "decode_ceiling_tps (perfmodel roofline; "
                           "roundtable_decode_ceiling_tps gauge when "
                           "serving live)",
        "gap_frac": "frontier record gap.gap_frac (span_overheads "
                    "attribution rides gap.overheads)",
        "derived_thresholds": "frontier record derived_thresholds "
                              "(what admission loads through "
                              "ROUNDTABLE_GATEWAY_CAPACITY_FILE)",
        "points": "len(frontier record points)",
        "live_inflight": "roundtable_gateway_inflight_streams gauge "
                         "(series count)",
        "live_admitted": "roundtable_gateway_admitted_total"
                         "{reason=...} sum",
        "live_shed": "roundtable_gateway_shed_total{reason=...} sum",
        "record_errors":
            "roundtable_gateway_capacity_record_errors_total "
            "(malformed-record loud-degrade counter)",
    },
    # `roundtable status --slo` (ISSUE 20): the burn-rate monitor's
    # machine shape — capacity-record SLO baseline joined with the
    # live burn gauges; commands/status.py slo_surface() is the one
    # builder (statically drift-bound like capacity_status).
    "slo_status": {
        "armed": "derived (p95_slo_s > 0)",
        "p95_slo_s": "capacity record derived_thresholds.p95_slo_s "
                     "(the admission SLO baseline)",
        "source": "static (default | capacity_record)",
        "record_path": "static (which frontier record was loaded)",
        "error_budget": "static config "
                        "(ROUNDTABLE_SLO_ERROR_BUDGET)",
        "threshold": "static config "
                     "(ROUNDTABLE_SLO_BURN_THRESHOLD)",
        "burn_fast": "roundtable_slo_burn_rate{window=fast} gauge",
        "burn_slow": "roundtable_slo_burn_rate{window=slow} gauge",
        "breaches": "roundtable_slo_breaches_total",
        "slo_dumps": "roundtable_flight_dumps_total"
                     "{trigger=slo_burn}",
        "traces_retained": "roundtable_traces_retained_total"
                           "{outcome=...} sum",
    },
}


def registry_view() -> dict[str, Any]:
    """The roll-up fleet_health()/describe() embed: counters + gauges
    plus flight-recorder state, so the one store is visible from the
    surfaces operators already poll."""
    rec = recorder()
    return {
        "metrics": REGISTRY.snapshot_compact(),
        "flight_dumps": rec.dumps,
        "last_flight_dump": rec.last_dump_path,
        "spans_emitted": spans_emitted(),
        "armed": ACTIVE,
    }


if os.environ.get("ROUNDTABLE_TELEMETRY"):
    arm()
