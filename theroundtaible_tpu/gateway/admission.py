"""SLO-driven admission control: shed/queue decisions from live
serving signals.

Every decision derives from state the serving stack already publishes
— nothing here samples the device or adds a poll loop:

| signal            | source                                  | shed reason    |
|-------------------|-----------------------------------------|----------------|
| fleet drain       | deadlines.DRAINING / scheduler.paused   | draining (503) |
| dead engine       | supervisor.engine_dead_reason           | engine_dead (503) |
| spent deadline    | client deadline header <= 0             | deadline_expired (408) |
| inflight cap      | live gateway stream table               | inflight_cap (429) |
| queue depth       | scheduler describe()["admission"]       | queue_full (429) |
| KV page pressure  | paged free pages + spill headroom       | kv_pressure (429) |
| adapter residency | LoraStore.can_admit (lora.py)           | adapters_busy (429) |
| p95 turn latency  | gateway's own recent-TTFT window        | slo_p95 (429)  |

The serving-stack signals arrive through a provider (`source=`):
`SchedulerSignals` reads one scheduler/engine (the default — and the
exact pre-ISSUE-17 behavior), the router's `FleetSignals` reads the
whole replica fleet and only sheds when NO replica can serve.

Priority classes: "high" requests bypass the soft signals (p95) and
shed only at hard caps; "low" requests shed at half the inflight/queue
caps — under pressure the cheap traffic goes first. Every shed carries
`Retry-After` plus a machine-readable reason so clients back off
deterministically instead of hammering a collapsing server.

Counters move in lockstep with decisions (`_count` is the one writer):
roundtable_gateway_{admitted,shed,queued,expired}_total{reason=...};
`queued` is the subset of admissions that entered a NONEMPTY scheduler
queue — admitted, but waiting behind in-flight rounds to start.
"""

from __future__ import annotations

import os
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Optional

from ..engine import deadlines
from ..utils import telemetry, tracing

_PRIORITY_SCALE = {"high": 1.0, "normal": 1.0, "low": 0.5}


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, ""))
    except ValueError:
        return default


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, ""))
    except ValueError:
        return default


# --- derived thresholds (ISSUE 19) -----------------------------------

CAPACITY_FILE_ENV = "ROUNDTABLE_GATEWAY_CAPACITY_FILE"

# field -> (env var, parse, built-in default). Precedence per FIELD:
# explicit ctor arg > env var > capacity record > built-in default.
_FIELD_ENVS: dict[str, tuple] = {
    "max_inflight": ("ROUNDTABLE_GATEWAY_MAX_INFLIGHT", int, 32),
    "max_queue_depth": ("ROUNDTABLE_GATEWAY_MAX_QUEUE_DEPTH", int, 16),
    "page_headroom": ("ROUNDTABLE_GATEWAY_PAGE_HEADROOM", float, 0.05),
    "p95_slo_s": ("ROUNDTABLE_GATEWAY_P95_SLO_S", float, 0.0),
    "retry_after_s": ("ROUNDTABLE_GATEWAY_RETRY_AFTER_S", float, 2.0),
}


@dataclass(frozen=True)
class Thresholds:
    """The admission caps with their provenance. `resolve()` layers
    env var > measured capacity record (CAPACITY_FILE_ENV) > built-in
    default — a malformed record degrades LOUDLY to defaults (stderr
    + roundtable_gateway_capacity_record_errors_total) and never
    crashes admission."""

    max_inflight: int = 32
    max_queue_depth: int = 16
    page_headroom: float = 0.05
    p95_slo_s: float = 0.0
    retry_after_s: float = 2.0
    source: str = "default"      # default | capacity_record
    record_path: Optional[str] = None
    env_overrides: tuple = field(default_factory=tuple)

    @classmethod
    def from_capacity_record(cls, record: Any, *,
                             path: Optional[str] = None
                             ) -> "Thresholds":
        """Thresholds DERIVED from a measured capacity frontier
        (loadgen sweep record, bare or bench-wrapped). Raises
        ValueError on a malformed record — resolve() turns that into
        the loud-degrade path."""
        from ..loadgen.capacity import extract_thresholds
        th = extract_thresholds(record)
        return cls(max_inflight=int(th["max_inflight"]),
                   max_queue_depth=int(th["max_queue_depth"]),
                   p95_slo_s=float(th["p95_slo_s"]),
                   source="capacity_record", record_path=path)

    @classmethod
    def resolve(cls) -> "Thresholds":
        base = cls()
        path = os.environ.get(CAPACITY_FILE_ENV)
        if path:
            try:
                from ..loadgen.capacity import load_record
                base = cls.from_capacity_record(load_record(path),
                                                path=path)
            except ValueError as e:
                telemetry.inc("roundtable_gateway_capacity_record_"
                              "errors_total")
                print(f"[gateway] ignoring {CAPACITY_FILE_ENV}="
                      f"{path!r}: {e} — falling back to built-in "
                      "admission defaults", file=sys.stderr)
        overrides: dict[str, Any] = {}
        for fname, (env, parse, _default) in _FIELD_ENVS.items():
            if env not in os.environ:
                continue
            try:
                overrides[fname] = parse(os.environ[env])
            except ValueError:
                # Matches the historical _env_* behavior: an unparsable
                # env value falls through to the layer below.
                continue
        if not overrides:
            return base
        return cls(**{**{f: getattr(base, f) for f in _FIELD_ENVS},
                      **overrides},
                   source=base.source, record_path=base.record_path,
                   env_overrides=tuple(sorted(overrides)))

    def describe(self) -> dict[str, Any]:
        return {
            "max_inflight": self.max_inflight,
            "max_queue_depth": self.max_queue_depth,
            "page_headroom": self.page_headroom,
            "p95_slo_s": self.p95_slo_s,
            "source": self.source,
            "record_path": self.record_path,
            "env_overrides": list(self.env_overrides),
        }


@dataclass(frozen=True)
class Decision:
    admit: bool
    reason: str                  # "ok" or the shed reason tag
    status: int = 200            # HTTP status for sheds
    retry_after_s: float = 0.0
    # Admitted INTO a nonempty scheduler queue: the request parks
    # behind in-flight rounds instead of starting now. Drives the
    # queued counter (roundtable_gateway_queued_total).
    queued: bool = False


class SchedulerSignals:
    """The single-engine admission signal provider: every signal reads
    ONE scheduler/engine, exactly as the gateway did before ISSUE 17.
    The router's FleetSignals implements the same protocol over N
    replicas — single-engine serving is just the N=1 case."""

    def __init__(self, scheduler):
        self.sched = scheduler

    def drain_state(self) -> Optional[str]:
        paused = self.sched.paused
        if deadlines.DRAINING or paused is not None:
            return "draining" if (deadlines.DRAINING
                                  or paused == "fleet.drain") \
                else f"paused:{paused}"
        return None

    def dead_reason(self) -> Optional[str]:
        from ..engine.supervisor import engine_dead_reason
        return engine_dead_reason(self.sched.engine)

    def queue_depth(self) -> int:
        return self.sched.describe()["admission"]["queued"]

    def kv_pressure(self, headroom: float) -> bool:
        engine = self.sched.engine
        kv = getattr(engine, "kv", None)
        if kv is None:
            return False
        floor = int(kv.usable_pages() * headroom)
        return (kv.free_pages() <= floor
                and getattr(engine, "kv_offload", None) is None)

    def adapters_busy(self, adapters) -> bool:
        store = getattr(self.sched.engine, "lora", None)
        return (store is not None
                and not store.can_admit(adapters))


class AdmissionController:
    """Derives one Decision per request from the live signals above.

    Stateless against the signal source (reads its provider methods —
    `SchedulerSignals` for one engine, the router's `FleetSignals` for
    a fleet); its own state is the shed/admit accounting and a bounded
    window of recent TTFT samples for the p95 SLO signal."""

    def __init__(self, scheduler, *,
                 source=None,
                 max_inflight: Optional[int] = None,
                 max_queue_depth: Optional[int] = None,
                 page_headroom: Optional[float] = None,
                 p95_slo_s: Optional[float] = None,
                 retry_after_s: Optional[float] = None,
                 thresholds: Optional[Thresholds] = None):
        self.sched = scheduler
        self.source = source if source is not None \
            else SchedulerSignals(scheduler)
        # Defaults layer through Thresholds.resolve(): env var >
        # measured capacity record (ROUNDTABLE_GATEWAY_CAPACITY_FILE)
        # > built-in. Explicit ctor args still win over everything.
        th = thresholds if thresholds is not None \
            else Thresholds.resolve()
        self.thresholds = th
        self.max_inflight = max_inflight if max_inflight is not None \
            else th.max_inflight
        self.max_queue_depth = max_queue_depth \
            if max_queue_depth is not None else th.max_queue_depth
        self.page_headroom = page_headroom if page_headroom is not None \
            else th.page_headroom
        self.p95_slo_s = p95_slo_s if p95_slo_s is not None \
            else th.p95_slo_s
        self.retry_after_s = retry_after_s if retry_after_s is not None \
            else th.retry_after_s
        self._ttfts: list[float] = []   # bounded window, newest last
        self.admitted = 0
        self.shed = 0
        self.expired = 0
        self.queued = 0
        # SLO burn-rate monitor (ISSUE 20): every TTFT sample and shed
        # this controller sees also feeds the multiwindow burn rate
        # against the capacity-record SLO — the PR-19 frontier becomes
        # a live alerting baseline instead of a one-shot bench artifact.
        self.slo = tracing.SloBurnMonitor(p95_slo_s=self.p95_slo_s,
                                          source=th.source)

    # -- accounting (single writer for counters + registry) --

    def _count(self, outcome: str, reason: str,
               replica: Optional[str] = None) -> None:
        setattr(self, outcome, getattr(self, outcome) + 1)
        if replica is not None:
            telemetry.inc(f"roundtable_gateway_{outcome}_total",
                          reason=reason, replica=replica)
        else:
            telemetry.inc(f"roundtable_gateway_{outcome}_total",
                          reason=reason)
        if outcome == "shed":
            # Sheds are budget-burning events regardless of the SLO
            # being armed — both burn windows see them.
            self.slo.note_shed()

    def note_ttft(self, seconds: float, trace_id: str = "") -> None:
        """One writer for every TTFT surface: the p95 shed window, the
        roundtable_gateway_ttft_seconds histogram (with a trace-id
        exemplar so a bad bucket links to a concrete trace), and the
        SLO burn monitor."""
        self._ttfts.append(seconds)
        if len(self._ttfts) > 256:
            del self._ttfts[:-256]
        telemetry.observe("roundtable_gateway_ttft_seconds", seconds,
                          exemplar=trace_id or None)
        self.slo.note_ttft(seconds, trace_id)

    def p95_ttft(self) -> Optional[float]:
        if len(self._ttfts) < 8:
            return None
        ordered = sorted(self._ttfts)
        return ordered[min(int(len(ordered) * 0.95),
                           len(ordered) - 1)]

    # -- the decision ladder --

    def decide(self, *, rows: int, inflight: int,
               deadline_s: Optional[float] = None,
               priority: str = "normal",
               adapters: Optional[list] = None) -> Decision:
        """The decision ladder, wrapped in an `admission` span (armed
        telemetry only) recording the signal that decided — the trace
        waterfall's first stage. Callers put the request trace on the
        thread stack (telemetry.attached) so the span parents to it."""
        if not telemetry.ACTIVE:
            return self._decide(rows=rows, inflight=inflight,
                                deadline_s=deadline_s,
                                priority=priority, adapters=adapters)
        with telemetry.span("admission", rows=rows, inflight=inflight,
                            priority=priority) as sp:
            dec = self._decide(rows=rows, inflight=inflight,
                               deadline_s=deadline_s,
                               priority=priority, adapters=adapters)
            sp.set_attr("admit", dec.admit)
            sp.set_attr("signal", dec.reason)
            if not dec.admit:
                sp.set_attr("status", dec.status)
            return dec

    def _decide(self, *, rows: int, inflight: int,
                deadline_s: Optional[float] = None,
                priority: str = "normal",
                adapters: Optional[list] = None) -> Decision:
        src = self.source
        scale = _PRIORITY_SCALE.get(priority, 1.0)

        # 1. Drain / pause: finish in-flight, refuse new (503 — the
        # gate reopens; clients retry the same pod after Retry-After).
        # Fleet sources only report this when EVERY live replica is
        # closed — one rolling replica never 503s the front door.
        drain = src.drain_state()
        if drain is not None:
            return self._shed(drain, 503)

        # 2. Dead engine: the supervisor exhausted its restart budget —
        # (fleet: on EVERY replica) nothing this pod serves can
        # succeed (503, longer backoff).
        if src.dead_reason() is not None:
            return self._shed("engine_dead", 503,
                              retry_after=4 * self.retry_after_s)

        # 3. Spent deadline: the client's SLO budget is already gone —
        # admitting would burn a slot to produce a guaranteed timeout.
        if deadline_s is not None and deadline_s <= 0:
            self._count("expired", "deadline_expired")
            return Decision(False, "deadline_expired", 408,
                            self.retry_after_s)

        # 4. Hard caps, priority-scaled: low-priority traffic sheds at
        # half the cap so paid/interactive traffic keeps headroom.
        if inflight >= max(int(self.max_inflight * scale), 1):
            return self._shed("inflight_cap", 429)
        depth = src.queue_depth()
        if depth >= max(int(self.max_queue_depth * scale), 1):
            return self._shed("queue_full", 429)
        # Below the cap but behind queued work: the request admits but
        # parks in the scheduler's FIFO — surfaced on the Decision so
        # note_admitted() counts it under `queued`.
        will_queue = depth > 0

        # 5. KV page pressure: a paged pool within the headroom band
        # AND no host-RAM spill tier to evacuate into means the next
        # admission trades page faults for collapse — shed instead.
        if src.kv_pressure(self.page_headroom):
            return self._shed("kv_pressure", 429)

        # 6. Adapter residency: every LoRA store slot referenced by
        # live rows — retirement frees refs; back off rather than park
        # in the scheduler queue behind an unknown-duration round.
        if (adapters and any(a is not None for a in adapters)
                and src.adapters_busy(adapters)):
            return self._shed("adapters_busy", 429)

        # 7. Soft SLO: the gateway's own p95 TTFT window over target —
        # shed everything except high priority until latency recovers.
        slo = self.p95_slo_s
        if slo and priority != "high":
            p95 = self.p95_ttft()
            if p95 is not None and p95 > slo:
                return self._shed("slo_p95", 429)

        return Decision(True, "ok", queued=will_queue)

    def note_admitted(self, queued: bool = False,
                      replica: Optional[str] = None) -> None:
        """Counted by the gateway AFTER submit_async succeeds — the
        scheduler can still refuse between decide() and submit (a
        drain racing the request), and that lands under `shed`, so the
        two counters never both claim one request. `queued` marks an
        admission that parked behind a nonempty scheduler queue
        (Decision.queued) — the queue path's own lockstep counter.
        `replica` labels the series when a router placed the stream
        (single-engine output stays byte-identical)."""
        self._count("admitted", "ok", replica=replica)
        if queued:
            self._count("queued", "behind_queue", replica=replica)

    def note_shed(self, reason: str,
                  replica: Optional[str] = None) -> None:
        """Submit-time refusals (scheduler raced the decision)."""
        self._count("shed", reason, replica=replica)

    def _shed(self, reason: str, status: int,
              retry_after: Optional[float] = None) -> Decision:
        self._count("shed", reason)
        return Decision(False, reason, status,
                        retry_after if retry_after is not None
                        else self.retry_after_s)

    def describe(self) -> dict:
        return {
            "admitted": self.admitted,
            "shed": self.shed,
            "expired": self.expired,
            "queued": self.queued,
            "p95_ttft_s": self.p95_ttft(),
            "caps": {
                "max_inflight": self.max_inflight,
                "max_queue_depth": self.max_queue_depth,
                "page_headroom": self.page_headroom,
                "p95_slo_s": self.p95_slo_s,
                "source": self.thresholds.source,
                "record_path": self.thresholds.record_path,
            },
            "slo": self.slo.describe(),
        }


def make_budget(deadline_s: Optional[float]):
    """The scheduler-facing deadline: a Budget root bounded by the
    client's remaining SLO (None = unbounded). 0 is born expired —
    submit_async fails it fast with DeadlineExpired."""
    if deadline_s is None:
        return None
    return deadlines.Budget.root(max(deadline_s, 0.0), rung="turn")


def clock() -> float:
    return time.monotonic()
