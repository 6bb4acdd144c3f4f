"""Router core: per-session replica assignment, cross-replica KV
migration, rolling restarts, and failure containment (ISSUE 17).

Everything through PR 16 scales ONE engine; this module turns those
single-engine capabilities into a serving tier. The pieces it composes
were all built replica-independent on purpose:

- `HostOffloadTier.evacuate()/adopt()` is a pool-independent,
  byte-identical KV manifest — promoted here from spill target to the
  cross-replica transfer fabric (quantized pages move at their stored
  int8/int4 width, so handoff bandwidth is already halved-to-quartered).
- The fsynced `SessionJournal` is a replica-independent session record
  — `replay_turns` re-establishes KV on a survivor when a dead
  replica's pool (and any un-evacuated pages in it) is gone.
- `EngineSupervisor.restart` already quiesces, evacuates, rebuilds
  under the PR-12 budget, and re-adopts — `roll()` wraps it with
  fleet-side drain (idle sessions migrate to peers first) so a planned
  roll loses zero sessions and zero tokens.

Routing signals (cold sessions pick the minimum `load_score`):

| signal              | source                                | weight env |
|---------------------|---------------------------------------|------------|
| queue depth + rows  | scheduler describe()                  | ROUNDTABLE_ROUTER_QUEUE_WEIGHT (1.0) |
| paged page fill     | kv.free_pages()/usable_pages()        | ROUNDTABLE_ROUTER_PAGE_WEIGHT (4.0)  |
| LoRA residency      | LoraStore.can_admit(adapters)         | fixed +2.0 |
| supervisor state    | engine_dead_reason / paused / rolling | inf / +1e3 |

Returning sessions never re-route while their replica lives: the
replica holds their KV (resident or host-spilled), and affinity is
what makes prefix reuse and own-slot reuse work across turns. After a
process restart the assignment map is empty, so affinity falls back to
the journal's `replica=` meta on the session's last committed turn.

Thread model: `_lock` guards the assignment map (gateway submit
threads), `_op_lock` serializes the fleet operations (migrate / roll /
failover). Engine-touching steps additionally take the source engine's
serve lock, same as the supervisor, so a migration can never race an
in-flight dispatch on the pages it is moving.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Optional

from ..engine.session_journal import replay_turns
from ..utils import telemetry

# --- test counters (tests/conftest.py `router` marker guard) ---

_test_crossings = 0


def reset_test_counters() -> None:
    global _test_crossings
    _test_crossings = 0


def boundary_crossings() -> int:
    return _test_crossings


def note_boundary_crossing() -> None:
    """One session's state crossed a replica boundary (migration
    adopt, or failover replay). The conftest guard requires marked
    router tests to move this — a "router test" that never left its
    replica is testing the N=1 path under a multi-replica name."""
    global _test_crossings
    _test_crossings += 1


# --- module-wide active router (fleet_health / status roll-up) ---

_active: Optional["SessionRouter"] = None


def active_router() -> Optional["SessionRouter"]:
    return _active


def set_active_router(router: Optional["SessionRouter"]) -> None:
    global _active
    _active = router


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, ""))
    except ValueError:
        return default


class NoLiveReplica(RuntimeError):
    """Every replica is dead or rolling — nothing can serve. The
    gateway's fleet admission sheds `engine_dead` before submits get
    here; this raise is the backstop for direct scheduler_for users."""


class Replica:
    """One data-parallel serving replica: an engine plus its session
    scheduler, under a fleet-unique name (replicas share the engine
    config's `name`, so telemetry needs the extra label)."""

    def __init__(self, name: str, engine, scheduler):
        self.name = name
        self.engine = engine
        self.scheduler = scheduler
        self._bind()

    def _bind(self) -> None:
        self.engine._replica_name = self.name
        self.scheduler.set_replica(self.name)

    @property
    def tier(self):
        return getattr(self.engine, "kv_offload", None)

    def dead_reason(self) -> Optional[str]:
        from ..engine.supervisor import engine_dead_reason
        return engine_dead_reason(self.engine)

    def refresh_engine(self) -> None:
        """Re-sync after a supervised restart swapped the scheduler's
        engine (reattach_engine) — the replica must point at, and
        label, the rebuilt engine."""
        self.engine = self.scheduler.engine
        self._bind()

    def snapshot_sessions(self) -> dict[str, str]:
        try:
            return self.scheduler.snapshot()["sessions"]
        except Exception:  # noqa: BLE001 — advisory
            return {}

    def describe(self) -> dict[str, Any]:
        d = self.scheduler.describe()
        return {
            "name": self.name,
            "engine": getattr(self.engine.cfg, "name", "?"),
            "dead": self.dead_reason(),
            "paused": d["admission"]["paused"],
            "queued": d["admission"]["queued"],
            "active_rows": d["active_rows"],
        }


class SessionRouter:
    """The session→replica map and the fleet operations over it."""

    def __init__(self, replicas: list[Replica], *,
                 journal=None,
                 roll_timeout_s: Optional[float] = None):
        if not replicas:
            raise ValueError("SessionRouter needs at least one replica")
        names = [r.name for r in replicas]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate replica names: {names}")
        self.replicas = list(replicas)
        self.journal = journal
        self.roll_timeout_s = roll_timeout_s \
            if roll_timeout_s is not None \
            else _env_float("ROUNDTABLE_ROUTER_ROLL_TIMEOUT_S", 30.0)
        self.queue_weight = _env_float(
            "ROUNDTABLE_ROUTER_QUEUE_WEIGHT", 1.0)
        self.page_weight = _env_float(
            "ROUNDTABLE_ROUTER_PAGE_WEIGHT", 4.0)
        self._assign: dict[str, str] = {}
        self._rolling: set[str] = set()
        self._retired: set[str] = set()
        self._lock = threading.RLock()
        self._op_lock = threading.RLock()
        self.migrations = 0
        self.failovers = 0
        self.rolls = 0
        from ..engine import supervisor as sup
        sup.on_engine_dead(self._on_engine_dead)
        for r in self.replicas:
            self._publish_sessions(r.name)

    # --- lookup ---

    def _replica(self, name: str) -> Replica:
        for r in self.replicas:
            if r.name == name:
                return r
        raise KeyError(f"no replica named {name!r}")

    def _live(self, *, exclude: Optional[str] = None) -> list[Replica]:
        out = []
        for r in self.replicas:
            if r.name in self._retired or r.name == exclude:
                continue
            if r.name in self._rolling or r.dead_reason() is not None:
                continue
            out.append(r)
        return out

    def _publish_sessions(self, name: str) -> None:
        if name in self._retired:
            return
        n = sum(1 for v in self._assign.values() if v == name)
        telemetry.set_gauge("roundtable_router_sessions", n,
                            replica=name)

    # --- routing ---

    def load_score(self, rep: Replica,
                   adapters: Optional[list] = None) -> float:
        """Cold-session placement score from the replica's EXISTING
        backpressure signals — nothing here samples the device."""
        if rep.dead_reason() is not None:
            return float("inf")
        score = 0.0
        if rep.name in self._rolling:
            score += 1e6
        d = rep.scheduler.describe()
        if d["admission"]["paused"] is not None:
            score += 1e3
        score += self.queue_weight * (d["admission"]["queued"]
                                      + d["active_rows"])
        engine = rep.engine
        kv = getattr(engine, "kv", None)
        if kv is not None:
            usable = max(kv.usable_pages(), 1)
            score += self.page_weight * (1.0 - kv.free_pages() / usable)
        store = getattr(engine, "lora", None)
        if (store is not None and adapters
                and any(a is not None for a in adapters)
                and not store.can_admit(adapters)):
            score += 2.0
        return score

    def replica_for(self, session: str,
                    adapters: Optional[list] = None) -> Replica:
        """Sticky per-session assignment with journal affinity for
        sessions from before this process, load-scored placement for
        cold ones. Raises NoLiveReplica when nothing can serve.

        Armed telemetry wraps the lookup in a `placement` span
        (ISSUE 20): the gateway calls this under the request trace's
        context, so the span lands in the request's waterfall naming
        the replica that won."""
        if not telemetry.ACTIVE:
            return self._place(session, adapters)
        with telemetry.span("placement", session=session) as sp:
            rep = self._place(session, adapters)
            sp.set_attr("replica", rep.name)
            return rep

    def _place(self, session: str,
               adapters: Optional[list] = None) -> Replica:
        with self._lock:
            name = self._assign.get(session)
            if name is not None and name not in self._retired:
                rep = self._replica(name)
                if (rep.dead_reason() is None
                        and name not in self._rolling):
                    return rep
                # Dead or mid-roll: fall through and re-place. The
                # failover callback normally re-assigns first; this is
                # the race window where a submit beat it.
            if name is None and self.journal is not None:
                last = None
                try:
                    last = self.journal.last_replica(session)
                except Exception:  # noqa: BLE001 — affinity is advisory
                    pass
                if last is not None and last not in self._retired:
                    try:
                        rep = self._replica(last)
                    except KeyError:
                        rep = None
                    if (rep is not None and rep.dead_reason() is None
                            and last not in self._rolling):
                        self._assign[session] = last
                        self._publish_sessions(last)
                        return rep
            live = self._live()
            if not live:
                raise NoLiveReplica(
                    "no live replica (all dead, rolling, or retired)")
            rep = min(live, key=lambda r: self.load_score(r, adapters))
            self._assign[session] = rep.name
            self._publish_sessions(rep.name)
            return rep

    def scheduler_for(self, session: str,
                      adapters: Optional[list] = None):
        return self.replica_for(session, adapters).scheduler

    def signals(self):
        """The gateway admission controller's fleet-wide signal
        provider (the N=1 case is admission.py's SchedulerSignals)."""
        from .signals import FleetSignals
        return FleetSignals(self)

    # --- migration (the host tier as transfer fabric) ---

    def _session_idle(self, rep: Replica, session: str) -> bool:
        state = rep.snapshot_sessions().get(session, "")
        return not (state.startswith("queued")
                    or state.startswith("active"))

    def migrate(self, session: str,
                dst: Optional[str] = None) -> Replica:
        """Move one idle session's KV to another replica:
        `evacuate()` on the source → `adopt()` onto the destination →
        `restore_for` fires transparently on the destination's next
        dispatch. Byte-identical — quantized pages move at stored
        width. Falls back to journal replay when either side has no
        host tier. Raises if the session is mid-turn on the source."""
        with self._op_lock, telemetry.span("migration",
                                           session=session):
            with self._lock:
                src_name = self._assign.get(session)
            src = self._replica(src_name) if src_name else None
            if dst is not None:
                target = self._replica(dst)
                if target.dead_reason() is not None:
                    raise NoLiveReplica(
                        f"migration target {dst!r} is dead")
            else:
                live = self._live(exclude=src_name)
                if not live:
                    raise NoLiveReplica(
                        f"no live migration target for {session!r}")
                target = min(live, key=self.load_score)
            if src is None or src is target:
                self._assign_to(session, target.name, src_name)
                return target
            if src.dead_reason() is not None:
                self._failover_session(session, src, target)
                return target
            if not self._session_idle(src, session):
                raise RuntimeError(
                    f"session {session!r} has in-flight work on "
                    f"{src.name!r} — migrate only idle sessions "
                    "(quiesce or wait for the turn to retire)")
            self._transfer(session, src, target)
            self._assign_to(session, target.name, src_name)
            self.migrations += 1
            telemetry.inc("roundtable_router_migrations_total",
                          replica=target.name)
            note_boundary_crossing()
            telemetry.recorder().record(
                "router_migrate", session=session, src=src.name,
                dst=target.name)
            return target

    def _assign_to(self, session: str, name: str,
                   old: Optional[str]) -> None:
        with self._lock:
            self._assign[session] = name
            self._publish_sessions(name)
            if old is not None and old != name:
                self._publish_sessions(old)

    def _transfer(self, session: str, src: Replica,
                  dst: Replica) -> None:
        """The KV handoff itself. Serialized against the source
        engine's dispatches exactly like the supervisor's cycle: the
        serve lock is the one mutex every generate path holds."""
        if src.tier is not None and dst.tier is not None:
            lock = getattr(src.engine, "_serve_lock", None)
            held = False
            if lock is not None:
                if not lock.acquire(timeout=self.roll_timeout_s):
                    raise TimeoutError(
                        f"serve lock on {src.name!r} never freed — "
                        f"cannot migrate {session!r}")
                held = True
            try:
                src.tier.evacuate(sessions=[session])
                adopted = dst.tier.adopt(src.tier, sessions=[session])
            finally:
                if held:
                    lock.release()
            if session in adopted:
                return
            # evacuate() ran but adopt() refused (no host-resident
            # record — e.g. the session held no KV). Fall through to
            # replay, which also covers the no-KV case by rebuilding
            # from the journal.
        if self.journal is None:
            raise RuntimeError(
                f"cannot migrate {session!r}: no host tier on both "
                "sides and no journal to replay from")
        replay_turns(self.journal, session, dst.scheduler.submit)

    # --- rolling restart ---

    def roll(self, name: Optional[str] = None) -> list[dict]:
        """Roll one replica (or, with no name, the whole fleet one
        replica at a time): drain it — admission closed, in-flight
        turns finish, idle sessions migrate to peers — supervise the
        rebuild under the PR-12 restart budget, re-admit. Sessions
        that could not move ride the supervisor's own
        evacuate→rebuild→adopt cycle inside the replica. Streams
        crossing the roll reconnect through the PR-16 resume ladder
        untouched."""
        targets = [name] if name is not None \
            else [r.name for r in self.replicas
                  if r.name not in self._retired]
        return [self._roll_one(t) for t in targets]

    def _roll_one(self, name: str) -> dict:
        rep = self._replica(name)
        with self._op_lock, telemetry.span("roll", replica=name):
            report: dict[str, Any] = {"replica": name, "op": "roll"}
            rep.scheduler.pause_admission("router.roll")
            with self._lock:
                self._rolling.add(name)
            try:
                report["quiesced"] = rep.scheduler.quiesce(
                    self.roll_timeout_s)
                report["migrated"] = self._evacuate_sessions(rep)
                from ..engine.supervisor import supervisor, EngineDead
                try:
                    sup_report = supervisor().restart(
                        rep.engine, reason="roll",
                        scheduler=rep.scheduler)
                    report["ok"] = bool(sup_report.get("ok"))
                    report["restart"] = sup_report.get("restart")
                except EngineDead as e:
                    # Budget exhausted mid-roll: the death callback
                    # already moved this replica's sessions to
                    # survivors; report the truth.
                    report["ok"] = False
                    report["dead"] = str(e)[:200]
                rep.refresh_engine()
            finally:
                with self._lock:
                    self._rolling.discard(name)
                rep.scheduler.reopen_admission()
            self.rolls += 1
            telemetry.inc("roundtable_router_rolls_total",
                          replica=name)
            telemetry.recorder().record("router_roll", replica=name,
                                        ok=report.get("ok"))
            return report

    def _evacuate_sessions(self, rep: Replica) -> int:
        """Migrate every idle session assigned to `rep` onto live
        peers. Sessions that refuse to move (or have nowhere to go)
        stay — the supervisor's in-replica evacuation covers them."""
        with self._lock:
            mine = [s for s, n in self._assign.items()
                    if n == rep.name]
        moved = 0
        for session in mine:
            live = self._live(exclude=rep.name)
            if not live:
                break
            try:
                self.migrate(session,
                             dst=min(live, key=self.load_score).name)
                moved += 1
            except Exception:  # noqa: BLE001 — stay-behind is safe
                pass
        return moved

    # --- failure containment ---

    def _on_engine_dead(self, engine, reason: str, kind: str) -> None:
        """Supervisor death callback: an unplanned dead replica's
        journaled sessions migrate to survivors. Host-resident spill
        records survive the lost device and adopt() straight across;
        everything else re-establishes KV by journal replay."""
        dead_name = getattr(engine, "_replica_name", None)
        rep = None
        for r in self.replicas:
            if r.engine is engine or (dead_name is not None
                                      and r.name == dead_name):
                rep = r
                break
        if rep is None or rep.name in self._retired:
            return
        with self._op_lock:
            telemetry.recorder().record(
                "router_replica_dead", replica=rep.name,
                reason=reason[:200], failure_kind=kind)
            with self._lock:
                sessions = [s for s, n in self._assign.items()
                            if n == rep.name]
            # Journal-only sessions (a pre-restart process served
            # them) also belong to this replica — fold them in so
            # their next turn finds KV on a survivor.
            if self.journal is not None:
                try:
                    for s in self.journal.sessions():
                        if (s not in sessions
                                and self.journal.last_replica(s)
                                == rep.name):
                            sessions.append(s)
                except Exception:  # noqa: BLE001 — advisory
                    pass
            for session in sessions:
                live = self._live(exclude=rep.name)
                if not live:
                    # Whole fleet down: leave assignments; admission
                    # sheds engine_dead with Retry-After until a
                    # replica returns.
                    break
                dst = min(live, key=self.load_score)
                try:
                    self._failover_session(session, rep, dst)
                except Exception as e:  # noqa: BLE001 — containment
                    telemetry.recorder().record(
                        "router_failover_error", session=session,
                        replica=rep.name, error=str(e)[:200])

    def _failover_session(self, session: str, dead: Replica,
                          dst: Replica) -> None:
        with telemetry.span("failover", session=session,
                            src=dead.name, dst=dst.name) as sp:
            adopted: list[str] = []
            if dead.tier is not None and dst.tier is not None:
                try:
                    # NEVER spill from a dead engine — only records
                    # that were already fully host-resident cross here.
                    adopted = dst.tier.adopt(dead.tier,
                                             sessions=[session])
                except Exception:  # noqa: BLE001 — fall back to replay
                    adopted = []
            if session not in adopted:
                if self.journal is None:
                    raise RuntimeError(
                        f"session {session!r} lost with {dead.name!r}: "
                        "no host-resident KV and no journal to replay")
                replay_turns(self.journal, session,
                             dst.scheduler.submit)
            with self._lock:
                self._assign[session] = dst.name
                self._publish_sessions(dst.name)
                self._publish_sessions(dead.name)
            self.failovers += 1
            telemetry.inc("roundtable_router_failovers_total",
                          replica=dead.name)
            note_boundary_crossing()
            sp.set_attr("via", "adopt" if adopted else "replay")
            telemetry.recorder().record(
                "router_failover", session=session, src=dead.name,
                dst=dst.name, via="adopt" if adopted else "replay")

    # --- retirement (RT-GAUGE-LEAK: series die with the replica) ---

    def retire(self, name: str) -> None:
        """Drop a replica from the fleet and remove every telemetry
        series labeled with it — a long-lived router must not keep one
        dead series per replica ever rolled out."""
        rep = self._replica(name)
        with self._op_lock:
            with self._lock:
                for s, n in list(self._assign.items()):
                    if n == name:
                        del self._assign[s]
                self._retired.add(name)
                self._rolling.discard(name)
            ename = getattr(rep.engine.cfg, "name", "engine")
            tname = rep.scheduler._tname
            telemetry.remove_gauge("roundtable_router_sessions",
                                   replica=name)
            telemetry.remove_gauge("roundtable_engine_dead",
                                   engine=ename, replica=name)
            telemetry.remove_gauge("roundtable_sched_queue_depth",
                                   engine=tname, replica=name)
            telemetry.remove_gauge("roundtable_sched_active_rows",
                                   engine=tname, replica=name)
            telemetry.recorder().record("router_retire", replica=name)

    # --- lifecycle / observability ---

    def describe(self) -> dict[str, Any]:
        with self._lock:
            assigned = dict(self._assign)
            rolling = sorted(self._rolling)
            retired = sorted(self._retired)
        per = {}
        for r in self.replicas:
            if r.name in retired:
                continue
            d = r.describe()
            d["sessions"] = sum(1 for v in assigned.values()
                                if v == r.name)
            per[r.name] = d
        return {
            "replicas": per,
            "sessions": len(assigned),
            "rolling": rolling,
            "retired": retired,
            "migrations": self.migrations,
            "failovers": self.failovers,
            "rolls": self.rolls,
        }

    def close(self) -> None:
        from ..engine import supervisor as sup
        sup.remove_death_callback(self._on_engine_dead)
        if active_router() is self:
            set_active_router(None)


def build_replicas(engine, n: int, *, journal=None,
                   **scheduler_opts) -> list[Replica]:
    """Build an N-replica fleet around an existing engine: replica
    `r0` wraps the given engine and its (acquired) scheduler; replicas
    `r1..` are fresh clones from the same `_engine_config` rebuild
    recipe — the identical recipe the supervisor uses, so a rolled or
    replaced replica is indistinguishable from a built one. All
    schedulers share one journal: turn numbering (and the gateway's
    resume ladder) stays global across the fleet."""
    if n < 1:
        raise ValueError(f"need at least 1 replica, got {n}")
    cfg = getattr(engine, "_engine_config", None)
    if n > 1 and cfg is None:
        raise ValueError(
            "multi-replica serving needs a rebuild recipe "
            "(engine._engine_config) — construct the engine via "
            "from_config/get_engine")
    from ..engine.scheduler import acquire_scheduler
    replicas = []
    for i in range(n):
        eng = engine if i == 0 \
            else type(engine).from_config(dict(cfg))
        sched, created = acquire_scheduler(eng, **scheduler_opts)
        if journal is not None and sched.journal is not journal:
            sched.attach_journal(journal)
        rep = Replica(f"r{i}", eng, sched)
        # Whether THIS build created the scheduler — the caller closes
        # only those (replica 0 may wrap a pre-existing scheduler that
        # other sessions still share).
        rep.owned_scheduler = created
        replicas.append(rep)
    return replicas
