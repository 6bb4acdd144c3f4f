"""`roundtable serve` — K concurrent discussions on one shared fleet.

The discuss command serves exactly one session; this command is the
ISSUE 4 entry point that drives MANY: each topic gets its own discussion
thread with its own session directory, metrics file and adapter
instances, while every tpu-llm adapter routes its rounds through the
per-engine continuous-batching SessionScheduler — so the sessions'
decode work genuinely interleaves on the shared engines instead of
serializing behind one serve lock.

Programmatic surface: `serve_discussions(topics, config, project_root)`
returns per-session results plus each scheduler's decision provenance;
bench_discuss's offered-load mode and the scheduler test-suite drive it
directly.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Callable, Optional

from ..adapters.factory import initialize_adapters
from ..core.config import load_config
from ..core.errors import ConfigError
from ..core.orchestrator import run_discussion
from ..utils.ui import style


def _dedupe_topics(topics: list[str]) -> list[str]:
    """Session dirs are named date-HHMM-slug(topic): concurrent sessions
    whose topics slug identically would share (and corrupt) one session
    directory. Duplicates get a "(session N)" PREFIX — slugify truncates
    at 50 chars, so a suffix on any sentence-length topic would land
    past the cut and the slugs would still collide."""
    from ..utils.session import slugify
    seen: set = set()
    out = []
    for t in topics:
        cand, n = t, 1
        while slugify(cand) in seen:
            n += 1
            cand = f"(session {n}) {t}"
        seen.add(slugify(cand))
        out.append(cand)
    return out


def _attach_schedulers(adapters: dict, session_id: str,
                       admit_hold_s: float,
                       journal=None, router=None) -> tuple[list, list]:
    """Bind every tpu-llm adapter in this session's seat map to its
    session id and to the SHARED per-engine scheduler (scheduler_for:
    one scheduler per resident engine, however many sessions share it)
    — or, under a session router, to the scheduler of the REPLICA the
    router placed this session on (affinity + load score; ISSUE 17).
    Returns (schedulers touched, schedulers CREATED here) — the caller
    must only close the latter: a scheduler that pre-existed this serve
    call belongs to someone else's sessions too, and closing it would
    kill their in-flight rounds with SchedulerClosed."""
    scheds, owned = [], []
    for adapter in adapters.values():
        attach = getattr(adapter, "attach_scheduler", None)
        if attach is None:
            continue
        if router is not None:
            # The router owns replica schedulers' lifecycles; serving
            # goes through the scheduler, so the adapter's own engine
            # handle is only a tokenizer/config source.
            sched = router.scheduler_for(session_id)
            attach(sched, session=session_id)
            if sched not in scheds:
                scheds.append(sched)
            continue
        try:
            engine = adapter._get_engine()
        except Exception:  # noqa: BLE001 — seat probes already warned
            # The engine may still come up later (execute_round retries
            # construction on the breaker's probe) — the session
            # NAMESPACE must be bound regardless, or two sessions'
            # same-named knights would collide on the recovered engine.
            adapter.session = session_id
            continue
        from ..engine.scheduler import acquire_scheduler
        sched, created = acquire_scheduler(
            engine, admit_hold_s=admit_hold_s)
        if journal is not None and sched.journal is not journal:
            # Durable turn journal (ISSUE 12): one journal per serve
            # root, shared by every scheduler — committed turns fsync
            # at retire so `serve --resume` survives a kill -9. A
            # different already-attached journal is REPLACED: `--resume
            # DIR1 --journal DIR2` must journal new turns into DIR2,
            # not keep the replay-attached DIR1 (the full-disk
            # migration case).
            sched.attach_journal(journal)
        attach(sched, session=session_id)
        if sched not in scheds:
            scheds.append(sched)
        if created and sched not in owned:
            owned.append(sched)
    return scheds, owned


def serve_discussions(
    topics: list[str],
    config,
    project_root: str,
    *,
    read_source_code: bool = False,
    admit_hold_s: float = 0.25,
    reporter_factory: Optional[Callable[[str], Any]] = None,
    close_schedulers: bool = True,
    journal_dir: Optional[str] = None,
    replicas: int = 1,
) -> dict[str, Any]:
    """Run one discussion per topic, all concurrently, on shared engines.

    Each session gets its OWN adapter instances (adapter state —
    last_stats, degradation markers, the fallback cache — is per
    session) seated from the same config; the engine cache underneath
    dedups the resident models, and scheduler_for dedups the scheduler
    per engine, so N sessions share one model + one continuous batch.

    Returns {"sessions": [{topic, session_id, ok, result|error,
    wall_s, session_path}], "schedulers": [describe()...],
    "wall_s": total}.
    """
    topics = _dedupe_topics(list(topics))
    journal = None
    if journal_dir is not None:
        from ..engine.session_journal import SessionJournal
        journal = SessionJournal(journal_dir)
    router = None
    if replicas > 1:
        # N-replica fleet (ISSUE 17): one engine per replica behind a
        # session router — sessions place by affinity/load and every
        # scheduler shares the one journal. `--replicas 1` (and every
        # caller that doesn't pass it) takes the classic path below,
        # byte-identical to single-engine serving.
        from ..router import SessionRouter, build_replicas, \
            set_active_router
        probe = initialize_adapters(config)
        engine = None
        for adapter in probe.values():
            if hasattr(adapter, "attach_scheduler"):
                try:
                    engine = adapter._get_engine()
                    break
                except Exception:  # noqa: BLE001 — try the next seat
                    continue
        if engine is None:
            raise ConfigError(
                "--replicas needs at least one tpu-llm knight whose "
                "engine can be built")
        reps = build_replicas(engine, replicas, journal=journal,
                              admit_hold_s=admit_hold_s)
        router = SessionRouter(reps, journal=journal)
        set_active_router(router)
    all_scheds: list = []
    owned_scheds: list = []
    # Session ids carry a per-CALL unique component: two concurrent
    # serve_discussions calls share the resident engine (by design), so
    # plain "s0"/"s1" ids would merge unrelated discussions into one
    # KV isolation domain.
    import uuid
    call_tag = uuid.uuid4().hex[:6]
    session_entries: list[dict[str, Any]] = [
        {"topic": t, "session_id": f"{call_tag}-s{i}"}
        for i, t in enumerate(topics)]
    threads = []
    t0 = time.monotonic()

    def run_one(entry: dict[str, Any]) -> None:
        ts = time.monotonic()
        try:
            adapters = initialize_adapters(config)
            if not adapters:
                raise ConfigError(
                    "A roundtable with no knights is just a table.")
            # Plain appends from session threads; deduped by identity
            # when the report is built.
            scheds, owned = _attach_schedulers(
                adapters, entry["session_id"], admit_hold_s,
                journal=journal, router=router)
            all_scheds.extend(scheds)
            owned_scheds.extend(owned)
            reporter = (reporter_factory(entry["session_id"])
                        if reporter_factory else None)
            result = run_discussion(
                entry["topic"], config, adapters, project_root,
                read_source_code=read_source_code, reporter=reporter)
            entry["ok"] = True
            entry["result"] = result
            entry["session_path"] = result.session_path
        except Exception as e:  # noqa: BLE001 — per-session containment
            entry["ok"] = False
            entry["error"] = e
        entry["wall_s"] = round(time.monotonic() - ts, 3)

    for entry in session_entries:
        th = threading.Thread(target=run_one, args=(entry,),
                              name=f"serve-{entry['session_id']}",
                              daemon=True)
        threads.append(th)
        th.start()
    for th in threads:
        th.join()
    uniq = list({id(s): s for s in all_scheds}.values())
    report = {
        "sessions": session_entries,
        "schedulers": [s.describe() for s in uniq],
        "wall_s": round(time.monotonic() - t0, 3),
    }
    if router is not None:
        report["router"] = router.describe()
    if close_schedulers:
        # Only schedulers CREATED by this call — a pre-existing one is
        # shared with sessions outside this call and must keep running.
        for s in {id(s): s for s in owned_scheds}.values():
            s.close()
        if router is not None:
            router.close()
            for rep in router.replicas:
                if getattr(rep, "owned_scheduler", False):
                    rep.scheduler.close()
    return report


# Factored into the engine layer (ISSUE 16): the gateway restores
# committed sessions on boot through the same seam the CLI uses. The
# re-export keeps `commands.serve.resume_from_journal` — and the
# `serve --resume` behavior behind it — byte-identical.
from ..engine.recovery import resume_from_journal  # noqa: E402,F401


def serve_command(topics: list[str], sessions: Optional[int] = None,
                  read_code: Optional[bool] = None,
                  project_root: Optional[str] = None,
                  journal_dir: Optional[str] = None,
                  resume_dir: Optional[str] = None,
                  replicas: int = 1) -> int:
    """CLI: `roundtable serve "topic" --sessions 4` (one topic fanned
    into K concurrent discussions), `roundtable serve "t1" "t2" "t3"`
    (one discussion each), `--journal DIR` for crash-durable turn
    records, `--resume DIR` to replay a crashed process's journal."""
    project_root = project_root or os.getcwd()
    config = load_config(project_root)
    if not topics and not resume_dir:
        raise ConfigError(
            "serve needs topics to discuss (or --resume DIR)")
    if resume_dir:
        print(style.bold(f"\n  Resuming sessions from journal "
                         f"{resume_dir}..."))
        r = resume_from_journal(resume_dir, config=config,
                                project_root=project_root)
        print(style.dim(
            f"  replayed {r['turns']} committed turn(s) across "
            f"{r['sessions']} session(s) — KV restored at the last "
            "committed turn"))
        # A resumed serve keeps journaling into the same directory
        # unless the operator pointed --journal elsewhere.
        journal_dir = journal_dir or resume_dir
        if not topics:
            # Nothing to serve: the replay above VALIDATED the journal
            # (every committed turn re-prefilled cleanly), but the
            # restored KV lives only in this process — continuing the
            # work needs topics in the same invocation.
            from ..engine.session_journal import SessionJournal
            j = SessionJournal(resume_dir)
            for session in j.sessions():
                last = j.last_turn(session)
                print(style.dim(
                    f"    {session}: resumed at committed turn {last}"))
            print(style.dim(
                "\n  journal validated — no topics given, so this "
                "process exits. To continue serving after a crash, "
                "pass the next topics in the same invocation:\n"
                "    roundtable serve --resume DIR \"next topic\"\n"))
            return 0
    if sessions and len(topics) == 1:
        topics = topics * sessions
    elif sessions and len(topics) != sessions:
        raise ConfigError(
            f"--sessions {sessions} with {len(topics)} topics — give ONE "
            "topic to replicate, or one topic per session")

    print(style.bold(f"\n  Serving {len(topics)} concurrent "
                     "discussion(s) on the shared fleet...\n"))
    report = serve_discussions(topics, config, project_root,
                               read_source_code=bool(read_code),
                               journal_dir=journal_dir,
                               replicas=replicas)

    failed = 0
    for entry in report["sessions"]:
        if entry.get("ok"):
            r = entry["result"]
            verdict = ("consensus" if r.consensus
                       and not r.unanimous_rejection
                       else "rejection" if r.consensus else "escalated")
            print(f"  {style.green(entry['session_id'])} "
                  f"{verdict} in {r.rounds} round(s), "
                  f"{entry['wall_s']:.1f}s — {entry['session_path']}")
        else:
            failed += 1
            print(f"  {style.red(entry['session_id'])} failed: "
                  f"{entry.get('error')}")
    for sched in report["schedulers"]:
        print(style.dim(
            f"\n  scheduler: admitted {sched['admitted']}, "
            f"completed {sched['completed']}, "
            f"max occupancy {sched['max_occupancy']} rows, "
            f"mean {sched['occupancy_mean']} over "
            f"{sched['segments']} segment(s), "
            f"queue peak {sched['queued_peak']}"))
    if report.get("router"):
        rt = report["router"]
        print(style.dim(
            f"  router: {len(rt['replicas'])} replica(s), "
            f"{rt['sessions']} session(s) placed, "
            f"{rt['migrations']} migration(s), "
            f"{rt['failovers']} failover(s)"))
    print(style.dim(f"  total wall: {report['wall_s']:.1f}s\n"))
    return 1 if failed else 0
