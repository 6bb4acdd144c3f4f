"""`roundtable gateway` — serve the streaming HTTP/SSE front door.

Seats the configured adapters, acquires the first tpu-llm engine's
shared SessionScheduler (the same seam `serve --resume` uses), wires
the durable journals, optionally replays a crashed process's committed
turns, and blocks serving HTTP until interrupted.
"""

from __future__ import annotations

import os
from typing import Optional

from ..core.config import load_config
from ..core.errors import ConfigError
from ..utils.ui import style


def _build_scheduler(config, journal_dir: Optional[str]):
    """First tpu-llm engine's shared scheduler (+ attached journal)."""
    from ..adapters.factory import initialize_adapters
    from ..engine.scheduler import acquire_scheduler

    # A seat whose engine cannot be built is dropped at seating, with
    # its reason in the notice — keep the notices for the error below.
    unseated: list[str] = []

    def note(kind: str, message: str) -> None:
        if kind == "unavailable":
            unseated.append(message)

    adapters = initialize_adapters(config, note)
    sched = None
    last_error: Optional[Exception] = None
    for adapter in adapters.values():
        if not hasattr(adapter, "attach_scheduler"):
            continue
        try:
            engine = adapter._get_engine()
            sched, _created = acquire_scheduler(engine)
            break
        except Exception as e:  # noqa: BLE001 — try the next seat
            last_error = e
            continue
    if sched is None:
        # The reasons ride along: an out-of-memory on the chip must not
        # read as "no scheduler available".
        if last_error is not None:
            unseated.append(f"{type(last_error).__name__}: {last_error}")
        raise ConfigError(
            "gateway needs at least one tpu-llm knight whose engine "
            "can be built — no scheduler available to serve"
            + (f" ({'; '.join(unseated)})" if unseated else "")
        ) from last_error
    if journal_dir is not None and sched.journal is None:
        from ..engine.session_journal import SessionJournal
        sched.attach_journal(SessionJournal(journal_dir))
    return sched


def _build_router(sched, replicas: int):
    """N-replica fleet around the scheduler's engine (ISSUE 17): the
    router owns session placement, migration, rolls, and failover;
    admission reads fleet-wide signals through router.signals()."""
    from ..router import SessionRouter, build_replicas, \
        set_active_router
    reps = build_replicas(sched.engine, replicas,
                          journal=sched.journal)
    router = SessionRouter(reps, journal=sched.journal)
    set_active_router(router)
    return router


def gateway_command(host: Optional[str] = None,
                    port: Optional[int] = None,
                    journal_dir: Optional[str] = None,
                    resume_dir: Optional[str] = None,
                    replicas: int = 1,
                    project_root: Optional[str] = None) -> int:
    project_root = project_root or os.getcwd()
    config = load_config(project_root)
    from ..gateway import Gateway

    if resume_dir is not None:
        # Boot-time recovery through the library seam
        # (engine/recovery.py — the factored `serve --resume` path):
        # committed turns replay into KV BEFORE the socket opens, so
        # the first Last-Event-ID reconnect finds its session restored.
        print(style.bold(f"\n  Resuming sessions from journal "
                         f"{resume_dir}..."))
        from ..engine.recovery import resume_from_journal
        r = resume_from_journal(resume_dir, config=config,
                                project_root=project_root)
        sched = r["scheduler"]
        print(style.dim(
            f"  replayed {r['turns']} committed turn(s) across "
            f"{r['sessions']} session(s)"))
        journal_dir = journal_dir or resume_dir
        if journal_dir != str(sched.journal.root):
            from ..engine.session_journal import SessionJournal
            sched.attach_journal(SessionJournal(journal_dir))
    else:
        sched = _build_scheduler(config, journal_dir)

    router = _build_router(sched, replicas) if replicas > 1 else None
    gw = Gateway(sched, host=host, port=port, intent_dir=journal_dir,
                 router=router)
    if router is not None:
        print(style.dim(f"  serving across {replicas} replicas "
                        f"({', '.join(r.name for r in router.replicas)})"))
    print(style.bold(f"\n  Gateway listening on "
                     f"http://{gw.host}:{gw.port}"))
    print(style.dim(
        "    POST /v1/chat/completions   (OpenAI-compatible, SSE)\n"
        "    POST /v1/discussions        (native multi-knight, SSE)\n"
        "    GET  /v1/streams/<id>       (Last-Event-ID reconnect)\n"
        "    POST /v1/admin/roll         (rolling restart, fleets)\n"
        "    GET  /healthz · GET /metrics\n"))
    gw.run()
    gw.stop()
    if router is not None:
        router.close()
    return 0
