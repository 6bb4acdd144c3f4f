"""`roundtable status` — show the latest session.

Parity with reference src/commands/status.ts:11-77.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

from ..utils.session import find_latest_session
from ..utils.ui import style

PHASE_DISPLAY = {
    "discussing": ("⚔️", "The knights are discussing", style.blue),
    "consensus_reached": ("✓", "Consensus reached", style.green),
    "escalated": ("!", "Escalated to the King", style.yellow),
    "applying": ("…", "The Lead Knight is applying the decision", style.cyan),
    "completed": ("✓", "Completed", style.green),
}

DECISIONS_PREVIEW_LINES = 10


def phase_display(status) -> tuple[str, str, object]:
    """(icon, label, color) for a SessionStatus, rejection-aware.

    The reference writes phase "consensus_reached" for unanimous rejection
    too (orchestrator.ts:616) and can't distinguish them afterward; we
    persist `unanimous_rejection` in status.json so the session lists
    don't misreport a rejected idea as an agreed decision.
    """
    if status.phase == "consensus_reached" and status.unanimous_rejection:
        return ("✗", "Unanimously rejected", style.red)
    return PHASE_DISPLAY.get(status.phase, ("?", status.phase, style.white))


def status_command(project_root: Optional[str] = None,
                   telemetry_view: bool = False,
                   perf_view: bool = False,
                   kv_view: bool = False,
                   health_view: bool = False,
                   gateway_view: bool = False,
                   fleet_view: bool = False,
                   capacity_view: bool = False,
                   slo_view: bool = False) -> int:
    project_root = project_root or os.getcwd()
    if health_view:
        # Fleet health needs no session dir — it reads the live
        # process's breaker/scheduler/supervisor state.
        return health_status()
    if gateway_view:
        # Gateway ledger is live-registry state too — no session dir.
        return gateway_status()
    if fleet_view:
        # Multi-replica serving view — live router + registry state.
        return fleet_status()
    if capacity_view:
        # Capacity frontier: file-based record vs live gateway gauges.
        return capacity_status(project_root)
    if slo_view:
        # SLO burn-rate view: capacity-record baseline vs live burn
        # gauges + trace retention (ISSUE 20).
        return slo_status(project_root)
    session = find_latest_session(project_root)
    if session is None:
        print(style.dim("\n  No sessions yet. "
                        'Start one with "roundtable discuss".\n'))
        return 0
    if kv_view:
        return kv_status(session)
    if perf_view:
        return perf_status(session)
    if telemetry_view:
        return telemetry_status(session)

    print(style.bold(f"\n  Latest session: {session.name}"))
    if session.topic:
        print(f"  Topic: {session.topic}")
    if session.status:
        s = session.status
        icon, label, color = phase_display(s)
        print(f"  Phase: {color(f'{icon} {label}')}")
        print(f"  Round: {s.round}")
        # consensus_reached is True for unanimous rejection too (schema
        # parity with the reference) — the display must not contradict
        # the rejection phase line above it
        consensus = ("unanimous rejection" if s.unanimous_rejection
                     else "yes" if s.consensus_reached else "no")
        print(f"  Consensus: {consensus}")
        if s.current_knight:
            print(f"  Current knight: {s.current_knight}")
        if s.lead_knight:
            print(f"  Lead knight: {s.lead_knight}")
        print(style.dim(f"  Started: {s.started_at}"))
        print(style.dim(f"  Updated: {s.updated_at}"))

    decisions = Path(session.path) / "decisions.md"
    if decisions.exists():
        lines = decisions.read_text(encoding="utf-8").split("\n")
        print(style.bold("\n  Decision preview:"))
        for line in lines[:DECISIONS_PREVIEW_LINES]:
            print(style.dim(f"    {line}"))
        if len(lines) > DECISIONS_PREVIEW_LINES:
            print(style.dim("    ..."))
    print("")
    return 0


METRICS_PREVIEW_LINES = 40
SPAN_PREVIEW_LINES = 8


def telemetry_status(session) -> int:
    """`roundtable status --telemetry` — render the latest session's
    view of the unified registry (ISSUE 5): the per-round Prometheus
    snapshot metrics.json's writer drops, the span-tree summary from
    spans.jsonl, and any flight-recorder dumps. All file-based: the
    serving process owns the live registry; these files are its
    per-round export (plus this process's own registry when serving
    in-process, e.g. `roundtable serve` foreground)."""
    import json as _json

    from ..utils import telemetry

    tdir = Path(session.path) / "telemetry"
    print(style.bold(f"\n  Telemetry — session {session.name}"))
    if not tdir.exists() and not telemetry.ACTIVE:
        print(style.dim(
            "  No telemetry captured. Run with ROUNDTABLE_TELEMETRY=1 "
            "to arm span tracing and the registry snapshot.\n"))
        return 0

    prom = tdir / "metrics.prom"
    if prom.exists():
        print(style.bold("\n  Registry snapshot (metrics.prom):"))
        lines = [ln for ln in
                 prom.read_text(encoding="utf-8").splitlines()
                 if ln and not ln.startswith("#")
                 and "_bucket{" not in ln]
        for ln in lines[:METRICS_PREVIEW_LINES]:
            print(style.dim(f"    {ln}"))
        if len(lines) > METRICS_PREVIEW_LINES:
            print(style.dim(f"    ... ({len(lines)} series total)"))
    elif telemetry.ACTIVE:
        # In-process view (serve foreground / tests): the live registry.
        print(style.bold("\n  Registry (live, this process):"))
        for k, v in sorted(
                telemetry.REGISTRY.snapshot_compact().items()):
            print(style.dim(f"    {k} {v:g}"))

    spans = tdir / "spans.jsonl"
    if spans.exists():
        per_rung: dict[str, int] = {}
        total = 0
        tail: list[dict] = []
        for line in spans.read_text(encoding="utf-8").splitlines():
            try:
                rec = _json.loads(line)
            except ValueError:
                continue
            total += 1
            per_rung[rec.get("rung", "?")] = \
                per_rung.get(rec.get("rung", "?"), 0) + 1
            tail.append(rec)
        print(style.bold(f"\n  Spans ({total} in spans.jsonl):"))
        print(style.dim("    " + "  ".join(
            f"{r}:{per_rung[r]}" for r in sorted(per_rung))))
        for rec in tail[-SPAN_PREVIEW_LINES:]:
            attrs = rec.get("attrs", {})
            who = attrs.get("session") or attrs.get("engine") or ""
            print(style.dim(
                f"    {rec.get('rung', '?'):<10} "
                f"{rec.get('dur_s', 0):>9.3f}s  "
                f"{rec.get('status', '')}  {who}"))

    dumps = sorted(Path(telemetry.dump_dir()).glob("flight-*.json")) \
        if Path(telemetry.dump_dir()).exists() else []
    if dumps:
        print(style.bold(f"\n  Flight-recorder dumps ({len(dumps)}):"))
        for p in dumps[-5:]:
            print(style.dim(f"    {p}"))
    print("")
    return 0


# --- `roundtable status --health` (ISSUE 12) ---


def health_status() -> int:
    """`roundtable status --health` — the fleet-health view: breaker
    state, the admission gate, scheduler queues, and the ISSUE 12
    supervision roll-up (restart totals, dead engines and why, and each
    engine's bounded restart history). Live-process state: meaningful
    from the serving process (serve foreground, tests, a REPL driving
    the fleet) — a fresh CLI process reports an idle fleet."""
    from ..engine.fleet import fleet_health

    h = fleet_health()
    print(style.bold("\n  Fleet health"))
    print(style.dim(
        f"    engines={h['total']}  breakers_open={h['open']}  "
        f"degraded={h['degraded']}  draining={h['draining']}  "
        f"hangs={h['hangs']}  queued_sessions={h['queued_sessions']}"))
    for s in h["schedulers"]:
        gate = ("closed" if s.get("closed")
                else f"paused:{s['paused']}" if s.get("paused")
                else "open")
        print(style.dim(
            f"    scheduler[{s['engine']}] queued={s['queued']} "
            f"active_rows={s['active_rows']} "
            f"sessions={len(s['sessions'])} (admission {gate})"))

    sup = h["supervisor"]
    print(style.bold("\n  Supervision (engine restarts):"))
    print(style.dim(
        f"    restarts={sup['restarts']}  "
        f"sessions_recovered={sup['sessions_recovered']}  "
        f"sessions_lost={sup['sessions_lost']}  "
        f"dead_engines={sup['dead_engines']}"))
    if not sup["engines"]:
        print(style.dim("    (no engine has ever needed a restart)"))
    for e in sup["engines"]:
        state = (style.red(f"DEAD: {e['dead_reason']}") if e["dead"]
                 else style.green("alive"))
        print(f"    {e['engine']}: {e['restarts']} restart(s), "
              f"{e['failed_restarts']} failed — {state}")
        for ev in e["history"][-5:]:
            ok = "ok" if ev.get("ok") else "FAILED"
            extra = ""
            if ev.get("restored_sessions") is not None:
                extra = f", restored {ev['restored_sessions']} session(s)"
            print(style.dim(
                f"      #{ev.get('restart', '?')} {ev.get('reason')}: "
                f"{ok} in {ev.get('wall_s', 0):.3f}s{extra}"))
    print("")
    return 0


# --- `roundtable status --gateway` (ISSUE 16) ---


def gateway_status() -> int:
    """`roundtable status --gateway` — the serving gateway's
    admission/shed ledger, rendered from the live registry's
    roundtable_gateway_* series: admitted/shed/queued/expired totals
    broken down by reason label, the inflight-stream gauge, and the
    resume / drop-to-summary counters. Live-process state like
    --health: meaningful from the serving process; a fresh CLI process
    reports an idle gateway."""
    from ..utils import telemetry

    series = telemetry.REGISTRY.snapshot_compact()
    print(style.bold("\n  Serving gateway"))

    def by_reason(outcome: str) -> dict[str, float]:
        name = f"roundtable_gateway_{outcome}_total"
        out: dict[str, float] = {}
        for key, val in series.items():
            if key.split("{", 1)[0] != name:
                continue
            out[_labels(key).get("reason", "?")] = val
        return out

    any_out = False
    for outcome in ("admitted", "shed", "queued", "expired"):
        reasons = by_reason(outcome)
        if not reasons:
            continue
        any_out = True
        total = sum(reasons.values())
        print(style.bold(f"\n  {outcome.capitalize()}: {total:g}"))
        for reason in sorted(reasons):
            print(style.dim(f"    {reason:<20} {reasons[reason]:g}"))

    inflight = [k for k in series
                if k.split("{", 1)[0]
                == "roundtable_gateway_inflight_streams"]
    if inflight:
        any_out = True
        print(style.bold(f"\n  Inflight streams: {len(inflight)}"))
        for k in sorted(inflight):
            lb = _labels(k)
            print(style.dim(f"    {lb.get('request', '?')}"))

    extras = [("roundtable_gateway_resumed_streams_total",
               "reconnects resumed"),
              ("roundtable_gateway_dropped_events_total",
               "events coalesced to summary (slow consumers)")]
    lines = []
    for name, label in extras:
        vals = [v for k, v in series.items()
                if k.split("{", 1)[0] == name]
        if vals:
            lines.append(f"    {label:<44} {sum(vals):g}")
    if lines:
        any_out = True
        print(style.bold("\n  Resilience:"))
        for ln in lines:
            print(style.dim(ln))

    # ISSUE 20: the TTFT stage split — the former one-lump TTFT
    # decomposed into the critical-path stages the tracer attributes,
    # aggregated over this process's recent traces.
    from ..utils import tracing
    recent = [r for r in tracing.store().recent()
              if r.get("stages")]
    if recent:
        any_out = True
        agg: dict[str, list[float]] = {}
        for r in recent:
            for stage, dur in r["stages"].items():
                agg.setdefault(stage, []).append(dur)
        print(style.bold(
            f"\n  TTFT stage split ({len(recent)} recent traces):"))
        print(style.dim("    stage            n      mean_s       p95_s"))
        for stage in tracing.STAGES:
            vals = sorted(agg.get(stage, ()))
            if not vals:
                continue
            p95 = vals[min(int(len(vals) * 0.95), len(vals) - 1)]
            print(style.dim(
                f"    {stage:<14}{len(vals):>4}"
                f"{sum(vals) / len(vals):>12.4f}{p95:>12.4f}"))

    if not any_out:
        print(style.dim(
            "\n  No gateway series in this process. Run `roundtable "
            "gateway` (or drive a Gateway in-process) to populate the "
            "admission/shed ledger.\n"))
    print("")
    return 0


# --- `roundtable status --slo` (ISSUE 20) ---


def slo_surface(frontier, record_path, series) -> dict:
    """The SLO view's machine shape: the capacity record's p95 SLO
    baseline joined with the live burn-rate gauges and trace
    retention. Keys are bound in telemetry.SURFACE_BINDINGS
    ["slo_status"] (RT-SURFACE-DRIFT)."""
    from ..utils import tracing

    th = (frontier or {}).get("derived_thresholds", {})
    p95 = float(th.get("p95_slo_s") or 0.0)
    mon = tracing.SloBurnMonitor(
        p95_slo_s=p95,
        source="capacity_record" if frontier else "default")

    def gauge(name: str, **labels) -> float:
        total = 0.0
        for key, val in series.items():
            if key.split("{", 1)[0] != name:
                continue
            lb = _labels(key)
            if any(lb.get(k) != v for k, v in labels.items()):
                continue
            total += val
        return total

    return {
        "armed": mon.armed,
        "p95_slo_s": p95,
        "source": mon.source,
        "record_path": record_path,
        "error_budget": mon.error_budget,
        "threshold": mon.threshold,
        "burn_fast": gauge("roundtable_slo_burn_rate", window="fast"),
        "burn_slow": gauge("roundtable_slo_burn_rate", window="slow"),
        "breaches": gauge("roundtable_slo_breaches_total"),
        "slo_dumps": gauge("roundtable_flight_dumps_total",
                           trigger="slo_burn"),
        "traces_retained": gauge("roundtable_traces_retained_total"),
    }


def slo_status(project_root: str) -> int:
    """`roundtable status --slo` — the SLO burn-rate view (ISSUE 20):
    the p95 TTFT SLO from the capacity frontier record, the live
    fast/slow burn-rate gauges against the error budget, breach /
    flight-dump counters, and trace retention. Live-process gauges
    like --gateway: a fresh CLI process shows the armed baseline with
    zero burn."""
    from ..utils import telemetry, tracing

    print(style.bold("\n  SLO burn rate"))
    path, frontier = _find_capacity_record(project_root)
    series = telemetry.REGISTRY.snapshot_compact()
    surf = slo_surface(frontier, path, series)

    armed = ("armed" if surf["armed"]
             else "DISARMED (no p95 SLO — sweep a capacity record)")
    print(style.dim(
        f"    {armed}  p95_slo_s={surf['p95_slo_s']:g}  "
        f"source={surf['source']}"))
    if surf["record_path"]:
        print(style.dim(f"    record: {surf['record_path']}"))
    print(style.bold("\n  Burn (bad-fraction / error budget):"))
    print(style.dim(
        f"    fast={surf['burn_fast']:g}  slow={surf['burn_slow']:g}  "
        f"budget={surf['error_budget']:g}  "
        f"fires at >{surf['threshold']:g} on BOTH windows"))
    print(style.bold("\n  Incidents:"))
    print(style.dim(
        f"    breaches={surf['breaches']:g}  "
        f"slo_burn flight dumps={surf['slo_dumps']:g}  "
        f"traces retained={surf['traces_retained']:g}"))
    recent = [r for r in tracing.store().recent()
              if "slo_violation" in r.get("flags", ())]
    if recent:
        print(style.bold("\n  Recent SLO-violating traces:"))
        for r in recent[-5:]:
            print(style.dim(
                f"    {r['trace_id']}  ttft={r.get('ttft_s', 0):g}s  "
                f"{r.get('session', '')}"))
    print("")
    return 0


# --- `roundtable status --fleet` (ISSUE 17) ---


def fleet_status() -> int:
    """`roundtable status --fleet` — the multi-replica serving view:
    per-replica liveness, session assignment and queue/row gauges from
    the live router (when this process serves one), plus every
    replica-labeled registry series — so an operator sees WHERE the
    sessions live, which replica is rolling/dead, and the router's
    migration / failover / roll history. Live-process state like
    --health: a fresh CLI process reports no fleet."""
    from ..router import active_router
    from ..utils import telemetry

    print(style.bold("\n  Multi-replica serving"))
    router = active_router()
    if router is not None:
        d = router.describe()
        print(style.dim(
            f"    replicas={len(d['replicas'])}  "
            f"sessions={d['sessions']}  "
            f"migrations={d['migrations']}  "
            f"failovers={d['failovers']}  rolls={d['rolls']}"
            + (f"  rolling={','.join(d['rolling'])}"
               if d["rolling"] else "")
            + (f"  retired={','.join(d['retired'])}"
               if d["retired"] else "")))
        for name, rep in sorted(d["replicas"].items()):
            state = (style.red(f"DEAD: {rep['dead']}") if rep["dead"]
                     else style.yellow(f"paused:{rep['paused']}")
                     if rep["paused"] else style.green("live"))
            print(f"    {name} [{rep['engine']}]: {state}")
            print(style.dim(
                f"      sessions={rep['sessions']}  "
                f"queued={rep['queued']}  "
                f"active_rows={rep['active_rows']}"))

    series = telemetry.REGISTRY.snapshot_compact()
    labeled = {k: v for k, v in series.items()
               if "replica=" in k}
    if labeled:
        print(style.bold("\n  Replica-labeled series:"))
        for k in sorted(labeled):
            print(style.dim(f"    {k} {labeled[k]:g}"))
    if router is None and not labeled:
        print(style.dim(
            "\n  No replica fleet in this process. Serve with "
            "`roundtable gateway --replicas N` (or `serve --replicas "
            "N`) to route sessions across N engine replicas.\n"))
    print("")
    return 0


# --- `roundtable status --capacity` (ISSUE 19) ---


def _find_capacity_record(project_root: str):
    """(path, frontier) of the capacity record to render:
    ROUNDTABLE_GATEWAY_CAPACITY_FILE when set, else the newest
    CAPACITY_r19.json under the project root. (None, None) when there
    is nothing loadable — an unreadable record prints WHY."""
    from ..gateway.admission import CAPACITY_FILE_ENV
    from ..loadgen.capacity import load_record

    candidates = []
    envp = os.environ.get(CAPACITY_FILE_ENV)
    if envp:
        candidates.append(envp)
    local = Path(project_root) / "CAPACITY_r19.json"
    if local.exists():
        candidates.append(str(local))
    for path in candidates:
        try:
            return path, load_record(path)
        except ValueError as e:
            print(style.red(f"  unreadable capacity record: {e}"))
    return None, None


def capacity_surface(frontier, record_path, series) -> dict:
    """The capacity view's machine shape: the measured frontier record
    next to the LIVE gateway ledger, so predicted-vs-measured and
    configured-vs-derived drift is one lookup. Keys are bound in
    telemetry.SURFACE_BINDINGS["capacity_status"] (RT-SURFACE-DRIFT)."""
    knee = frontier.get("knee", {})
    predicted = frontier.get("predicted") or {}
    gap = frontier.get("gap") or {}
    live_inflight = sum(
        1 for k in series
        if k.split("{", 1)[0] == "roundtable_gateway_inflight_streams")
    shed = sum(v for k, v in series.items()
               if k.split("{", 1)[0] == "roundtable_gateway_shed_total")
    admitted = sum(
        v for k, v in series.items()
        if k.split("{", 1)[0] == "roundtable_gateway_admitted_total")
    record_errors = sum(
        v for k, v in series.items()
        if k.split("{", 1)[0]
        == "roundtable_gateway_capacity_record_errors_total")
    return {
        "record_path": record_path,
        "knee_rate": knee.get("rate"),
        "knee_ttft_p95_s": knee.get("ttft_p95_s"),
        "measured_tok_s": knee.get("accepted_tok_s"),
        "predicted_tok_s": predicted.get("decode_ceiling_tps"),
        "gap_frac": gap.get("gap_frac"),
        "derived_thresholds": dict(
            frontier.get("derived_thresholds", {})),
        "points": len(frontier.get("points", [])),
        "live_inflight": live_inflight,
        "live_admitted": admitted,
        "live_shed": shed,
        "record_errors": record_errors,
    }


def capacity_status(project_root: str) -> int:
    """`roundtable status --capacity` — the measured capacity frontier
    (latest CAPACITY_r19.json / ROUNDTABLE_GATEWAY_CAPACITY_FILE)
    rendered against the live gateway gauges: per-rate frontier table,
    the perfmodel predicted curve vs the measured knee, the derived
    admission thresholds, and this process's admission ledger so an
    operator sees at a glance whether live load sits inside the
    measured envelope."""
    from ..utils import telemetry

    print(style.bold("\n  Capacity frontier"))
    path, frontier = _find_capacity_record(project_root)
    series = telemetry.REGISTRY.snapshot_compact()
    if frontier is None:
        print(style.dim(
            "\n  No capacity record found. Sweep one with `roundtable "
            "loadgen` (or `python bench_load.py`) — it writes "
            "CAPACITY_r19.json and ROUNDTABLE_GATEWAY_CAPACITY_FILE "
            "feeds it back into admission.\n"))
        return 0
    surf = capacity_surface(frontier, path, series)
    print(style.dim(f"    record: {path}"))
    if frontier.get("chip"):
        ch = frontier["chip"]
        print(style.dim(f"    chip: {ch.get('name')} "
                        f"({ch.get('source', '?')}), "
                        f"n_devices={frontier.get('n_devices', 1)}"))

    print(style.bold("\n  Frontier (measured):"))
    print(style.dim("    offered_rps  admitted  shed_rate  ttft_p95_s"
                    "  accepted_tok_s  sessions/chip"))
    for p in frontier.get("points", []):
        p95 = p.get("ttft_p95_s")
        print(style.dim(
            f"    {p['offered_rps']:>11.2f}  {p['admitted']:>8.0f}"
            f"  {p['shed_rate']:>9.3f}"
            f"  {p95 if p95 is None else f'{p95:.3f}':>10}"
            f"  {p['accepted_tok_s']:>14.1f}"
            f"  {p['sessions_per_chip']:>13.2f}"))
    knee = frontier.get("knee", {})
    rate = surf["knee_rate"]
    print(style.bold(
        f"\n  Knee: {f'{rate:.2f}' if rate is not None else '?'} "
        "sessions/s"))
    print(style.dim(f"    {knee.get('reason', '')}"))

    if surf["predicted_tok_s"] is not None:
        meas = surf["measured_tok_s"] or 0.0
        gapf = surf["gap_frac"]
        print(style.bold("\n  Predicted vs measured:"))
        print(style.dim(
            f"    roofline decode ceiling: "
            f"{surf['predicted_tok_s']:.1f} tok/s"))
        print(style.dim(f"    measured at knee:        {meas:.1f} tok/s"
                        + (f"  (gap {gapf * 100:.1f}%)"
                           if gapf is not None else "")))
        for name, frac in (frontier.get("gap", {})
                           .get("overheads", {}).items()):
            if isinstance(frac, (int, float)):
                print(style.dim(f"      {name:<24} {frac * 100:6.1f}%"))

    th = surf["derived_thresholds"]
    if th:
        print(style.bold("\n  Derived admission thresholds:"))
        print(style.dim(
            f"    max_inflight={th.get('max_inflight')}  "
            f"max_queue_depth={th.get('max_queue_depth')}  "
            f"p95_slo_s={th.get('p95_slo_s')}"))

    print(style.bold("\n  Live gateway (this process):"))
    print(style.dim(
        f"    inflight_streams={surf['live_inflight']:g}  "
        f"admitted={surf['live_admitted']:g}  "
        f"shed={surf['live_shed']:g}  "
        f"record_errors={surf['record_errors']:g}"))
    if not surf["live_admitted"] and not surf["live_inflight"]:
        print(style.dim(
            "    (idle — run the gateway in-process to compare live "
            "load against the frontier)"))
    print("")
    return 0


# --- `roundtable status --kv` (ISSUE 7) ---


def kv_status(session) -> int:
    """`roundtable status --kv` — the KV-tier view: the paged-pool
    memory ledger with its cross-session sharing split (shared pages
    counted once), the prefix cache's hit/miss/eviction series, the
    host-RAM offload tier's spill state, and per-session KV footprints.
    Same sourcing as --perf: the session's metrics.prom export overlaid
    with this process's live registry."""
    print(style.bold(f"\n  KV tiers — session {session.name}"))
    series = _series_for_perf(session)

    def section(title: str, prefixes: tuple[str, ...]) -> bool:
        keys = sorted(k for k in series
                      if k.split("{")[0].startswith(prefixes))
        if not keys:
            return False
        print(style.bold(f"\n  {title}:"))
        for k in keys:
            print(style.dim(f"    {k} {series[k]:g}"))
        return True

    any_out = section("Memory ledger (HBM tier)", (
        "roundtable_kv_slots", "roundtable_kv_slot_",
        "roundtable_kv_cached", "roundtable_kv_pages",
        "roundtable_kv_page_", "roundtable_kv_fragmentation",
        "roundtable_kv_shared_pages", "roundtable_kv_exclusive_pages",
        "roundtable_kv_hbm_bytes", "roundtable_hbm_"))
    # ISSUE 11: the quantized-page dtype split — kv_dtype rendered
    # from the bits gauge (0 = bf16 pool), logical vs resident bytes
    # and the saved delta next to each other so the compression claim
    # is auditable from the same screen as the residency it frees.
    quant_keys = [k for k in series
                  if k.split("{")[0] == "roundtable_kv_quant_bits"]
    if quant_keys:
        print(style.bold("\n  Quantized KV pages (ISSUE 11):"))
        for k in sorted(quant_keys):
            lb = _labels(k)
            bits = int(series[k])
            dtype = {8: "int8", 4: "int4"}.get(bits, "bf16")
            eng = lb.get("engine", "?")
            logical = series.get(
                f"roundtable_kv_bytes_logical{{engine={eng}}}", 0)
            saved = series.get(
                f"roundtable_kv_quant_bytes_saved{{engine={eng}}}", 0)
            print(style.dim(
                f"    {eng:<16} kv_dtype={dtype:<5} "
                f"kv_bytes_logical={logical:g} "
                f"kv_bytes_resident={logical - saved:g} "
                f"saved={saved:g}"))
        any_out = True
    any_out |= section("Prefix cache (cross-session index)",
                       ("roundtable_prefix_",))
    any_out |= section("Host-RAM offload tier", (
        "roundtable_kv_spill", "roundtable_kv_restores",
        "roundtable_kv_spilled_sessions", "roundtable_kv_host_bytes"))

    sess_keys = [k for k in series
                 if k.split("{")[0] == "roundtable_session_kv_bytes"
                 and series[k] > 0]
    if sess_keys:
        print(style.bold("\n  Per-session KV footprint:"))
        for k in sorted(sess_keys):
            lb = _labels(k)
            print(style.dim(f"    {lb.get('session', '?'):<24}"
                            f"{series[k] / 1e6:10.2f} MB"))
        any_out = True
    if not any_out:
        print(style.dim(
            "\n  No KV series captured. Serve an engine with "
            "ROUNDTABLE_TELEMETRY=1 to populate the "
            "ledger, prefix-cache and offload series.\n"))
    print("")
    return 0


# --- `roundtable status --perf` (ISSUE 6) ---


def _series_for_perf(session) -> dict[str, float]:
    """Perf registry series, compact-key → value: the session's
    metrics.prom export where present, overlaid with the LIVE registry
    when this process is serving (live values are fresher)."""
    from ..utils import telemetry

    series: dict[str, float] = {}
    prom = Path(session.path) / "telemetry" / "metrics.prom"
    if prom.exists():
        for ln in prom.read_text(encoding="utf-8").splitlines():
            if not ln or ln.startswith("#") or "_bucket{" in ln:
                continue
            key, _, val = ln.rpartition(" ")
            try:
                series[key.replace('"', "")] = float(val)
            except ValueError:
                continue
    series.update(telemetry.REGISTRY.snapshot_compact())
    return series


def _labels(key: str) -> dict[str, str]:
    if "{" not in key:
        return {}
    body = key[key.index("{") + 1:key.rindex("}")]
    return dict(part.split("=", 1) for part in body.split(",") if "=" in
                part)


def _by_engine(series: dict[str, float],
               name: str) -> dict[str, tuple[float, dict]]:
    """{engine: (value, labels)} for one series name."""
    out: dict[str, tuple[float, dict]] = {}
    for key, val in series.items():
        if key.split("{", 1)[0] != name:
            continue
        labels = _labels(key)
        eng = labels.get("engine", "?")
        out[eng] = (val, labels)
    return out


STARVED_SERIES = "roundtable_sched_starved_seconds_total"
PAGE_COPIES_SERIES = "roundtable_page_copies_total"
PAGE_COPY_PROGRAMS_SERIES = "roundtable_page_copy_programs_total"
DISPATCH_SERIES_PREFIX = "roundtable_dispatch_"
SETUP_SECONDS_SERIES = "roundtable_setup_seconds_total"
SETUP_PROGRAMS_SERIES = "roundtable_setup_programs_total"
SETUP_BODIES_SERIES = "roundtable_setup_bodies_total"


def print_setup_split(setup: dict) -> None:
    """Where a start's seconds went (compile_watch.summary()["setup"],
    ISSUE 54): thread-seconds by stage of bringing a program up, wall
    seconds by phase of the build, the programs compiled fresh by label
    and those lowered more than once, and how many calls of a layer's
    body traced it and how many found it traced (ISSUE 55). Printed by
    `status --perf` and at the end of `roundtable warmup`."""
    stages, phases = setup["stages"], setup["phases"]
    if not (any(stages.values()) or any(phases.values())):
        return

    def row(d: dict) -> str:
        return "  ".join(f"{k} {v:.1f}s" for k, v in d.items())

    state = "closed" if setup.get("closed") else "open"
    head = f"    set-up: {setup['wall_s']:.1f}s wall ({state})  " \
        if setup["wall_s"] else "    set-up:  "
    print(style.dim(
        f"{head}programs={setup['programs']}  "
        f"bodies traced={setup.get('bodies_traced', 0)} "
        f"reused={setup.get('bodies_reused', 0)}  "
        f"cache_hits={setup['cache_hits']}  "
        f"cache_misses={setup['cache_misses']}  "
        f"saved={setup['saved_s']:.1f}s"))
    print(style.dim(f"      stages (thread-seconds): {row(stages)}"))
    unmarked = setup["wall_s"] - sum(phases.values())
    print(style.dim(
        f"      phases (wall): {row(phases)}"
        + (f"  unmarked {unmarked:.1f}s" if setup["wall_s"] else "")))
    if any(setup.get("staged", {}).values()):
        print(style.dim(f"      of them in a stage: "
                        f"{row(setup['staged'])}"))
    for title, counts in (("compiled fresh", setup["misses"]),
                          ("lowered more than once", setup["twice"])):
        if counts:
            print(style.dim(f"      {title}: " + ", ".join(
                f"{k} x{n}" for k, n in counts.items())))
    for r in setup["slowest"]:
        last = "retrieve" if r.get("cache_hit") else "compile"
        print(style.dim(
            f"      {r['label']:<30} {r['fun_name']:<28} "
            f"trace {r['trace_s']:.2f}  lower {r['lower_s']:.2f}  "
            f"{last} {r.get(last + '_s', 0.0):.2f}"))


def perf_status(session) -> int:
    """`roundtable status --perf` — live performance attribution from
    the unified registry (ISSUE 6): the per-engine roofline table
    (ceiling, decode rate, and the seconds the scheduler's loop left
    the device unfed, by phase), the page copies each program of the
    page cache's copier gathered and which copier that is, the host
    buffers and launches a step program cost, the compile
    observatory's history, steady-state sentinel state and split of
    the set-up, the memory ledger, and the span-tree overhead
    breakdown."""
    from ..utils import perfmodel, telemetry

    print(style.bold(f"\n  Performance — session {session.name}"))
    series = _series_for_perf(session)
    perf = perfmodel.perf_series(series)

    # --- roofline table ---
    ceilings = _by_engine(perf, "roundtable_decode_ceiling_tps")
    engines = sorted(
        set(ceilings)
        | {lb.get("engine", "?") for k in perf
           for lb in [_labels(k)] if "engine" in lb})
    if engines and any(k.split("{")[0].startswith(
            ("roundtable_decode", STARVED_SERIES)) for k in perf):
        print(style.bold("\n  Roofline (per engine):"))
        print(style.dim("    engine            ceiling_tps  decode_tps"
                        "  starved_s (the device unfed, by loop phase)"))
        for eng in engines:
            def val(name):
                for key, v in perf.items():
                    if (key.split("{", 1)[0] == name
                            and _labels(key).get("engine") == eng):
                        return v
                return None

            def fmt(v):
                return "         -" if v is None else f"{v:10.1f}"

            # Where the scheduler's loop left the device with no step
            # program of its own outstanding (the loop clock's feed
            # bit), largest phase first; replicas of one engine add up.
            starved: dict[str, float] = {}
            for key, v in perf.items():
                lb = _labels(key)
                if (key.split("{", 1)[0] == STARVED_SERIES
                        and lb.get("engine") == eng and v > 0):
                    phase = lb.get("phase", "?")
                    starved[phase] = starved.get(phase, 0.0) + v
            by_phase = " ".join(
                f"{phase}={sec:.3f}" for phase, sec in sorted(
                    starved.items(), key=lambda kv: -kv[1]))
            print(style.dim(
                f"    {eng:<18}{fmt(val('roundtable_decode_ceiling_tps'))}"
                f"{fmt(val('roundtable_decode_tps'))}"
                f"  {by_phase or '-'}"))

    # --- page copies (ISSUE 38) ---
    # A copy waits on the page cache for the next program that takes
    # the pools; copies a program says how much each flush gathered.
    copies: dict[str, dict[str, float]] = {}
    programs: dict[str, float] = {}
    paths: dict[str, set[str]] = {}
    for key, v in perf.items():
        name, lb = key.split("{", 1)[0], _labels(key)
        eng = lb.get("engine", "?")
        if name == PAGE_COPIES_SERIES:
            by_cause = copies.setdefault(eng, {})
            cause = lb.get("cause", "?")
            by_cause[cause] = by_cause.get(cause, 0.0) + v
        elif name == PAGE_COPY_PROGRAMS_SERIES:
            programs[eng] = programs.get(eng, 0.0) + v
            paths.setdefault(eng, set()).add(lb.get("path", "?"))
    if copies:
        print(style.bold("\n  Page copies (per engine):"))
        print(style.dim("    engine              copies  programs"
                        "  copies/program  by cause  path (dma, or why"
                        " XLA's gather and scatter)"))
        for eng in sorted(copies):
            n, progs = sum(copies[eng].values()), programs.get(eng, 0.0)
            per = f"{n / progs:14.1f}" if progs else "             -"
            by_cause = " ".join(
                f"{cause}={v:g}" for cause, v in sorted(
                    copies[eng].items(), key=lambda kv: -kv[1]))
            path = " | ".join(sorted(paths.get(eng, "?")))
            print(style.dim(f"    {eng:<18}{n:8g}{progs:10g}  {per}"
                            f"  {by_cause}  {path}"))

    # --- dispatches (ISSUE 53) ---
    # What the step seams sent and issued: 1.0 and 1.0 where a dispatch
    # travels as one packed buffer; more where a path still sends its
    # arrays one by one or issues helpers of its own.
    issued: dict[str, dict[str, float]] = {}
    for key, v in perf.items():
        name = key.split("{", 1)[0]
        if name.startswith(DISPATCH_SERIES_PREFIX):
            what = name[len(DISPATCH_SERIES_PREFIX):-len("_total")]
            by = issued.setdefault(_labels(key).get("engine", "?"), {})
            by[what] = by.get(what, 0.0) + v
    if issued:
        print(style.bold("\n  Dispatches (per engine):"))
        print(style.dim("    engine            programs  host_buffers"
                        "  launches  buffers/program  launches/program"))
        for eng in sorted(issued):
            by = issued[eng]
            progs = by.get("programs", 0.0)

            def per(n):
                return f"{n / progs:16.2f}" if progs else "               -"

            bufs, launches = (by.get("host_buffers", 0.0),
                              by.get("launches", 0.0))
            print(style.dim(f"    {eng:<18}{progs:8g}{bufs:14g}"
                            f"{launches:10g} {per(bufs)} {per(launches)}"))

    # --- compile observatory ---
    from ..engine import compile_watch
    summary = compile_watch.summary(recent=6)
    print(style.bold("\n  Compile observatory:"))
    print(style.dim(
        f"    mode={summary['mode']}  compiles={summary['compiles']}  "
        f"cache_hits={summary['cache_hits']}  "
        f"steady_state={summary['steady_state'] or 'not declared'}  "
        f"steady_compiles={summary['steady_state_compiles']}"
        + ("  STRICT" if summary["strict"] else "")))
    for e in summary.get("recent", []):
        flag = " [STEADY-STATE]" if e.get("steady_state") else ""
        hit = " (cache hit)" if e.get("cache_hit") else ""
        print(style.dim(f"    {e['label']:<32} {e['dur_s']:>8.3f}s"
                        f"{hit}{flag}"))
    setup = summary["setup"]
    if not setup["wall_s"]:
        # Another process served: its set-up's seconds by stage and
        # phase, and its hits and misses, are in the exported series.
        by = {_labels(k).get("stage"): v for k, v in perf.items()
              if k.split("{")[0] == SETUP_SECONDS_SERIES}
        outcomes = {_labels(k).get("outcome"): int(v)
                    for k, v in perf.items()
                    if k.split("{")[0] == SETUP_PROGRAMS_SERIES}
        bodies = {_labels(k).get("outcome"): int(v)
                  for k, v in perf.items()
                  if k.split("{")[0] == SETUP_BODIES_SERIES}
        setup = dict(setup, staged={}, stages={k: by.get(k, 0.0)
                                    for k in compile_watch.STAGES},
                     phases={k: by.get(k, 0.0)
                             for k in compile_watch.PHASES},
                     programs=sum(outcomes.values()),
                     bodies_traced=bodies.get("traced", 0),
                     bodies_reused=bodies.get("reused", 0),
                     cache_hits=outcomes.get("hit", 0),
                     cache_misses=outcomes.get("miss", 0))
    print_setup_split(setup)
    total = sum(v for k, v in perf.items()
                if k.split("{")[0] == "roundtable_compiles_total")
    steady = sum(v for k, v in perf.items()
                 if k.split("{")[0]
                 == "roundtable_steady_state_compiles_total")
    if total:
        print(style.dim(f"    registry: {total:g} compiles recorded, "
                        f"{steady:g} in steady state"))

    # --- memory ledger ---
    mem_keys = [k for k in perf if k.split("{")[0].startswith(
        ("roundtable_kv_", "roundtable_hbm_"))]
    if mem_keys:
        print(style.bold("\n  Memory ledger:"))
        for k in sorted(mem_keys):
            print(style.dim(f"    {k} {perf[k]:g}"))
    sess_keys = [k for k in perf
                 if k.split("{")[0] == "roundtable_session_kv_bytes"
                 and perf[k] > 0]
    if sess_keys:
        print(style.bold("\n  Per-session KV footprint:"))
        for k in sorted(sess_keys):
            lb = _labels(k)
            print(style.dim(f"    {lb.get('session', '?'):<24}"
                            f"{perf[k] / 1e6:10.2f} MB"))

    # --- span-tree overheads ---
    spans = telemetry.recorder().span_events()
    if not spans:
        spans_file = Path(session.path) / "telemetry" / "spans.jsonl"
        if spans_file.exists():
            import json as _json
            spans = []
            for ln in spans_file.read_text(encoding="utf-8").splitlines():
                try:
                    spans.append(_json.loads(ln))
                except ValueError:
                    continue
    over = perfmodel.span_overheads(spans) if spans else {}
    rungs = {k: v for k, v in over.items() if isinstance(v, dict)}
    if rungs:
        print(style.bold("\n  Overhead breakdown (per rung):"))
        print(style.dim("    rung        total_s  dispatch  host_sync"
                        "   gap"))
        for rung, a in sorted(rungs.items()):
            print(style.dim(
                f"    {rung:<10}{a['total_s']:>9.3f}"
                f"  {a['dispatch_frac'] * 100:6.1f}%"
                f"  {a['host_sync_frac'] * 100:7.1f}%"
                f"  {a['gap_frac'] * 100:5.1f}%"))
        if "queue_wait_s" in over:
            print(style.dim(
                f"    queue wait  {over['queue_wait_s']:.3f}s total"))
    if not perf and not spans:
        print(style.dim(
            "\n  No perf series captured. Serve with "
            "ROUNDTABLE_TELEMETRY=1 (and on CPU set "
            "ROUNDTABLE_PERF_CHIP=v5e for an assumed roofline).\n"))
    print("")
    return 0
