"""Thin telemetry seams for the engine layers.

The engines/serving loop talk to utils/telemetry through this module so
the per-call publishing lives ONCE: both engines publish a GenStats the
same way, every int4 routing decision counts the same way, and a future
engine gets the whole surface by importing two functions. Nothing here
touches jax — it is host-side counter/span plumbing only, and every
function is cheap enough to run unguarded at CALL rate (per round/turn,
never per token); hot per-segment/per-dispatch span call sites pre-guard
with `if telemetry.ACTIVE:` at the caller.
"""

from __future__ import annotations

from typing import Any, Optional

from ..utils import telemetry

span = telemetry.span  # re-export: engine call sites read trace_hooks.span


def publish_gen_stats(stats, engine_name: str) -> None:
    """Fold one generate call's GenStats into the registry — the
    engine-stats store metrics.json/bench records become views of."""
    if stats is None:
        return
    reg = telemetry.REGISTRY
    if stats.prefill_tokens:
        reg.inc("roundtable_prefill_tokens_total", stats.prefill_tokens,
                engine=engine_name)
    if stats.reused_tokens:
        reg.inc("roundtable_reused_tokens_total", stats.reused_tokens,
                engine=engine_name)
    if stats.decode_tokens:
        reg.inc("roundtable_decode_tokens_total", stats.decode_tokens,
                engine=engine_name)
    if stats.decode_seconds:
        reg.inc("roundtable_decode_seconds_total", stats.decode_seconds,
                engine=engine_name)
        reg.set_gauge("roundtable_decode_tps", stats.decode_tps,
                      engine=engine_name)
    if stats.prefill_seconds:
        reg.inc("roundtable_prefill_seconds_total",
                stats.prefill_seconds, engine=engine_name)
    sched = stats.sched or {}
    if sched.get("queue_wait_s") is not None:
        reg.observe("roundtable_queue_wait_seconds",
                    sched["queue_wait_s"])
    if sched.get("occupancy_mean") is not None:
        reg.set_gauge("roundtable_batch_occupancy",
                      sched["occupancy_mean"], engine=engine_name)


def publish_int4_paths(report: Optional[dict],
                       engine_name: str) -> None:
    """Registry view of the int4 path-provenance sink (PR 3): one gauge
    pair per engine — distinct kernel dispatches vs distinct XLA
    fallbacks — plus a counter per fallback reason, so a silent-fallback
    regression shows up on a dashboard, not only in describe()."""
    if not report:
        return
    reg = telemetry.REGISTRY
    reg.set_gauge("roundtable_int4_kernel_dispatches",
                  len(report.get("pallas_w4a16", ())),
                  engine=engine_name)
    reg.set_gauge("roundtable_int4_fallback_dispatches",
                  len(report.get("xla_dequant", ())),
                  engine=engine_name)
    for entry in report.get("xla_dequant", ()):
        reason = entry.get("fallback_reason") or "unknown"
        # Gauge not counter: the sink is cumulative per engine and this
        # re-publishes per call — a counter would multiply-count.
        reg.set_gauge("roundtable_int4_fallbacks", 1.0,
                      engine=engine_name, reason=reason[:60])


def publish_memory_ledger(engine) -> dict[str, Any]:
    """The memory ledger (ISSUE 6): fold one engine's KV-cache
    accounting and device HBM state into registry gauges, returning
    the ledger dict for describe()/tests.

    HBM comes from `device.memory_stats()` where the backend reports
    it; backends that don't (the CPU) fall back to
    `fleet.estimate_engine_hbm_bytes` under a gauge name that says so
    (`_estimated`) — an estimate must never impersonate a measurement.
    Event-rate cheap: host dict math over slot bookkeeping only."""
    reg = telemetry.REGISTRY
    name = engine.cfg.name
    ledger: dict[str, Any] = engine.kv.memory_ledger()
    reg.set_gauge("roundtable_kv_slots_in_use",
                  ledger["slots_in_use"], engine=name)
    reg.set_gauge("roundtable_kv_slot_occupancy",
                  ledger["slot_occupancy"], engine=name)
    reg.set_gauge("roundtable_kv_cached_tokens",
                  ledger["cached_tokens"], engine=name)
    reg.set_gauge("roundtable_kv_pages_in_use",
                  ledger["pages_in_use"], engine=name)
    reg.set_gauge("roundtable_kv_pages_total",
                  ledger["usable_pages"], engine=name)
    reg.set_gauge("roundtable_kv_page_utilization",
                  ledger["page_utilization"], engine=name)
    reg.set_gauge("roundtable_kv_fragmentation",
                  ledger["fragmentation"], engine=name)
    # ISSUE 7: the cross-session sharing split — shared pages
    # counted ONCE in pages_in_use; this makes the dedup
    # visible (and auditable) on a dashboard.
    reg.set_gauge("roundtable_kv_shared_pages",
                  ledger.get("shared_pages", 0), engine=name)
    reg.set_gauge("roundtable_kv_exclusive_pages",
                  ledger.get("exclusive_pages", 0), engine=name)
    reg.set_gauge("roundtable_prefix_cache_pages",
                  ledger.get("prefix_cache_pages", 0),
                  engine=name)
    # ISSUE 11: the quantized-page split — resident (payload +
    # scales, what the pools actually cost) vs logical (the
    # same pools at bf16 cells); bits=0 marks a bf16 pool so a
    # dashboard can tell "quantization off" from "no data".
    reg.set_gauge("roundtable_kv_quant_bits",
                  ledger.get("kv_quant_bits", 0), engine=name)
    reg.set_gauge("roundtable_kv_bytes_logical",
                  ledger.get("kv_bytes_logical",
                             ledger.get("hbm_bytes", 0)),
                  engine=name)
    reg.set_gauge("roundtable_kv_quant_bytes_saved",
                  ledger.get("kv_quant_bytes_saved", 0),
                  engine=name)
    reg.set_gauge("roundtable_kv_hbm_bytes", ledger["hbm_bytes"],
                  engine=name)
    # ISSUE 10: the multi-LoRA adapter store's HBM footprint rides
    # the same ledger publish — resident personas and what each costs,
    # next to the KV split they multiply scenario coverage against.
    store = getattr(engine, "lora", None)
    if store is not None:
        ledger["lora_resident_adapters"] = len(store.resident())
        ledger["lora_adapter_bytes"] = store.adapter_bytes()
        ledger["lora_stack_bytes"] = store.stack_bytes()
        reg.set_gauge("roundtable_lora_resident_adapters",
                      ledger["lora_resident_adapters"], engine=name)
        reg.set_gauge("roundtable_lora_stack_bytes",
                      ledger["lora_stack_bytes"], engine=name)
    # ISSUE 7: the host-RAM offload tier's footprint rides the same
    # ledger publish (sessions parked out of HBM + what they cost in
    # host bytes).
    tier = getattr(engine, "kv_offload", None)
    if tier is not None:
        ledger["spilled_sessions"] = len(tier.spilled_sessions())
        ledger["host_bytes"] = tier.host_bytes()
        reg.set_gauge("roundtable_kv_spilled_sessions",
                      ledger["spilled_sessions"], engine=name)
        reg.set_gauge("roundtable_kv_host_bytes",
                      ledger["host_bytes"], engine=name)
    stats = None
    try:
        stats = engine.mesh.devices.flatten()[0].memory_stats()
    except Exception:  # noqa: BLE001 — unsupported backends return/raise
        stats = None
    if stats and stats.get("bytes_in_use") is not None:
        reg.set_gauge("roundtable_hbm_bytes_in_use",
                      stats["bytes_in_use"], engine=name)
        if stats.get("bytes_limit"):
            reg.set_gauge("roundtable_hbm_bytes_limit",
                          stats["bytes_limit"], engine=name)
        ledger["hbm_bytes_in_use"] = int(stats["bytes_in_use"])
    else:
        try:
            from .fleet import estimate_engine_hbm_bytes
            cfg_dict: dict[str, Any] = {
                "max_seq_len": engine.max_seq_len,
                "num_slots": engine.kv.num_slots,
            }
            if getattr(engine, "quant", "none") != "none":
                cfg_dict["quant"] = engine.quant
            est = estimate_engine_hbm_bytes(cfg_dict,
                                            model_cfg=engine.cfg)
            reg.set_gauge("roundtable_hbm_bytes_estimated", est,
                          engine=name)
            ledger["hbm_bytes_estimated"] = est
        except Exception:  # noqa: BLE001 — the ledger is best-effort
            pass
    from ..utils import perfmodel
    perfmodel.note_published(1)
    return ledger


def _engine_labeled(key: str, engine_name: str) -> bool:
    """True when the flattened series key carries EXACTLY the label
    engine=<engine_name>. Label-element comparison, not substring: a
    fleet with engines 'knight' and 'knight2' must not fold knight2's
    series into knight's view on a prefix match."""
    if "{" not in key:
        return False
    labels = key[key.index("{") + 1:key.rindex("}")]
    return f"engine={engine_name}" in labels.split(",")


def engine_telemetry_view(engine_name: str) -> dict[str, Any]:
    """The describe() embed: this engine's registry series + flight
    recorder state (one store, viewed per engine)."""
    snap = telemetry.REGISTRY.snapshot_compact()
    mine = {k: v for k, v in snap.items()
            if _engine_labeled(k, engine_name)}
    rec = telemetry.recorder()
    return {"metrics": mine, "flight_dumps": rec.dumps,
            "last_flight_dump": rec.last_dump_path,
            "armed": telemetry.ACTIVE}
