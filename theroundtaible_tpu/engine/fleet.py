"""Heterogeneous multi-model fleet planning.

BASELINE.md config 3 serves three DIFFERENT checkpoints (Gemma-7B /
Llama-3-8B / Mistral-7B) from one pod at once — a capability with no
reference counterpart (the reference time-multiplexes Ollama's single GPU;
SURVEY.md §2.3 "heterogeneous multi-model scheduler"). The TPU answer is
spatial: partition the pod's chips into disjoint per-model submeshes sized
by each model's weight footprint, so every model is resident and the
orchestrator can fan a round out to all knights concurrently.

`plan_fleet` runs at adapter-initialization time (before any engine is
built): it groups the knights' tpu-llm engine configs by model identity,
sizes each group's submesh (power-of-two growth, weighted by parameter
bytes), and injects the chosen device indices into each config. Engines
then build their meshes over exactly those chips.
"""

from __future__ import annotations

import warnings
from typing import Any, Optional

from .models.common import ModelConfig
from .models.registry import resolve_model_config

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def estimate_param_count(cfg: ModelConfig) -> int:
    """Closed-form parameter count (no arrays built)."""
    e, h, k, d, f = (cfg.embed_dim, cfg.num_heads, cfg.num_kv_heads,
                     cfg.head_dim, cfg.mlp_dim)
    if cfg.layer_kinds is not None:
        # One mixer a layer (models/hybrid.py), counted by kind; the
        # experts are the ones HELD here, the router the published width.
        d_in, conv = cfg.mamba_d_inner, cfg.mamba_conv_dim
        per_kind = {
            "mamba2": (e * (d_in + conv + cfg.mamba_heads) + d_in * e
                       + (cfg.conv_kernel + 1) * conv
                       + 3 * cfg.mamba_heads + d_in),
            "experts": ((3 if cfg.expert_gated else 2) * e * (
                cfg.experts_held * cfg.expert_dim + cfg.shared_expert_dim)
                + (e + (cfg.router_rule == "sigmoid_bias_topk"))
                * cfg.routed_experts),
            # (and the two head norms, where q and k are normed a head)
            "attention": (2 * e * h * d + 2 * e * k * d
                          + (2 * d if cfg.qk_norm else 0)),
            "mlp": 3 * e * f,
            # in (E -> 3E), the taps, out (models/shortconv.py)
            "shortconv": 4 * e * e + cfg.conv_kernel * e,
            # q, k, v, o, one gate a kv head, the two head norms
            "retention": 2 * e * h * d + 2 * e * k * d + e * k + 2 * d,
        }
        if cfg.mamba1_layers:
            # in, conv and its bias, x, the three small norms (where the
            # model has them), dt and its bias, A_log, D, out
            # (models/mamba1.py); a gated memory unit: in and out
            d1, n, r = cfg.mamba1_dim, cfg.ssm_state, cfg.dt_rank
            per_kind["mamba1"] = (
                2 * e * d1 + (cfg.conv_kernel + 1) * d1
                + d1 * (r + 2 * n)
                + (r + 2 * n if cfg.mamba1_norms else 0) + (r + 1) * d1
                + n * d1 + d1 + d1 * e)
            per_kind["gmu"] = 2 * e * d1
        if cfg.diff_attn:
            # (models/diffattn.py) q and o, four lambda vectors, the
            # pair norm and the stored l0; an attention layer also k and
            # v; every projection's bias where the model has them
            cross = 2 * e * h * d + 6 * d + 1
            bias = (h * d + e, 2 * k * d) if cfg.attn_bias else (0, 0)
            per_kind["cross"] = cross + bias[0]
            per_kind["attention"] = cross + 2 * e * k * d + sum(bias)
        if cfg.latent:
            r_q, r_kv = cfg.q_lora_rank, cfg.kv_lora_rank
            per_kind["attention"] = (
                e * r_q + r_q + r_q * h * d
                + e * (r_kv + cfg.qk_rope_dim) + r_kv
                + r_kv * h * (cfg.qk_nope_dim + cfg.v_head_dim)
                + h * cfg.v_head_dim * e)
        # (a norm a layer and the final one: weight, and bias where it
        # is a LayerNorm)
        norm = 2 * e if cfg.layer_norm else e
        total = (sum(per_kind[kind] + norm for kind in cfg.layer_kinds)
                 + (1 if cfg.tie_embeddings else 2) * cfg.vocab_size * e
                 + norm)
        if cfg.attn_layers is not None or cfg.attn_gate:
            # Each attention layer's own heads (q and out-projection
            # above were counted at the model-level `h`), and its gate.
            gate = e if cfg.attn_gate else 0
            total += sum((v.num_heads - h) * 2 * e * d
                         + v.num_heads * gate
                         for v in cfg.attention_views)
        return total
    mlp = 3 * e * f
    if cfg.num_experts:
        mlp = cfg.num_experts * 3 * e * f + e * cfg.num_experts  # + router
    per_layer = 2 * e * h * d + 2 * e * k * d + mlp + 2 * e
    if cfg.attn_bias:  # Qwen2: q/k/v projection biases
        per_layer += h * d + 2 * k * d
    total = cfg.num_layers * per_layer + cfg.vocab_size * e + e
    if not cfg.tie_embeddings:
        total += cfg.vocab_size * e
    return total


def estimate_engine_hbm_bytes(engine_cfg: dict[str, Any],
                              model_cfg: Optional[ModelConfig] = None) -> int:
    """Closed-form resident HBM bytes for one engine (no arrays built):
    weights (quant-aware) + KV pool + an activation/workspace margin.

    Approximate by design — the point is to catch a fleet misconfiguration
    at plan time with a clear message instead of minutes later as an
    opaque XLA allocation error. Margins err high (weights dominate)."""
    if model_cfg is None:
        model_cfg = resolve_model_config(engine_cfg)
    max_seq = int(engine_cfg.get("max_seq_len") or model_cfg.max_seq_len)
    n_params = estimate_param_count(model_cfg)
    dtype_b = _DTYPE_BYTES.get(engine_cfg.get("dtype", "bfloat16"), 2)
    # int8: 1 byte per weight + per-output-channel scales (~a few % of
    # leaf count) — 1.05 covers every registry family's scale overhead.
    # int4: packed nibbles (0.5 B) + per-group scales (2 B / 64-group)
    # — 0.58 covers scales plus the few leaves that fall back to int8.
    quant = engine_cfg.get("quant")
    w_bytes = int(n_params * (1.05 if quant == "int8"
                              else 0.58 if quant == "int4"
                              else dtype_b))
    num_slots = int(engine_cfg.get("num_slots", 4))
    # Default pool: half of every slot at max_seq_len. Total across the
    # submesh: the page axis shards over "data" and kv heads over
    # "model" (engine/paging.py per-replica pools), so
    # check_fleet_fits' whole-estimate/group-size division is exact —
    # the pool is not replicated per data replica (advisor r3
    # underestimate, closed).
    kv_bytes = (num_slots * max_seq * len(model_cfg.attention_layers)
                * model_cfg.page_cells * dtype_b) // 2
    # Quantized KV pages (ISSUE 11): charge cells at the CONFIGURED
    # page dtype width, not bf16. resolve_spec applies the same
    # ROUNDTABLE_KV_QUANT kill-switch the engine applies, so the
    # plan matches what construction will actually allocate. With
    # an explicit num_pages the pool bytes follow the quantized
    # cell directly; the DEFAULT pool keeps the bf16 byte budget by
    # design (page_ratio x more pages in the same bytes — the
    # 2-4x-sessions payoff), so kv_bytes stays the halved budget.
    num_pages = engine_cfg.get("num_pages")
    if num_pages is not None:
        from .kv_quant import cell_bytes_per_token, resolve_spec
        kvq = engine_cfg.get("kv_quant")
        spec = (resolve_spec(kvq)[0] if kvq and kvq != "none"
                else None)
        page_size = int(engine_cfg.get("page_size", 128))
        kv_bytes = int(int(num_pages) * page_size
                       * cell_bytes_per_token(model_cfg, spec,
                                              dtype_b))
    state_bytes = 0
    if model_cfg.layer_kinds is not None:
        # Recurrent state beside the pools (engine/hybrid_state.py): a
        # row a slot plus scratch, and the snapshot store's budget (the
        # engine's default: four snapshots a slot).
        from .models.hybrid import state_bytes_per_sequence
        per = state_bytes_per_sequence(model_cfg)
        snap = engine_cfg.get("state_snapshot_bytes")
        state_bytes = ((num_slots + 1) * per
                       + (int(snap) if snap is not None
                          else 4 * num_slots * per))
    lora_bytes = 0
    lora_cfg = engine_cfg.get("lora")
    if lora_cfg:
        # Multi-LoRA adapter store (ISSUE 10): stacked A/B tensors are
        # allocated for every slot up front (shapes are config-static)
        # — charged by the same closed form the store itself derives
        # from (engine/lora.stack_bytes_for: shared defaults, the
        # `targets:` restriction, int8 at one byte per element), so
        # the plan cannot drift from the real allocation.
        from .lora import stack_bytes_for
        lora_bytes = stack_bytes_for(model_cfg, lora_cfg,
                                     dtype_bytes=dtype_b)
    # Activations + XLA workspace: prefill chunks are ≤2048 tokens, so
    # this is small next to 7B-class weights; floor it for tiny models.
    margin = max(256 << 20, w_bytes // 16)
    return w_bytes + kv_bytes + state_bytes + lora_bytes + margin


# HBM per chip by device_kind, for backends whose memory_stats is None
# or lacks bytes_limit (the v5e reports it: 16 909 336 064, PR 22's chip
# run — and it wins over this table). Public TPU specs.
_DEVICE_KIND_HBM = {
    "TPU v5 lite": 16 << 30,
    "TPU v5e": 16 << 30,
    "TPU v5": 95 << 30,         # v5p
    "TPU v5p": 95 << 30,
    "TPU v4": 32 << 30,
    "TPU v6 lite": 32 << 30,    # Trillium
    "TPU v3": 16 << 30,
    "TPU v2": 8 << 30,
}
# Fraction of raw capacity treated as plannable: the runtime reserves a
# slice and serving needs workspace for concurrently-dispatched prefill
# programs. Calibrated against a real failure: a trio estimated at
# 12.4 GiB resident OOM'd at concurrent prefill on a 16 GiB v5e, so
# plannable is set below that observed ceiling.
_HBM_UTILIZATION = 0.75


def device_memory_bytes() -> Optional[int]:
    """Plannable per-device HBM bytes: memory_stats' bytes_limit where
    the backend reports it, else a device_kind table — both scaled by
    _HBM_UTILIZATION. None (no check) when neither source knows."""
    import jax
    try:
        dev = jax.devices()[0]
    except Exception:
        return None
    try:
        stats = dev.memory_stats()
    except Exception:
        # A backend whose memory_stats RAISES (rather than returning
        # None) still gets the device_kind fallback below.
        stats = None
    raw = (stats or {}).get("bytes_limit")
    if not raw:
        raw = _DEVICE_KIND_HBM.get(getattr(dev, "device_kind", ""))
    return int(raw * _HBM_UTILIZATION) if raw else None


def partition_devices(weights: list[int], n_devices: int) -> list[list[int]]:
    """Split device indices 0..n-1 into one contiguous group per weight.

    Every group starts at 1 device; remaining devices are granted by
    repeated DOUBLING (keeps each submesh a power of two, so TP axis sizes
    divide heads/mlp cleanly), always to the group with the highest
    bytes-per-device. Groups are contiguous index ranges — on a real slice,
    neighboring indices are ICI neighbors, so a submesh's collectives stay
    on-torus. Leftover devices (when no group can double) stay idle.

    If there are more models than devices, groups share: model i gets
    device i % n_devices (time-multiplexed residency, still correct —
    XLA serializes programs per device).
    """
    m = len(weights)
    if m == 0:
        return []
    if n_devices < m:
        return [[i % n_devices] for i in range(m)]

    sizes = [1] * m
    remaining = n_devices - m
    while True:
        # candidate = most HBM-pressured group whose doubling fits
        best, best_load = None, -1.0
        for i in range(m):
            if sizes[i] <= remaining:
                load = weights[i] / sizes[i]
                if load > best_load:
                    best, best_load = i, load
        if best is None:
            break
        remaining -= sizes[best]
        sizes[best] *= 2

    groups: list[list[int]] = []
    start = 0
    for size in sizes:
        groups.append(list(range(start, start + size)))
        start += size
    return groups


def _engine_identity(cfg: dict[str, Any]) -> str:
    """Two configs with the same identity share one engine (and submesh)."""
    return f"{cfg.get('model', 'tiny-gemma')}|{cfg.get('checkpoint', '')}"


def check_fleet_fits(identities: dict[str, list[dict[str, Any]]],
                     groups: list[list[int]],
                     budget_bytes: int) -> None:
    """Validate every device's resident-bytes total against its HBM.

    Per-group per-device bytes = the group's engine estimate divided by
    its submesh size (TP shards weights and KV); groups sharing a device
    (models > devices) accumulate. An over-budget device triggers the
    degrade path: the largest offending group whose config does NOT set
    quant explicitly flips to int8 with a warning; if no flippable group
    remains and a device is still over, raise with the full breakdown —
    a clear plan-time error instead of an opaque XLA allocation failure
    minutes into engine builds (VERDICT r2 weak #3).
    """
    items = list(identities.items())

    def per_device_totals():
        from . import _cache_key
        totals: dict[int, float] = {}
        contrib = []  # (ident, cfgs, group, per_dev_bytes)
        for (ident, cfgs), group in zip(items, groups):
            # One identity can still build SEVERAL resident engines: the
            # engine cache keys on more than (model, checkpoint) — e.g.
            # two knights with different max_seq_len — so charge each
            # distinct engine config, not the identity once.
            distinct = {_cache_key(c): c for c in cfgs}
            per_dev = 0.0
            for c in distinct.values():
                try:
                    per_dev += (estimate_engine_hbm_bytes(c)
                                / max(len(group), 1))
                except ValueError:
                    pass  # unknown model: same tolerance as the weights
                    # loop — plan proceeds, XLA is the backstop
            contrib.append((ident, cfgs, group, per_dev))
            for dev in group:
                totals[dev] = totals.get(dev, 0.0) + per_dev
        return totals, contrib

    while True:
        totals, contrib = per_device_totals()
        over = {d: t for d, t in totals.items() if t > budget_bytes}
        if not over:
            return
        worst_dev = max(over, key=over.get)
        # Two degrade tiers: bf16 → int8, then (still over) int8 → int4.
        # Only AUTO-degraded int8 re-flips — an operator's explicit
        # quant/dtype choice is never rewritten.
        flippable = [(ident, cfgs, per_dev)
                     for ident, cfgs, group, per_dev in contrib
                     if worst_dev in group
                     # EVERY config in the group must be unpinned — the
                     # flip rewrites them all, and an explicit
                     # quant/float32 choice is the operator's to keep
                     and all((("quant" not in c)
                              or (c.get("_quant_auto_degraded")
                                  and c.get("quant") == "int8"))
                             and c.get("dtype", "bfloat16") != "float32"
                             for c in cfgs)]
        if not flippable:
            def gib(x): return f"{x / (1 << 30):.1f} GiB"
            lines = "; ".join(
                f"{ident.split('|')[0]}: {gib(per_dev)}/device over "
                f"{len(group)} device(s)"
                for ident, _c, group, per_dev in contrib)
            raise ValueError(
                f"Fleet does not fit: device {worst_dev} needs "
                f"{gib(over[worst_dev])} of {gib(budget_bytes)} HBM "
                f"({lines}). Fix: quant='int8'/'int4' on the big models, "
                "fewer models per chip, smaller max_seq_len/num_slots, "
                "or more devices.")
        ident, cfgs, per_dev = max(flippable, key=lambda x: x[2])
        next_quant = ("int4" if cfgs[0].get("quant") == "int8"
                      else "int8")
        warnings.warn(
            f"Fleet over HBM budget on device {worst_dev}: quantizing "
            f"{ident.split('|')[0]} to {next_quant} to fit; set "
            "quant explicitly to override", stacklevel=3)
        for c in cfgs:
            c["quant"] = next_quant
            # Surfaced in the engine's describe() as e.g. "int8
            # (auto-degraded)" — a non-interactive/driver run can easily
            # miss the warning stream, and the serving numerics silently
            # differ from what the operator configured (advisor r3).
            c["_quant_auto_degraded"] = True


def fleet_health() -> dict[str, Any]:
    """Health roll-up of every resident engine's circuit breaker (ISSUE 1
    engine→adapter-fallback rung): per-engine snapshots keyed exactly like
    the engine cache, plus open/total counts. A fleet where `open > 0`
    has at least one engine the adapters are routing around; `degraded`
    additionally counts engines with recent (not yet trip-level)
    consecutive failures. `draining` reports the admission gate and
    `hangs` the watchdog's recent hang detections (ISSUE 2 time ladder).
    `schedulers` (ISSUE 4) snapshots every live continuous-batching
    session scheduler: queue depth and per-session state, so an operator
    can see WHO is waiting behind a drain or a full batch. Cheap —
    host-side counters only, no device work — so status surfaces can
    poll it per round."""
    from . import breaker_snapshots, deadlines
    from ..utils import telemetry
    from .scheduler import schedulers
    snaps = breaker_snapshots()
    sched_snaps = [s.snapshot() for s in schedulers()]
    return {
        "engines": snaps,
        "total": len(snaps),
        "open": sum(1 for s in snaps if s["open"]),
        "degraded": sum(1 for s in snaps
                        if s["failures"] > 0 and not s["open"]),
        "draining": deadlines.DRAINING,
        "hangs": len(deadlines.hang_log()),
        "schedulers": sched_snaps,
        "queued_sessions": sum(s["queued"] for s in sched_snaps),
        # ISSUE 5: the unified store's view — hang/fault/breaker/sched
        # counters, flight-recorder state — so fleet_health is a window
        # onto the SAME registry bench records and status render.
        "telemetry": telemetry.registry_view(),
        # ISSUE 6: compile-observatory roll-up — is the fleet in steady
        # state, and has anything recompiled mid-serve since?
        "perf": _perf_rollup(),
        # ISSUE 12: the supervisor's restart history — totals, dead
        # engines and WHY, per-engine restart budgets. Cheap: reads the
        # process singleton's host-side state, never constructs it.
        "supervisor": _supervisor_rollup(),
        # ISSUE 17: the session router's fleet view when one is active
        # (multi-replica serving) — per-replica liveness + assignment
        # counts, migration/failover/roll history. None without one.
        "router": _router_rollup(),
    }


def _perf_rollup() -> dict[str, Any]:
    from .compile_watch import summary
    s = summary()
    return {"compile_mode": s["mode"], "compiles": s["compiles"],
            "steady_state": s["steady_state"],
            "steady_state_compiles": s["steady_state_compiles"],
            "strict": s["strict"]}


def _supervisor_rollup() -> dict[str, Any]:
    from .supervisor import supervisor_snapshot
    return supervisor_snapshot()


def _router_rollup() -> Optional[dict[str, Any]]:
    from ..router.core import active_router
    r = active_router()
    return r.describe() if r is not None else None


def drain(timeout_s: float = 30.0, flush_kv: bool = True) -> dict[str, Any]:
    """Graceful fleet drain (ISSUE 2): stop admitting turns, let every
    in-flight generation finish its rung, then flush per-knight KV state.

    Sequence:
    1. Flip the module-level admission gate (deadlines.begin_drain) —
       every later `generate_batch*` call on ANY resident engine raises
       DrainingError; calls already past the gate (in flight, or queued
       on a serve lock) complete normally.
    1b. Reject every QUEUED-but-unadmitted session scheduler request
       immediately with a clean DrainingError (ISSUE 4 satellite: a
       queued session must not wait out its whole budget just to learn
       the fleet is going away); the schedulers' ACTIVE sessions finish
       their rounds like any in-flight turn, releasing the serve locks
       step 2 waits on.
    2. For each resident engine, acquire its serve lock within
       `timeout_s` — acquisition IS the proof that in-flight work
       finished — and, holding it, flush every per-knight slot through
       the cache's normal release path (PagedKVCache.flush: the pool
       decrefs/frees their pages — including the cross-session prefix
       cache's index, which UNREFS its held pages rather than
       force-freeing (ISSUE 7), so a drained pool reads zero
       pages in use). An
       engine whose in-flight turn outlives the timeout is reported
       `in_flight_drained: False` and left unflushed. Host-RAM spill
       records (kv_offload) survive a drain — a resumed fleet restores
       idle sessions without re-prefill.

    Admission stays closed after drain() returns (the caller is shutting
    down, checkpointing, or re-seating); `resume()` re-opens it. Returns
    a report: per-engine flush counts and whether the drain was clean."""
    import time
    from . import _engines, _lock, deadlines
    from ..utils import telemetry
    from .scheduler import schedulers
    deadlines.begin_drain()
    # The drain is itself a postmortem trigger (ISSUE 5): the ring holds
    # whatever the fleet was doing when the operator pulled the cord.
    telemetry.recorder().record("drain_begin", timeout_s=timeout_s)
    dump_path = telemetry.flight_dump("drain")
    deadline = time.monotonic() + timeout_s
    # Queued scheduler sessions fail fast NOW — their submitters were
    # never admitted, so there is nothing to wait for; active sessions
    # drain through the serve-lock wait below like any in-flight turn.
    # The admission gate closes too (ISSUE 12): a drained scheduler
    # must not race new admissions against the flush below — resume()
    # reopens it (the module DRAINING flag alone left the gate shut).
    for s in schedulers():
        s.pause_admission("fleet.drain")
    rejected = sum(s.reject_queued() for s in schedulers())
    with _lock:
        engines = list(_engines.items())
    report: dict[str, Any] = {"draining": True, "clean": True,
                              "engines": [],
                              "queued_sessions_rejected": rejected,
                              "telemetry_dump": dump_path}
    for key, eng in engines:
        entry: dict[str, Any] = {
            "engine": getattr(getattr(eng, "cfg", None), "name", key)}
        lock = getattr(eng, "_serve_lock", None)
        acquired = True
        if lock is not None:
            acquired = lock.acquire(
                timeout=max(deadline - time.monotonic(), 0.0))
        entry["in_flight_drained"] = acquired
        if acquired:
            try:
                if flush_kv:
                    # Best-effort per engine: one cache's flush failure
                    # must not abandon the remaining engines mid-drain.
                    try:
                        entry["flushed_slots"] = eng.kv.flush()
                        hy = getattr(eng, "hybrid", None)
                        if hy is not None:
                            # The slots' recurrent states and the
                            # snapshots go with their pages.
                            hy.forget_all()
                            hy.drop_all_snapshots()
                        # Spilled sessions' kept-resident pages are the
                        # only thing left between a flushed paged pool
                        # and zero pages in use — evacuate them to host
                        # RAM (ISSUE 7): the drain claim stays true and
                        # the sessions still resume without re-prefill
                        # after fleet.resume().
                        tier = getattr(eng, "kv_offload", None)
                        if tier is not None:
                            # evacuate() returns a restorable manifest
                            # (ISSUE 12); the drain report keeps its
                            # historical pages-count key.
                            manifest = tier.evacuate()
                            entry["evacuated_pages"] = \
                                manifest["pages_moved"]
                    except Exception as e:  # noqa: BLE001
                        entry["flush_error"] = str(e)
                        report["clean"] = False
            finally:
                if lock is not None:
                    lock.release()
        else:
            report["clean"] = False
        report["engines"].append(entry)
    return report


def resume() -> None:
    """Re-open admission after a drain (fleet_health()['draining'] goes
    False; engines accept new turns again).

    Also re-opens every attached scheduler's admission gate (ISSUE 12
    satellite): drain() closes the per-scheduler gates, and flipping
    only the module-level DRAINING flag left a drained scheduler's
    queue paused forever — submits after resume() queued but never
    admitted. Reopening is idempotent and wakes the loops."""
    from . import deadlines
    from .scheduler import schedulers
    deadlines.end_drain()
    for s in schedulers():
        s.reopen_admission()


def plan_fleet(engine_configs: list[dict[str, Any]],
               n_devices: Optional[int] = None,
               budget_bytes: Optional[int] = None) -> None:
    """Assign disjoint device groups to heterogeneous engine configs.

    Mutates each config dict, setting "devices" (a list of device indices
    into jax.devices()) — and, when a group would overflow its devices'
    HBM, degrading unpinned configs to int8 or raising a clear error
    (check_fleet_fits). No-ops when: fewer than two distinct models, any
    config already pins "devices" or "mesh" (explicit layout wins), or
    device count can't be determined.
    """
    configs = [c for c in engine_configs if c is not None]
    if any(c.get("devices") or c.get("mesh") for c in configs):
        return
    # Multi-host: join the process group before the jax.devices() below
    # initializes a single-process backend (engine/distributed.py).
    from .distributed import maybe_init_distributed
    maybe_init_distributed()
    identities: dict[str, list[dict[str, Any]]] = {}
    for c in configs:
        identities.setdefault(_engine_identity(c), []).append(c)
    if len(identities) < 2:
        return

    if n_devices is None:
        import jax
        n_devices = len(jax.devices())

    weights = []
    for ident, cfgs in identities.items():
        try:
            weights.append(estimate_param_count(
                resolve_model_config(cfgs[0])))
        except ValueError:
            weights.append(1)
    groups = partition_devices(weights, n_devices)
    if budget_bytes is None:
        budget_bytes = device_memory_bytes()
    if budget_bytes:
        check_fleet_fits(identities, groups, budget_bytes)
    for (ident, cfgs), group in zip(identities.items(), groups):
        for c in cfgs:
            c["devices"] = list(group)
