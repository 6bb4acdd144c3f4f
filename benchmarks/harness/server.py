"""The system under test, built and served the way `roundtable gateway`
does it: a scratch project's `.roundtable/config.json` → `load_config` →
`gateway_cmd._build_scheduler` → `Gateway.start_in_thread` on a loopback
port. The only thing the benchmark adds to the program is a registry
entry: the `ModelConfig` its configuration file describes, under the
configuration's own name.
"""

from __future__ import annotations

import json
import os
from typing import Any

from . import traffic

# Largest seed the engine is handed (it also uses seed + 1): the
# driver's seeds pass 2**31, a PRNG key's word does not.
_SEED_MODULUS = 2_147_483_629


def register_model(config: dict[str, Any]):
    """The configuration file's sizes (the published config.json's own
    keys) as a registry entry named after the configuration."""
    from theroundtaible_tpu.engine.models.common import ModelConfig
    from theroundtaible_tpu.engine.models.registry import register

    heads = int(config["num_attention_heads"])
    return register(ModelConfig(
        name=config["name"],
        vocab_size=int(config["vocab_size"]),
        num_layers=int(config["num_hidden_layers"]),
        embed_dim=int(config["hidden_size"]),
        num_heads=heads,
        num_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config.get("head_dim")
                     or int(config["hidden_size"]) // heads),
        mlp_dim=int(config["intermediate_size"]),
        max_seq_len=int(config["engine"]["max_seq_len"]),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        sliding_window=config.get("sliding_window"),
        attn_bias=bool(config.get("attention_bias", False)),
        tie_embeddings=bool(config["tie_word_embeddings"])))


def write_project(root: str, config: dict[str, Any], seed: int) -> None:
    """A scratch project whose knights share one tpu-llm seat."""
    engine_cfg = dict(config["engine"], model=config["name"],
                      seed=seed % _SEED_MODULUS)
    knights = traffic.KNIGHT_NAMES[:int(config.get("knights", 3))]
    project = {
        "version": "1.0", "project": "benchmark", "language": "en",
        "knights": [{"name": name, "adapter": "tpu-llm",
                     "capabilities": ["logic"], "priority": i}
                    for i, name in enumerate(knights, 1)],
        "rules": {"max_rounds": 5, "consensus_threshold": 9,
                  "timeout_per_turn_seconds": 1500,
                  "escalate_to_user_after": 3, "auto_execute": False,
                  "ignore": [".git"], "parallel_rounds": True},
        "chronicle": ".roundtable/chronicle.md",
        "adapter_config": {"tpu-llm": engine_cfg},
    }
    os.makedirs(os.path.join(root, ".roundtable"), exist_ok=True)
    with open(os.path.join(root, ".roundtable", "config.json"), "w",
              encoding="utf-8") as f:
        json.dump(project, f, indent=2)


def build(project_dir: str, config: dict[str, Any], seed: int):
    """→ (scheduler, gateway), the gateway listening on a free loopback
    port. The caller stops the gateway and closes the scheduler."""
    from theroundtaible_tpu.commands.gateway_cmd import _build_scheduler
    from theroundtaible_tpu.core.config import load_config
    from theroundtaible_tpu.gateway import Gateway

    register_model(config)
    write_project(project_dir, config, seed)
    sched = _build_scheduler(load_config(project_dir), None)
    gw = Gateway(sched, host="127.0.0.1", port=0)
    gw.start_in_thread()
    return sched, gw


def counters(sched, gw) -> dict[str, Any]:
    """Every count a per-layer reader may want, at one instant. The
    program's counters are lifetime totals: readers take differences."""
    from theroundtaible_tpu.engine import compile_watch
    from theroundtaible_tpu.engine.pallas import attention as pattn
    from theroundtaible_tpu.utils import telemetry

    engine = sched.engine
    info = engine.describe()
    sd = info.pop("scheduler", None) or sched.describe()
    reg = telemetry.REGISTRY
    return {
        "scheduler": {k: sd[k] for k in (
            "admitted", "refused", "completed", "failed", "preemptions",
            "segments", "ragged_segments", "ragged_joins",
            "spec_segments", "segment_prefill_tokens",
            "segment_decode_tokens", "queued_peak", "max_occupancy",
            "spills", "deadline_expired")},
        "gateway": {k: gw.describe()[k] for k in (
            "admitted", "shed", "expired", "dropped_events")},
        "prefix_cache": info.get("prefix_cache") or {},
        "spec_decode": {k: info["spec_decode"].get(k) for k in (
            "enabled", "drafter", "verify_dispatches", "drafted_tokens",
            "accepted_tokens")} if "spec_decode" in info else {},
        "pool": {"pages": engine.kv.usable_pages(),
                 "in_use": engine.kv.pages_in_use()},
        "prefill_tokens": reg.counter_total(
            "roundtable_prefill_tokens_total"),
        "reused_tokens": reg.counter_total(
            "roundtable_reused_tokens_total"),
        "compiles": compile_watch.compiles_seen(),
        "steady_state_compiles": compile_watch.steady_state_compiles(),
        "ragged_kernel_dispatches": pattn.ragged_kernel_dispatches(),
        "ragged_fallback_dispatches": pattn.ragged_fallback_dispatches(),
        "degradations": sum(
            v for k, v in reg.snapshot()["counters"].items()
            if k.startswith("roundtable_degradations_total")),
    }


def degraded_paths(sched, on_chip: bool) -> list[str]:
    """What chip_smoke.py asserts: every kernel of the served path ran
    as a kernel. → the names of what did not hold (empty = sound)."""
    from theroundtaible_tpu.engine import faults
    from theroundtaible_tpu.engine.pallas import attention as pattn
    from theroundtaible_tpu.utils import telemetry

    engine = sched.engine
    info = engine.describe()
    sd = sched.describe()
    snap = telemetry.REGISTRY.snapshot()["counters"]
    checks = [
        ("paged_decode", info["paged_decode"] != "pool-direct"),
        ("ragged_path", info["ragged"]["path"] != "pallas_ragged"),
        ("ragged_fallback_reason",
         info["ragged"]["fallback_reason"] is not None),
        ("paged_degraded_reason",
         engine.paged_degraded_reason is not None),
        ("ragged_fallback_dispatches",
         pattn.ragged_fallback_dispatches() != 0),
        ("degradations", any(
            v for k, v in snap.items()
            if k.startswith("roundtable_degradations_total"))),
        ("faults_armed", bool(faults.ARMED)),
        ("scheduler.failed", sd["failed"] != 0),
        ("scheduler.refused", sd["refused"] != 0),
        ("scheduler.preemptions", sd["preemptions"] != 0),
    ]
    if on_chip:
        # On the CPU `attn: auto` resolves to the dense path by design.
        checks.append(("attn_impl", engine.cfg.attn_impl != "flash"))
    return [name for name, bad in checks if bad]
