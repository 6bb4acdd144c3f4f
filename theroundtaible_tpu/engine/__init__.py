"""The in-tree JAX/XLA inference engine (tpu-llm backend).

`get_engine(config)` is the single construction seam used by
adapters/tpu_llm.py: it joins the multi-host process group (distributed),
builds an InferenceEngine (engine), and caches engines by every config
key the build reads (ENGINE_CONFIG_KEYS) so knights with identical
configs share one resident model while differing ones never silently
collide (SURVEY.md §7.1; per-call settings like knight_sampling are
deliberately NOT in the key).
"""

from __future__ import annotations

import json
import os
import threading
from typing import Any

_engines: dict[str, Any] = {}
_breakers: dict[str, Any] = {}
_lock = threading.Lock()
# The one-shot cache decision (ISSUE 6 satellite): memoized for BOTH
# outcomes — the CPU no-op used to re-probe jax.default_backend() on
# every call — and recorded once into the telemetry registry and
# engine.describe() so an operator can see which it was after the fact.
_compile_cache_decision: dict[str, Any] | None = None
# The directory that holds the package: the fixed home of `.xla_cache`.
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compilation_cache():
    """Turn on JAX's persistent compilation cache (idempotent).

    Every engine process otherwise pays a full XLA compile per
    (batch, bucket) program — minutes of cold-start on a real chip
    (SURVEY.md §7.3 hard part 5). The directory is placed from outside:
    where JAX_COMPILATION_CACHE_DIR is set JAX already uses it and this
    function sets NO directory; where it is not, the cache lives at the
    fixed path `<checkout>/.xla_cache` (the path is part of the cache
    key, so a directory that moves never hits).

    CPU backends are a no-op: tiny-shape CPU compiles are seconds, and
    XLA:CPU AOT cache entries embed host machine features — reloading one
    compiled under different flags/machines warns "could lead to SIGILL".

    Returns the cache dir when enabled, None for the no-op — and either
    way decides exactly ONCE per process (get_compile_cache_decision()
    exposes the memoized outcome)."""
    global _compile_cache_decision
    if _compile_cache_decision is not None:
        return _compile_cache_decision.get("dir")
    import jax
    backend = jax.default_backend()
    if backend == "cpu":
        _compile_cache_decision = {
            "enabled": False, "backend": "cpu", "dir": None,
            "reason": "cpu no-op (AOT entries embed host features)"}
        _record_cache_decision()
        return None
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(_CHECKOUT, ".xla_cache")
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # Cache even fast compiles: serving has many small bucket programs and
    # the default 1s threshold would skip exactly the ones that add up.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _compile_cache_decision = {
        "enabled": True, "backend": backend, "dir": cache_dir}
    _record_cache_decision()
    return cache_dir


def _record_cache_decision() -> None:
    """One registry gauge + flight event per process for the decision —
    bench records and status --perf then carry which cold-start regime
    the numbers were measured under."""
    from ..utils import telemetry
    d = _compile_cache_decision or {}
    telemetry.set_gauge("roundtable_compile_cache_enabled",
                        1.0 if d.get("enabled") else 0.0)
    telemetry.recorder().record("compile_cache_decision", **d)


def get_compile_cache_decision() -> dict[str, Any] | None:
    """The memoized enable_compilation_cache outcome (None before the
    first call) — embedded in engine.describe()."""
    return _compile_cache_decision


# Every adapter-config key that shapes the engine built from it.
# InferenceEngine.from_config (and registry.resolve_model_config under
# it) sees a config through this tuple only, and _cache_key is made of
# the same one: a key the build can read is a key that separates
# engines. breaker_threshold and dispatch_retries are settings of the
# fault ladder around a built engine and are left out on purpose.
ENGINE_CONFIG_KEYS = (
    "model", "architecture", "checkpoint", "max_seq_len", "dtype", "mesh",
    "seq_parallel", "long_scheme", "long_threshold", "devices", "attn",
    "num_slots", "sampling", "seed", "kv_layout", "page_size",
    "num_pages", "quant", "dcn_axis", "prefix_cache",
    "prefix_cache_pages", "kv_offload", "ragged_attn", "ragged_tokens",
    "spec_decode", "spec_max_draft", "lora", "kv_quant",
    "state_snapshot_bytes")


def _cache_key(config: dict[str, Any]) -> str:
    return json.dumps({k: config.get(k) for k in ENGINE_CONFIG_KEYS},
                      sort_keys=True)


def get_engine(config: dict[str, Any]):
    """Build (or reuse) an engine for this adapter config."""
    # Join the multi-host process group BEFORE any backend/device call —
    # this seam runs ahead of plan_fleet's jax.devices() and every engine
    # constructor (engine/distributed.py; jax.distributed.initialize must
    # precede backend init).
    from .distributed import maybe_init_distributed
    maybe_init_distributed()
    key = _cache_key(config)
    with _lock:
        if key not in _engines:
            from .engine import InferenceEngine
            eng = InferenceEngine.from_config(config)
            # Supervision identity + rebuild recipe (ISSUE 12): the
            # EngineSupervisor rebuilds a dead engine from exactly this
            # config and keys its restart budget by this cache key.
            eng._engine_cache_key = key
            eng._engine_config = dict(config)
            _engines[key] = eng
        return _engines[key]


def replace_engine(old, new) -> bool:
    """Swap a rebuilt engine into the cache in place of the instance it
    supersedes (engine/supervisor.py restart cycle): every later
    get_engine with the same config serves the fresh engine. Returns
    whether a cache entry was replaced (False for engines constructed
    outside the cache — tests, ad-hoc instances)."""
    with _lock:
        for k, v in list(_engines.items()):
            if v is old:
                _engines[k] = new
                return True
    return False


def get_breaker(config: dict[str, Any]):
    """The circuit breaker for this engine config — keyed exactly like
    the engine cache, so every adapter sharing a resident engine shares
    its failure history (a sick engine is sick for all its knights).
    `breaker_threshold` in the config sets the consecutive-failure trip
    count (default 3) — FIRST caller wins, since breaker_threshold is
    deliberately not part of the engine cache key (it isn't
    serving-relevant); a later caller asking for a different threshold
    gets the shared breaker as-is, with a warning. Breakers exist even
    while the engine itself is unbuilt or broken: construction failures
    count too."""
    key = _cache_key(config)
    threshold = max(1, int(config.get("breaker_threshold", 3)))
    with _lock:
        breaker = _breakers.get(key)
        if breaker is None:
            from .faults import CircuitBreaker
            breaker = _breakers[key] = CircuitBreaker(
                threshold=threshold, name=config.get("model", "engine"))
        elif breaker.threshold != threshold and "breaker_threshold" \
                in config:
            import warnings
            warnings.warn(
                f"breaker_threshold {threshold} ignored: this engine's "
                f"shared breaker was created with threshold "
                f"{breaker.threshold} (first caller wins)")
        return breaker


def breaker_snapshots() -> list[dict[str, Any]]:
    """Health snapshot of every engine breaker (fleet.fleet_health)."""
    with _lock:
        return [b.snapshot() for b in _breakers.values()]


def reset_engines() -> None:
    """Drop all cached engines and their breakers (tests)."""
    with _lock:
        _engines.clear()
        _breakers.clear()


# Public multi-LoRA surface (ISSUE 10 satellite): `from
# theroundtaible_tpu.engine import LoraStore` without deep paths.
# PEP 562 lazy export — engine/__init__ must stay importable without
# pulling jax at module load (bench parents import it pre-backend).
_LORA_EXPORTS = ("LoraStore", "lora_enabled", "lora_dims",
                 "save_pair_tree")

# Public supervision surface (ISSUE 12): the supervisor singleton
# accessors, the classified dead-engine error, and the durable session
# journal — same lazy-export discipline (supervisor pulls core.errors
# only; the journal is pure host code). The singleton itself is reached
# as engine.supervisor.supervisor() — the bare name would shadow the
# submodule.
_SUPERVISION_EXPORTS = ("EngineSupervisor", "EngineDead",
                        "set_supervisor", "supervisor_snapshot")
_JOURNAL_EXPORTS = ("SessionJournal", "replay_turns",
                    "replay_turn_prompt")


def __getattr__(name: str):
    if name in _LORA_EXPORTS:
        from . import lora as _lora
        return getattr(_lora, name)
    if name in _SUPERVISION_EXPORTS:
        from . import supervisor as _sup
        return getattr(_sup, name)
    if name in _JOURNAL_EXPORTS:
        from . import session_journal as _sj
        return getattr(_sj, name)
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")
