"""Adapter factory + initialization.

Parity with reference src/utils/adapters.ts:15-106: `create_adapter` switches
over the static adapter ids plus dynamic prefix ids; `initialize_adapters`
probes availability per knight, substitutes the API adapter when a CLI is
missing (init-time fallback), and runs context-window detection for local
adapters. The map is keyed by **adapter id**, not knight name.

TPU-build additions: the `tpu-llm` / `tpu-llm-<model>` dynamic id family
(in-tree JAX engine) and the `fake` id (hermetic tests).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

from ..core.types import RoundtableConfig
from .base import BaseAdapter, DEFAULT_TIMEOUT_MS

# CLI id → API id used for init-time fallback (reference adapters.ts:89-100).
_CLI_TO_API = {
    "claude-cli": "claude-api",
    "gemini-cli": "gemini-api",
    "openai-cli": "openai-api",
}


def create_adapter(adapter_id: str, config: RoundtableConfig,
                   timeout_ms: int = DEFAULT_TIMEOUT_MS
                   ) -> Optional[BaseAdapter]:
    """Instantiate one adapter by id (reference adapters.ts:15-56)."""
    cfg: dict[str, Any] = config.adapter_config.get(adapter_id, {})

    if adapter_id == "claude-cli":
        from .cli_adapters import ClaudeCliAdapter
        return ClaudeCliAdapter(cfg.get("command", "claude"), timeout_ms)
    if adapter_id == "gemini-cli":
        from .cli_adapters import GeminiCliAdapter
        return GeminiCliAdapter(cfg.get("command", "gemini"),
                                cfg.get("model"), timeout_ms)
    if adapter_id == "openai-cli":
        from .cli_adapters import OpenAICliAdapter
        return OpenAICliAdapter(cfg.get("command", "codex"), timeout_ms)
    if adapter_id == "claude-api":
        from .api_adapters import ClaudeApiAdapter
        return ClaudeApiAdapter(cfg.get("model", "claude-sonnet-4-6"),
                                cfg.get("env_key", "ANTHROPIC_API_KEY"),
                                timeout_ms)
    if adapter_id == "gemini-api":
        from .api_adapters import GeminiApiAdapter
        return GeminiApiAdapter(cfg.get("model", "gemini-2.5-flash"),
                                cfg.get("env_key", "GEMINI_API_KEY"),
                                timeout_ms)
    if adapter_id == "openai-api":
        from .api_adapters import OpenAIApiAdapter
        return OpenAIApiAdapter(cfg.get("model", "gpt-5.2"),
                                cfg.get("env_key", "OPENAI_API_KEY"),
                                timeout_ms)
    if adapter_id.startswith("local-llm"):
        from .local_llm import LocalLlmAdapter
        if not cfg.get("endpoint") or not cfg.get("model"):
            return None
        return LocalLlmAdapter(
            endpoint=cfg["endpoint"], model=cfg["model"],
            name=cfg.get("name", adapter_id), source=cfg.get("source"),
            timeout_ms=timeout_ms)
    if adapter_id.startswith("tpu-llm"):
        from .tpu_llm import TpuLlmAdapter
        return TpuLlmAdapter.from_config(adapter_id, cfg, timeout_ms)
    if adapter_id == "fake":
        from .fake import FakeAdapter
        return FakeAdapter(name=cfg.get("name", "Fake"))
    return None


def initialize_adapters(
    config: RoundtableConfig,
    on_event: Optional[Callable[[str, str], None]] = None,
) -> dict[str, BaseAdapter]:
    """Probe + seat every knight's adapter (reference adapters.ts:62-106).

    on_event(kind, message): "seated" | "fallback" | "unavailable" notices
    for the command layer to display.
    """
    timeout_ms = config.rules.timeout_per_turn_seconds * 1000
    adapters: dict[str, BaseAdapter] = {}

    _plan_tpu_fleet(config, on_event)

    for knight in config.knights:
        adapter_id = knight.adapter
        if adapter_id in adapters:
            continue
        adapter = create_adapter(adapter_id, config, timeout_ms)
        if adapter is not None and adapter.is_available():
            _post_init(adapter)
            adapters[adapter_id] = adapter
            if on_event:
                on_event("seated", f"{knight.name} ({adapter_id}) is at the table")
            continue

        # Init-time CLI→API fallback (reference adapters.ts:89-100).
        api_id = _CLI_TO_API.get(adapter_id)
        if api_id:
            api_adapter = create_adapter(api_id, config, timeout_ms)
            if api_adapter is not None and api_adapter.is_available():
                adapters[adapter_id] = api_adapter
                if on_event:
                    on_event("fallback",
                             f"{knight.name}: {adapter_id} unavailable, "
                             f"seated via {api_id}")
                continue
        if on_event:
            # The reason rides along where the adapter knows it (a
            # tpu-llm seat whose engine failed to build: bad model
            # name, out of device memory).
            why = getattr(adapter, "unavailable_reason", lambda: None)()
            on_event("unavailable",
                     f"{knight.name} ({adapter_id}) is unavailable"
                     + (f": {why}" if why else ""))
    return adapters


def _plan_tpu_fleet(config: RoundtableConfig,
                    on_event: Optional[Callable[[str, str], None]]) -> None:
    """Heterogeneous serving: when several knights use DIFFERENT tpu-llm
    models, partition the chips into per-model submeshes before any engine
    is built (engine/fleet.py; SURVEY.md §2.3). Homogeneous setups and
    configs with explicit mesh/devices are untouched."""
    tpu_cfgs = []
    for knight in config.knights:
        if knight.adapter.startswith("tpu-llm"):
            # Unconfigured tpu-llm ids get a dict INSERTED into the config
            # map so the planner's device assignment reaches the adapter —
            # leaving one engine on the full default mesh would overlap the
            # submeshes planned for the others and double-book HBM.
            cfg = config.adapter_config.setdefault(knight.adapter, {})
            if isinstance(cfg, dict):
                tpu_cfgs.append(cfg)
    if len(tpu_cfgs) < 2:
        return
    try:
        from ..engine.fleet import plan_fleet
        plan_fleet(tpu_cfgs)
    except Exception as e:  # noqa: BLE001 — engines still run (sharing the
        # full default mesh), but the operator must hear planning failed:
        # the symptom otherwise is an unexplained HBM OOM at weight load.
        if on_event:
            on_event("unavailable",
                     f"fleet planning failed ({e}); engines will share "
                     f"the full device mesh")


def _post_init(adapter: BaseAdapter) -> None:
    """Context-window detection for adapters that support it
    (reference adapters.ts:78-83)."""
    detect = getattr(adapter, "detect_context_window", None)
    if callable(detect):
        try:
            detect()
        except Exception:
            pass
