"""`roundtable warmup` — pre-compile the TPU serving programs.

No reference counterpart (Ollama keeps a resident server; our engine
lives in-process). First-ever serving of a config pays XLA compilation;
with the persistent compilation cache (engine.enable_compilation_cache)
that cost is paid ONCE per config — this command lets the operator pay
it up front instead of inside the first `discuss` round. Subsequent
process starts deserialize from the cache in seconds.
"""

from __future__ import annotations

import os
import time

from ..core.config import load_config
from ..utils.ui import style


def warmup_command(project_root: str | None = None) -> int:
    project_root = project_root or os.getcwd()
    config = load_config(project_root)

    # KNIGHT order, not sorted: the fleet planner assigns device groups
    # by list order, and discuss plans through the factory in knight
    # order — warming a different assignment would compile programs the
    # first discuss never hits.
    tpu_ids = list(dict.fromkeys(
        k.adapter for k in config.knights
        if k.adapter.startswith("tpu-llm")))
    if not tpu_ids:
        print(style.dim("\n  No tpu-llm knights in this config — "
                        "nothing to warm.\n"))
        return 0

    from ..adapters.factory import _plan_tpu_fleet
    from ..engine import get_engine

    # The exact planning pass discuss runs (mutates config.adapter_config
    # in place, so get_engine sees the same device assignments).
    _plan_tpu_fleet(config, None)
    configs = [config.adapter_config.get(a, {}) for a in tpu_ids]

    # Batch sizes the orchestrator will actually dispatch: 1 (serial
    # turns) and the number of knights sharing each adapter (batched
    # rounds).
    knights_per_adapter = {
        a: sum(1 for k in config.knights if k.adapter == a)
        for a in tpu_ids}

    for adapter_id, engine_cfg in zip(tpu_ids, configs):
        n = knights_per_adapter[adapter_id]
        sizes = tuple(sorted({1, n}))
        print(style.dim(f"  Warming {adapter_id} "
                        f"(batch sizes {list(sizes)})..."))
        t0 = time.monotonic()
        engine = get_engine(engine_cfg)
        secs = engine.warmup(batch_sizes=sizes)
        d = engine.describe()
        print(f"  {style.green('✓')} {d['model']} on mesh {d['mesh']}: "
              f"built in {time.monotonic() - t0 - secs:.1f}s, "
              f"warmed in {secs:.1f}s")
    # Where the seconds went (ISSUE 54): stages, phases, what compiled
    # fresh — the block `status --perf` prints.
    from ..engine import compile_watch
    from .status import print_setup_split
    print(style.bold("\n  Set-up:"))
    print_setup_split(compile_watch.summary()["setup"])
    print(style.dim("\n  Programs are in the persistent compilation "
                    "cache — the next discuss starts hot.\n"))
    return 0
