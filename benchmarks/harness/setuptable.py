"""The program's own account of its set-up, for the readers of the
`setup.*` metrics.

`engine/compile_watch.py` keeps one always-on table from the moment its
hooks go in until the scheduler declares its warm-up complete:
thread-seconds by stage of bringing a program up (`trace`, `lower`,
`retrieve`, `compile`), wall seconds by phase of the build (`init`,
`quantize`, `pools`, `warm_programs`, `warm_traffic`), the programs
lowered and, of them, those compiled fresh. `run.py` declares the
warm-up complete before the window opens, so a reader — which runs after
the window, in the process that served it — finds the table closed. A
program without that table (a commit before it existed) and a table that
never closed give nothing to read: the readers return None and the line
leaves the metric out.
"""

from __future__ import annotations

from typing import Any, Optional

# What `unstaged_s` takes off the wall time whole: the build's own
# phases (the stage seconds heard inside them go with them).
BUILD_PHASES = ("init", "quantize", "pools")


def closed_report() -> Optional[dict[str, Any]]:
    """The set-up table of this process, once closed."""
    from theroundtaible_tpu.engine import compile_watch

    report = getattr(compile_watch, "setup_report", None)
    table = report() if report is not None else None
    return table if table and table.get("closed") else None


def count(key: str) -> Optional[float]:
    """One of the table's counters (`programs`, `cache_misses`)."""
    table = closed_report()
    return None if table is None else float(table[key])


def stage_seconds(*stages: str) -> Optional[float]:
    table = closed_report()
    if table is None:
        return None
    return float(sum(table["stages"][s] for s in stages))


def unstaged_seconds() -> Optional[float]:
    """Wall seconds of the set-up outside any stage and outside the
    build's own first three phases: what `warm_programs` and
    `warm_traffic` hold besides bringing programs up — the warm
    programs' own runs, the warm-up sessions' serving — and whatever no
    mark names yet."""
    table = closed_report()
    if table is None:
        return None
    staged_outside = sum(table["stages"].values()) - sum(
        table["staged"][p] for p in BUILD_PHASES)
    return float(table["wall_s"] - staged_outside
                 - sum(table["phases"][p] for p in BUILD_PHASES))
