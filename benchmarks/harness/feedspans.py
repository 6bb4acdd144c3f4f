"""What the scheduler's loop clock says of the device's feed, and the
spans of what a round's start does on the host, over the traced slice.

The loop clock (`telemetry.LoopClock`) knows whether its loop has a step
program outstanding on the device: every `loop.<phase>` record of the
armed buffer is wholly fed or wholly unfed and says which (`fed`: 0 |
1). Unfed seconds are seconds the device can only have spent idle for
want of work from that loop — the attribution of idle time by overlap,
made where the work happens and on the slice's own clock. A program
from before the feed bit writes no `fed`, and a reader then has nothing
to read, like every reader of `loopspans` on a run without a slice.
"""

from __future__ import annotations

from typing import Any, Optional

from . import loopspans

# The rungs under `admit` and `segment` that the feed bit came with.
ROUND_START_RUNGS = ("plan", "page_copy", "share", "pack")


def starved_seconds(ctx: dict[str, Any]) -> Optional[dict[str, float]]:
    """Unfed seconds of the slice in each phase of the scheduler's loop:
    every `loop.<phase>` span with `fed` 0, clipped to the slice.
    Several clocked loops are averaged, as `loopspans.loop_seconds`
    averages them. None where no record carries the bit."""
    spans = loopspans.slice_spans(ctx, loopspans.LOOKBACK_S)
    if spans is None:
        return None
    lo, hi = ctx["slice"]["start"], ctx["slice"]["end"]
    seconds: dict[str, float] = {}
    clocks = set()
    for r in spans:
        if not r["rung"].startswith(loopspans.LOOP_PREFIX):
            continue
        fed = r.get("attrs", {}).get("fed")
        a, b = max(r["t0"], lo), min(r["t0"] + r["dur_s"], hi)
        if fed is None or b <= a:
            continue
        clocks.add(r["trace_id"])
        if not fed:
            phase = r["rung"][len(loopspans.LOOP_PREFIX):]
            seconds[phase] = seconds.get(phase, 0.0) + (b - a)
    if not clocks:
        return None
    return {k: v / len(clocks) for k, v in seconds.items()}


def starved_share(ctx: dict[str, Any]) -> Optional[float]:
    """Percent of the slice the loop spent unfed outside `wait` — with
    nothing to run the host is not what the device waits for."""
    seconds = starved_seconds(ctx)
    if seconds is None:
        return None
    return 100.0 * sum(s for phase, s in seconds.items()
                       if phase != "wait") / loopspans.slice_seconds(ctx)


def round_start_spans(ctx: dict[str, Any]) -> Optional[list[dict]]:
    """The slice's buffered spans, if the program knows the round-start
    rungs (one of them, or a `loop.*` record with the feed bit, is in
    the buffer); None for a program from before them, whose silence
    would otherwise read as "no page was copied"."""
    spans = loopspans.slice_spans(ctx)
    if spans is None:
        return None
    if not any(r["rung"] in ROUND_START_RUNGS
               or "fed" in r.get("attrs", {}) for r in spans):
        return None
    return spans
