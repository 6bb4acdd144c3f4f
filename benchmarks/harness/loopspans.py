"""The program's own spans over the traced slice, for the readers of
`program_span` metrics.

While the slice is traced the span tracer is armed, and every finished
span lies in its in-memory buffer with `t0`, its start on
`time.monotonic()` — the clock the slice's two ends were read on
(`run.py: traced_slice`). So a reader takes
`telemetry.spans_between(...)` and needs no snapshot of a lifetime
total. A program without that buffer (a commit before it existed), a
run without a slice (the CPU) and a buffer that overflowed all give
nothing to read: the readers return None and the line leaves the metric
out.

The scheduler's loop clock writes one `loop.<phase>` span per stretch of
a phase, end to end on the loop's thread, so clipped to the slice they
sum to the slice's length: `loop_seconds` is where the scheduler's
thread spent the slice.
"""

from __future__ import annotations

from typing import Any, Optional

# A span that straddles the slice's start began before it: a blocking
# read of a 64-step segment lasts most of a second.
LOOKBACK_S = 60.0
LOOP_PREFIX = "loop."


def slice_spans(ctx: dict[str, Any],
                lookback_s: float = 0.0) -> Optional[list[dict]]:
    """The buffered spans that started in the slice (or up to
    `lookback_s` before it); None where there is nothing to read."""
    sl = ctx.get("slice")
    if not sl:
        return None
    from theroundtaible_tpu.utils import telemetry

    between = getattr(telemetry, "spans_between", None)
    dropped = getattr(telemetry, "spans_dropped", None)
    if between is None or dropped is None or dropped():
        return None
    return between(sl["start"] - lookback_s, sl["end"])


def loop_seconds(ctx: dict[str, Any]) -> Optional[dict[str, float]]:
    """Seconds of the slice in each phase of the scheduler's loop: every
    `loop.<phase>` span clipped to the slice. Several clocked loops (a
    fleet of replicas) are averaged, so the phases still sum to one
    slice."""
    spans = slice_spans(ctx, LOOKBACK_S)
    if spans is None:
        return None
    lo, hi = ctx["slice"]["start"], ctx["slice"]["end"]
    seconds: dict[str, float] = {}
    clocks = set()
    for r in spans:
        if not r["rung"].startswith(LOOP_PREFIX):
            continue
        a, b = max(r["t0"], lo), min(r["t0"] + r["dur_s"], hi)
        if b > a:
            phase = r["rung"][len(LOOP_PREFIX):]
            seconds[phase] = seconds.get(phase, 0.0) + (b - a)
            clocks.add(r["trace_id"])
    if not seconds:
        return None
    return {k: v / len(clocks) for k, v in seconds.items()}


def slice_seconds(ctx: dict[str, Any]) -> float:
    return ctx["slice"]["end"] - ctx["slice"]["start"]
