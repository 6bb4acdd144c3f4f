"""Device: the trace's idle share less what the scheduler knows it
caused — `sched.starved_share` (unfed outside `wait`) and the slice's
`wait` share (nothing to run). What is left is idle while the loop had
a program outstanding: launch latency and the gaps inside and between
queued programs. It bounds from below what host work at a round's start
can recover, and cannot be read without a device trace."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import feedspans, loopspans  # noqa: E402


def read(ctx):
    idle = (ctx["trace"] or {}).get("idle_share")
    starved = feedspans.starved_share(ctx)
    seconds = loopspans.loop_seconds(ctx)
    if idle is None or starved is None or seconds is None:
        return None
    wait = 100.0 * seconds.get("wait", 0.0) / loopspans.slice_seconds(ctx)
    return 100.0 * idle - starved - wait
