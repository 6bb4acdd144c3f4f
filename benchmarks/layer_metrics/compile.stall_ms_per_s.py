"""Compile watch: milliseconds of `compile` spans — fresh compiles and
fetches from the persistent cache alike, each stalls the thread it runs
on — per second of the traced slice. `compile.in_window` counts them
over the window; this weighs them."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import loopspans  # noqa: E402


def read(ctx):
    spans = loopspans.slice_spans(ctx)
    if spans is None:
        return None
    stalled = sum(r["dur_s"] for r in spans if r["rung"] == "compile")
    return 1e3 * stalled / loopspans.slice_seconds(ctx)
