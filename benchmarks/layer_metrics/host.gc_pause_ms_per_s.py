"""Scheduler: milliseconds of `gc` spans — collections of 1 ms or more,
each on the thread it stopped — per second of the traced slice. A
program without the collector's hook writes no such span, and its
silence is not "the collector never ran": None."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import loopspans  # noqa: E402


def read(ctx):
    from theroundtaible_tpu.engine import compile_watch

    spans = loopspans.slice_spans(ctx)
    if spans is None or not hasattr(compile_watch, "gc_report"):
        return None
    paused = sum(r["dur_s"] for r in spans if r["rung"] == "gc")
    return 1e3 * paused / loopspans.slice_seconds(ctx)
