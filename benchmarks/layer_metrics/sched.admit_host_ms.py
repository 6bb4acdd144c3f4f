"""Scheduler: the median, over the requests admitted in the traced
slice, of an admission's host work — its `admit` span's duration less
`sync_s`, the time it stood blocked in host_sync (prologue prefill,
first-token read): reuse plan, prefix attach, page allocation, issuing
the prologue's programs, the eager first-token sample."""
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import loopspans  # noqa: E402


def read(ctx):
    spans = loopspans.slice_spans(ctx)
    if spans is None:
        return None
    vals = [1e3 * max(r["dur_s"] - r.get("attrs", {}).get("sync_s", 0.0),
                      0.0)
            for r in spans if r["rung"] == "admit"]
    return statistics.median(vals) if vals else None
