"""Scheduler: the median duration of the traced slice's `share` spans
— `_apply_share_plans` for one request whose leader has written the
common span: aliasing it into the followers and allocating their tails,
before the followers' segment is packed, with the device waiting."""
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import feedspans  # noqa: E402


def read(ctx):
    spans = feedspans.round_start_spans(ctx)
    if spans is None:
        return None
    vals = [1e3 * r["dur_s"] for r in spans if r["rung"] == "share"]
    return statistics.median(vals) if vals else None
