"""Scheduler: milliseconds of host time an admission of the traced
slice spends issuing page copies — the `page_copy` spans' durations
(`paging._run_page_copy`: a boundary page of an alias, a share or a
copy-on-write; microseconds on the device behind a whole dispatch on
the host) summed over the slice, a join (`admit` span) of the slice."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import feedspans  # noqa: E402


def read(ctx):
    spans = feedspans.round_start_spans(ctx)
    if spans is None:
        return None
    joins = sum(1 for r in spans if r["rung"] == "admit")
    if not joins:
        return None
    return 1e3 * sum(r["dur_s"] for r in spans
                     if r["rung"] == "page_copy") / joins
