"""Scheduler: the median, over the traced slice's admissions that ran
the prologue (their `admit` span's attribute `deferred` is false: the
batch was empty, or the join was too small to defer), of the host work
— the span's duration less `sync_s`, the time it stood blocked in
host_sync. A round's first admission is one of these, and every row of
the round waits behind it; `sched.admit_host_ms` is the median over all
admissions, and the median admission is a deferred join."""
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
    vals = [1e3 * max(r["dur_s"] - r["attrs"].get("sync_s", 0.0), 0.0)
            for r in spans
            if r["rung"] == "admit"
            and r.get("attrs", {}).get("deferred") is False]
    return statistics.median(vals) if vals else None
