"""State cache: the most the snapshot store held at the end of any
segment of the traced slice (`snapshot_bytes` of the `segment` spans),
over the configuration's `state_snapshot_bytes`. Near 100 the store is
full and every new snapshot evicts one."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import loopspans  # noqa: E402


def read(ctx):
    budget = ctx["config"]["engine"].get("state_snapshot_bytes")
    spans = loopspans.slice_spans(ctx)
    if spans is None or not budget:
        return None
    held = [r["attrs"]["snapshot_bytes"] for r in spans
            if r["rung"] == "segment"
            and "snapshot_bytes" in r.get("attrs", {})]
    return 100.0 * max(held) / float(budget) if held else None
