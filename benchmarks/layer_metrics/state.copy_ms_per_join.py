"""State cache: milliseconds of device work an admission of the traced
slice costs in whole-state copies — what the restores wrote into slot
rows (`state_copy_bytes` of the `admit` spans) and what the programs'
captures wrote into the snapshot store (`state_capture_bytes` of the
`segment` spans), each byte read once and written once at the chip's
peak bandwidth, a join (`admit` span) of the slice. A floor from the
bytes the program says it moved: at 206 MB a state a copy is a quarter
of a millisecond, times what an admission restores and leaves behind.
A program whose spans carry no such attribute gives nothing to read."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import loopspans  # noqa: E402


def read(ctx):
    spans = loopspans.slice_spans(ctx)
    peak = (ctx.get("peaks") or {}).get("hbm_bytes_per_s")
    if spans is None or not peak:
        return None
    admits = [r.get("attrs", {}) for r in spans if r["rung"] == "admit"]
    admits = [a for a in admits if "state_copy_bytes" in a]
    if not admits:
        return None
    moved = sum(a["state_copy_bytes"] for a in admits) + sum(
        r.get("attrs", {}).get("state_capture_bytes", 0) for r in spans
        if r["rung"] == "segment")
    return 1e3 * 2.0 * moved / float(peak) / len(admits)
