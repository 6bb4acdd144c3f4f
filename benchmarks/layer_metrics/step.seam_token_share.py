"""Step programs, for a decoder whose upper layers keep nothing (no
pages, no state): of the tokens the slice's JOIN programs — prologue
chunks and ragged steps — ran through the layers below the seam, the
share that also went through the layers above it. A seamless program
runs every token through every layer (100); with the seam the layers
above run each sequence's last token only, so a ragged step of one
300-token join beside ten decode rows reads 11 / 310 = 3.5, a cold
1024-token chunk 0.1. From the slice's `segment` spans: sum of
`upper_rows` over sum of `lower_tokens` (HybridStateStore.note_join
counts both at the dispatch; a decode program, where every token is a
row's last, is left out). Without a slice (a rehearsal on the CPU) the
same over the whole run, from the registry's `roundtable_seam_*`
counters. A program whose spans lack the attributes (a commit before
them, a model without a seam) gives nothing to read."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import loopspans  # noqa: E402


def read(ctx):
    spans = loopspans.slice_spans(ctx)
    if spans is not None:
        segs = [r.get("attrs", {}) for r in spans
                if r["rung"] == "segment"]
        lower = sum(a.get("lower_tokens", 0) for a in segs)
        upper = sum(a.get("upper_rows", 0) for a in segs)
    elif ctx.get("slice") is None:
        from theroundtaible_tpu.utils import telemetry
        total = getattr(telemetry.REGISTRY, "counter_total", None)
        if total is None:
            return None
        lower = total("roundtable_seam_lower_tokens_total")
        upper = total("roundtable_seam_upper_rows_total")
    else:
        return None
    if not lower:
        return None
    return 100.0 * upper / lower
