"""Scheduler, the join packer: of the flat-buffer tokens the slice's
join dispatches computed, the share that were real — `real_tokens` over
`shape`, summed over the slice's `segment` spans of kind `ragged`
(`scheduler._note_ragged_fill` writes both, and the registry's
`roundtable_ragged_real_tokens_total` / `_buffer_tokens_total` by
shape). A dispatch computes its whole static buffer, pads included
(`serving_loop.ragged_shape_grid`), so 100 less this is the part of the
join programs' device time that served no token; a round of fifteen
joins served in two dispatches and not three reads higher. Without a
slice (a rehearsal on the CPU) the same over the whole run, from the
registry. A program whose spans lack the attributes (a commit before
them) gives nothing to read."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import loopspans  # noqa: E402


def read(ctx):
    spans = loopspans.slice_spans(ctx)
    if spans is not None:
        segs = [a for a in (r.get("attrs", {}) for r in spans
                            if r["rung"] == "segment")
                if a.get("kind") == "ragged" and "real_tokens" in a]
        buffer = sum(a["shape"] for a in segs)
        real = sum(a["real_tokens"] for a in segs)
    elif ctx.get("slice") is None:
        from theroundtaible_tpu.utils import telemetry
        total = getattr(telemetry.REGISTRY, "counter_total", None)
        if total is None:
            return None
        buffer = total("roundtable_ragged_buffer_tokens_total")
        real = total("roundtable_ragged_real_tokens_total")
    else:
        return None
    if not buffer:
        return None
    return 100.0 * real / buffer
