"""KV manager, for a decoder with window layers beside full ones: of
what the slice's segments' rows HELD, in pages x attention layers, the
share that lay wholly behind a window layer's window — `pages_behind_window`
over `pages_held`, summed over the slice's `segment` spans (both from
`engine.window_page_holdings`: a row's frontier at the segment's last
step; a page is behind when its every position is more than the window
back, which is where the decode walk starts). Window layers keep whole
pages under the one page table, so this is what an allocator by layer
class would free: with three layers of four behind a 1024 window over
128-wide pages at contexts of 3400, 0.75 x (1 - 9 / 27) = 50. 0: no
context has passed a window. Without a slice (a rehearsal on the CPU)
the same over the whole run, from the registry's
`roundtable_window_pages_*` counters. A program whose spans lack the
attributes (a commit before them) gives nothing to read."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import loopspans  # noqa: E402


def read(ctx):
    if "sliding_window" not in ctx["config"]:
        return None
    spans = loopspans.slice_spans(ctx)
    if spans is not None:
        segs = [r.get("attrs", {}) for r in spans
                if r["rung"] == "segment"]
        held = sum(a.get("pages_held", 0) for a in segs)
        behind = sum(a.get("pages_behind_window", 0) for a in segs)
    elif ctx.get("slice") is None:
        from theroundtaible_tpu.utils import telemetry
        total = getattr(telemetry.REGISTRY, "counter_total", None)
        if total is None:
            return None
        held = total("roundtable_window_pages_held_total")
        behind = total("roundtable_window_pages_behind_total")
    else:
        return None
    if not held:
        return None
    return 100.0 * behind / held
