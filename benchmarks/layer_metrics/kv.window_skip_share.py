"""KV manager, for a decoder with window layers beside full ones: of the
page visits a model of the same layers WITHOUT windows would have asked
of the attention kernels in the slice, the share the windows saved —
1 - (page_visits_full + page_visits_window) over page_visits_full x
(all attention layers / full layers), from the slice's `segment` spans
(both attributes count every layer of their class: the engine's
`_note_page_visits` for a ragged dispatch, `plain_window_reads` for a
decode segment). 0: the windows skip nothing (contexts under the
window); with three of five layers at a 512 window over 128-wide pages
and contexts of 3000, 45. Without a slice (a rehearsal on the CPU) the
same over the whole run, from the registry's `roundtable_window_*`
counters. A program whose spans lack the attributes (a commit before
them) gives nothing to read."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import loopspans, window_cost  # noqa: E402


def read(ctx):
    config = ctx["config"]
    if not window_cost.is_laguna(config):
        return None
    spans = loopspans.slice_spans(ctx)
    if spans is not None:
        segs = [r.get("attrs", {}) for r in spans
                if r["rung"] == "segment"]
        segs = [a for a in segs if "page_visits_full" in a]
        full = sum(a["page_visits_full"] for a in segs)
        made = full + sum(a["page_visits_window"] for a in segs)
    elif ctx.get("slice") is None:
        from theroundtaible_tpu.utils import telemetry
        total = getattr(telemetry.REGISTRY, "counter_total", None)
        if total is None:
            return None
        full = total("roundtable_window_page_visits_full_total")
        made = full + total("roundtable_window_page_visits_window_total")
    else:
        return None
    without = window_cost.unwindowed_visits(full, config)
    if not without:
        return None
    return 100.0 * (1.0 - made / without)
