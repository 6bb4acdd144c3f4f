"""Expert layers: of the routed experts held here, the share a step
read — `experts_hit` of the slice's `segment` spans (held experts that
some row chose, summed over steps and expert layers) over held x
`expert_layer_steps`. Low: most experts' weights stay unread in a step;
100: a step reads them all. Without a slice (a rehearsal on the CPU)
the same over the whole run, from the registry's `roundtable_moe_*`
counters."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import loopspans  # noqa: E402


def read(ctx):
    held = ctx["config"].get("n_routed_experts")
    if not held:
        return None
    spans = loopspans.slice_spans(ctx)
    if spans is not None:
        segs = [r.get("attrs", {}) for r in spans
                if r["rung"] == "segment"]
        hit = sum(a.get("experts_hit", 0) for a in segs)
        layer_steps = sum(a.get("expert_layer_steps", 0) for a in segs)
    elif ctx.get("slice") is None:
        from theroundtaible_tpu.utils import telemetry
        total = getattr(telemetry.REGISTRY, "counter_total", None)
        if total is None:
            return None
        hit = total("roundtable_moe_experts_hit_total")
        layer_steps = total("roundtable_moe_expert_layer_steps_total")
    else:
        return None
    if not layer_steps:
        return None
    return 100.0 * hit / (float(held) * layer_steps)
