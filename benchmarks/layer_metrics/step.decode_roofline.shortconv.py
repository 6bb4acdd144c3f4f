"""Step programs, for a decoder with gated short-convolution layers: the
least time the chip could take for the slice's plain decode segments
(harness/shortconv_cost.py: every conv mixer's and attention layer's
matrices, the dense SwiGLUs, the routers and the head once a step; the
routed experts some row chose; each advanced row's conv tails read once
and written once; the keys and values of the rows' contexts at 2 KB a
position a layer) over the decode program's device time in the slice —
the share of the whole step, as `step.decode_roofline.mamba1` is for a
Mamba-1 decoder. The steps and the rows come from the slice's `segment`
spans of kind `plain`; the experts hit a step from ALL the slice's
segment spans' `experts_hit` over their `expert_layer_steps` (the
all-hitting joins among them, so the floor errs high); the context
lengths from the client's rows. A share over 100 says the floor counts
too much or the time leaves out work: it is an error, not a value."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import kernel_cost, loopspans, shortconv_cost  # noqa: E402


def read(ctx):
    trace, sl, config = ctx["trace"], ctx["slice"], ctx["config"]
    if not trace or not sl or not shortconv_cost.is_shortconv(config):
        return None
    spans = loopspans.slice_spans(ctx)
    if spans is None:
        return None
    every = [r.get("attrs", {}) for r in spans if r["rung"] == "segment"]
    layer_steps = sum(a.get("expert_layer_steps", 0) for a in every)
    segs = [a for a in every if a.get("kind") == "plain"]
    steps = sum(a["steps"] for a in segs)
    row_steps = sum(a["decode_tokens"] for a in segs)
    seconds = sum(s for n, s in trace["module_seconds"].items()
                  if any(p in n for p in ctx["names"]["programs"]["decode"]))
    if not steps or not row_steps or not layer_steps or seconds <= 0:
        return None
    hit_a_layer_step = sum(a.get("experts_hit", 0)
                           for a in every) / layer_steps
    contexts = kernel_cost.decoded_in(ctx["rows"], sl["start"], sl["end"])
    mean_context = sum(contexts) / len(contexts) if contexts else 0.0
    work = shortconv_cost.decode_floor(
        config, steps=steps,
        experts_hit=int(hit_a_layer_step * steps
                        * shortconv_cost.sizes(config)["sparse"]),
        row_steps=row_steps,
        context_positions=int(mean_context * row_steps))
    share = 100.0 * kernel_cost.least_seconds(
        work, ctx["peaks"])["seconds"] / seconds
    if share > 100.0:
        raise RuntimeError(
            f"step.decode_roofline.shortconv reads {share:.1f} %: the "
            "floor of harness/shortconv_cost.py counts too much, or the "
            "decode program's device time leaves out part of the work")
    return share
