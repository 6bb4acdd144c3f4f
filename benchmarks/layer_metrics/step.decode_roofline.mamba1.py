"""Step programs, for a decoder with Mamba-1 layers: the least time the
chip could take for the slice's plain decode segments
(harness/mamba1_cost.py: every layer's weights and the head once a step;
each advanced row's state and conv tail read once and written once in
every Mamba layer; the attention layers' keys and values of the rows'
contexts) over the decode program's device time in the slice — the share
of the whole step, as `step.decode_roofline.retention` is for a
retention decoder. The steps and the rows come from the slice's
`segment` spans of kind `plain`, the context lengths from the client's
rows. A share over 100 says the floor counts too much or the time leaves
out work: it is an error, not a value."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import kernel_cost, loopspans, mamba1_cost  # noqa: E402


def read(ctx):
    trace, sl, config = ctx["trace"], ctx["slice"], ctx["config"]
    if not trace or not sl or not mamba1_cost.is_mamba1(config):
        return None
    spans = loopspans.slice_spans(ctx)
    if spans is None:
        return None
    segs = [r["attrs"] for r in spans if r["rung"] == "segment"
            and r.get("attrs", {}).get("kind") == "plain"]
    steps = sum(a["steps"] for a in segs)
    row_steps = sum(a["decode_tokens"] for a in segs)
    seconds = sum(s for n, s in trace["module_seconds"].items()
                  if any(p in n for p in ctx["names"]["programs"]["decode"]))
    if not steps or not row_steps or seconds <= 0:
        return None
    contexts = kernel_cost.decoded_in(ctx["rows"], sl["start"], sl["end"])
    mean_context = sum(contexts) / len(contexts) if contexts else 0.0
    work = mamba1_cost.decode_floor(
        config, steps=steps, row_steps=row_steps,
        context_positions=int(mean_context * row_steps))
    share = 100.0 * kernel_cost.least_seconds(
        work, ctx["peaks"])["seconds"] / seconds
    if share > 100.0:
        raise RuntimeError(
            f"step.decode_roofline.mamba1 reads {share:.1f} %: the floor "
            "of harness/mamba1_cost.py counts too much, or the decode "
            "program's device time leaves out part of the work")
    return share
