"""Step programs, for a decoder whose upper half keeps no cache (Mamba-1
and differential attention below, gated memory units and cross layers
above): the least time the chip could take for the slice's plain decode
segments (harness/sambay_cost.py: every layer's weights and the tied
head once a step; each advanced row's state and conv tail read once and
written once in every Mamba layer; keys and values of at most a window
in the window layers, of the whole context in the full layer and once
more in every cross layer that reads its pool) over the decode program's
device time in the slice — the share of the whole step, as
`step.decode_roofline.mamba1` is for a decoder whose layers each own
what they read. The steps and the rows come from the `segment` spans of
kind `plain` that OVERLAP the slice, each counted by the part of it that
lies inside (a 64-step segment that straddles an end would else count
whole against device seconds that are clipped to the slice: 7 % of a
6 s slice, my traced runs, PR 56), the context lengths from the client's
rows. A share over 100 says the floor counts too much or the time leaves
out work: it is an error, not a value."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import kernel_cost, loopspans, sambay_cost  # noqa: E402


def read(ctx):
    trace, sl, config = ctx["trace"], ctx["slice"], ctx["config"]
    if not trace or not sl or not sambay_cost.is_sambay(config):
        return None
    spans = loopspans.slice_spans(ctx, loopspans.LOOKBACK_S)
    if spans is None:
        return None
    steps = row_steps = 0.0
    for r in spans:
        a = r.get("attrs", {})
        if r["rung"] != "segment" or a.get("kind") != "plain":
            continue
        lo, hi = max(r["t0"], sl["start"]), min(r["t0"] + r["dur_s"],
                                                sl["end"])
        inside = (hi - lo) / r["dur_s"] if r["dur_s"] > 0 else float(
            sl["start"] <= r["t0"] < sl["end"])
        if inside > 0:
            steps += inside * a["steps"]
            row_steps += inside * a["decode_tokens"]
    seconds = sum(s for n, s in trace["module_seconds"].items()
                  if any(p in n for p in ctx["names"]["programs"]["decode"]))
    contexts = kernel_cost.decoded_in(ctx["rows"], sl["start"], sl["end"])
    if not steps or not row_steps or seconds <= 0 or not contexts:
        return None
    work = sambay_cost.decode_floor(config, steps=steps,
                                    row_steps=row_steps,
                                    context_lengths=contexts)
    share = 100.0 * kernel_cost.least_seconds(
        work, ctx["peaks"])["seconds"] / seconds
    if share > 100.0:
        raise RuntimeError(
            f"step.decode_roofline.sambay reads {share:.1f} %: the floor "
            "of harness/sambay_cost.py counts too much, or the decode "
            "program's device time leaves out part of the work")
    return share
