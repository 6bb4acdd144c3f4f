"""Kernels, for a decoder whose attention layers differ (`layer_types`:
window and full layers, `num_attention_heads_per_layer`): the least time
the chip could take for the attention the slice's tokens needed, over
the attention kernels' summed device time. `kernel.attn_roofline`'s
rule — the kernels found as it finds them, a Mosaic call with the KV
pool among its operands — with the cost of harness/window_cost.py, which
counts a sliding layer at what its window needs in whole pages and not
at the whole context (the accepted cost would read this configuration
over 100 %).

The floor counts, for every token decoded in the slice, one read of its
context's keys and values a layer, by the layer's own geometry, and the
operations on them; and for the prompt tokens the joins prefilled
there, one write each a layer (a join's reads are left out: the share
is a floor of the true one). Memory-bound at these shapes. A share over
100 is an error, not a value."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import kernel_cost, window_cost  # noqa: E402


def read(ctx):
    trace, sl, config = ctx["trace"], ctx["slice"], ctx["config"]
    if not trace or not sl or not window_cost.is_laguna(config):
        return None
    seconds = kernel_cost.attention_seconds(trace["op_seconds"], config)
    if seconds <= 0:
        return None
    work = window_cost.decode_kernel_floor(
        config, kernel_cost.decoded_in(ctx["rows"], sl["start"],
                                       sl["end"]))
    prefilled = (
        sl["counters_end"]["scheduler"]["segment_prefill_tokens"]
        - sl["counters_start"]["scheduler"]["segment_prefill_tokens"])
    work["bytes"] += window_cost.prefill_write_bytes(config, prefilled)
    share = 100.0 * kernel_cost.least_seconds(
        work, ctx["peaks"])["seconds"] / seconds
    if share > 100.0:
        raise RuntimeError(
            f"kernel.attn_roofline.window reads {share:.1f} %: the floor "
            "of harness/window_cost.py counts too much, or the kernels' "
            "time leaves out work")
    return share
