"""Kernels, for a decoder whose attention layers are some of its layers
(`hybrid_override_pattern`): the least time the chip could take for the
attention the slice's tokens needed, over the attention kernels' summed
device time. `kernel.attn_roofline`'s rule with the cost of
harness/hybrid_cost.py, which counts keys and values for the attention
layers alone (the accepted cost multiplies by `num_hidden_layers`, and
would read this configuration at six times its share).

The floor counts, for every token decoded in the slice, one read of its
context's keys and values and the operations on them; and for the
prompt tokens the ragged joins scanned there, one write each (a join's
reads are left out: the share is a floor of the true one)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import hybrid_cost, kernel_cost  # noqa: E402


def read(ctx):
    trace, sl, config = ctx["trace"], ctx["slice"], ctx["config"]
    if not trace or not sl or "hybrid_override_pattern" not in config:
        return None
    seconds = kernel_cost.attention_seconds(trace["op_seconds"], config)
    if seconds <= 0:
        return None
    positions = sum(kernel_cost.decoded_in(ctx["rows"], sl["start"],
                                           sl["end"]))
    prefilled = (
        sl["counters_end"]["scheduler"]["segment_prefill_tokens"]
        - sl["counters_start"]["scheduler"]["segment_prefill_tokens"])
    per_position = hybrid_cost.kv_bytes_per_position(config)
    work = {
        "bytes": float((positions + prefilled) * per_position),
        "flops": float(4 * positions * int(config["num_attention_heads"])
                       * int(config["head_dim"])
                       * hybrid_cost.count(config, "attention")),
    }
    share = 100.0 * kernel_cost.least_seconds(
        work, ctx["peaks"])["seconds"] / seconds
    if share > 100.0:
        raise RuntimeError(
            f"kernel.attn_roofline.hybrid reads {share:.1f} %: the floor "
            "counts too much, or the kernels' time leaves out work")
    return share
