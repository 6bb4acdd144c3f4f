"""Kernels: the least time the chip could take for the attention the
slice's tokens needed, over the attention kernels' summed device time.

The floor counts, for every token decoded in the slice, one read of its
context's keys and values and the operations on them; and for the prompt
tokens the ragged joins prefilled there, one write each (their offsets
are not known to the benchmark, so their reads are left out — the share
is a floor of the true one, never above it). At these shapes the bound
is memory: a decode step does 2 operations per byte of cache read."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import kernel_cost  # noqa: E402


def read(ctx):
    trace, sl = ctx["trace"], ctx["slice"]
    if not trace or not sl:
        return None
    seconds = kernel_cost.attention_seconds(trace["op_seconds"],
                                            ctx["config"])
    if seconds <= 0:
        return None
    work = kernel_cost.decode_floor(
        ctx["config"],
        kernel_cost.decoded_in(ctx["rows"], sl["start"], sl["end"]))
    prefilled = (
        sl["counters_end"]["scheduler"]["segment_prefill_tokens"]
        - sl["counters_start"]["scheduler"]["segment_prefill_tokens"])
    work["bytes"] += prefilled * kernel_cost.kv_bytes_per_token(
        ctx["config"])
    return 100.0 * kernel_cost.least_seconds(
        work, ctx["peaks"])["seconds"] / seconds
