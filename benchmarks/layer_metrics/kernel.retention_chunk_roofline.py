"""Kernels, for a decoder with power-retention layers: the least time the
chip could take for the chunk kernel's work in the slice — for the
tokens the slice's segments prefilled (joins, re-scans included: the
scheduler's `segment_prefill_tokens`, by difference over the slice), in
every layer, the state read once and written once a page of them and a
token's products with it (harness/retention_cost.py) — over the device
time of the kernel the program names `retention_chunk`. Bytes and
operations are level at the bfloat16 peak; the program multiplies in
float32. A share over 100 says the floor counts too much or the time
leaves out work: it is an error, not a value."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import kernel_cost, retention_cost  # noqa: E402


def read(ctx):
    trace, sl, config = ctx["trace"], ctx["slice"], ctx["config"]
    if not trace or not sl or not retention_cost.is_retention(config):
        return None
    seconds = retention_cost.retention_seconds(
        trace["op_seconds"], config, retention_cost.CHUNK_KERNEL)
    tokens = (sl["counters_end"]["scheduler"]["segment_prefill_tokens"]
              - sl["counters_start"]["scheduler"]["segment_prefill_tokens"])
    if seconds <= 0 or tokens <= 0:
        return None
    share = 100.0 * kernel_cost.least_seconds(
        retention_cost.chunk_kernel_floor(config, tokens),
        ctx["peaks"])["seconds"] / seconds
    if share > 100.0:
        raise RuntimeError(
            f"kernel.retention_chunk_roofline reads {share:.1f} %: the "
            "floor of harness/retention_cost.py counts too much, or the "
            "chunk kernel's time leaves out work")
    return share
