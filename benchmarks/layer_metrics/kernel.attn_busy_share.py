"""Kernels: device time of the Pallas attention kernels (the Mosaic
calls that read the KV pool) over the device's busy time, in the traced
slice."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import kernel_cost  # noqa: E402


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace.get("busy_s"):
        return None
    seconds = kernel_cost.attention_seconds(trace["op_seconds"],
                                            ctx["config"])
    if seconds <= 0:
        return None
    return 100.0 * seconds / (trace["busy_s"] * trace["devices"])
