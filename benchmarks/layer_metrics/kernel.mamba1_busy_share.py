"""Kernels, for a decoder with Mamba-1 layers: device time of the two
kernels that touch the Mamba state (the joins' `mamba1_scan` and the
decode step's pass over the slots, `mamba1_step`; harness/mamba1_cost.py
knows them by the names the program gives them) over the device's busy
time, in the traced slice."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import mamba1_cost  # noqa: E402


def read(ctx):
    trace, config = ctx["trace"], ctx["config"]
    if not trace or not trace.get("busy_s") \
            or not mamba1_cost.is_mamba1(config):
        return None
    seconds = mamba1_cost.kernel_seconds(trace["op_seconds"])
    if seconds <= 0:
        return None
    return 100.0 * seconds / (trace["busy_s"] * trace["devices"])
