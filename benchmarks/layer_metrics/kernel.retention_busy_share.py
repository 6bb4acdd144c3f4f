"""Kernels, for a decoder with power-retention layers: device time of
every Pallas kernel that reads the slot states (the decode step's
`retention_step` and the joins' `retention_chunk`;
harness/retention_cost.py knows them by the states among their operands
or by those names) over the device's busy time, in the traced slice."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import retention_cost  # noqa: E402


def read(ctx):
    trace, config = ctx["trace"], ctx["config"]
    if not trace or not trace.get("busy_s") \
            or not retention_cost.is_retention(config):
        return None
    seconds = retention_cost.retention_seconds(trace["op_seconds"], config)
    if seconds <= 0:
        return None
    return 100.0 * seconds / (trace["busy_s"] * trace["devices"])
