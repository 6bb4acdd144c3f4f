"""Kernels, for a decoder with latent attention: device time of every
Pallas kernel that reads the latent pool (decode walk, ragged join,
paged prefill: harness/mla_cost.py knows them by the pool among their
operands) over the device's busy time, in the traced slice."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import mla_cost  # noqa: E402


def read(ctx):
    trace, config = ctx["trace"], ctx["config"]
    if not trace or not trace.get("busy_s") or not mla_cost.is_mla(config):
        return None
    seconds = mla_cost.latent_seconds(trace["op_seconds"], config)
    if seconds <= 0:
        return None
    return 100.0 * seconds / (trace["busy_s"] * trace["devices"])
