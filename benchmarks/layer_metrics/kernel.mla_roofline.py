"""Kernels, for a decoder with latent attention (`kv_lora_rank`): the
least time the chip could take for the decode kernel's work in the
slice — for every token decoded there, one read of its context's latent
entries at their PUBLISHED 1152 bytes a layer and the absorbed form's
operations on them (harness/mla_cost.py) — over the device time of the
kernel the program names `mla_paged_decode` (a Mosaic call with the
latent pool among its operands). Half memory-bound, half compute-bound
at 64 heads: the roofline takes the larger of the two. A share over 100
says the floor counts too much or the time leaves out work: it is an
error, not a value."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import kernel_cost, mla_cost  # noqa: E402


def read(ctx):
    trace, sl, config = ctx["trace"], ctx["slice"], ctx["config"]
    if not trace or not sl or not mla_cost.is_mla(config):
        return None
    seconds = mla_cost.latent_seconds(trace["op_seconds"], config,
                                      "mla_paged_decode")
    if seconds <= 0:
        return None
    contexts = kernel_cost.decoded_in(ctx["rows"], sl["start"], sl["end"])
    work = mla_cost.decode_kernel_floor(config, contexts)
    share = 100.0 * kernel_cost.least_seconds(
        work, ctx["peaks"])["seconds"] / seconds
    if share > 100.0:
        raise RuntimeError(
            f"kernel.mla_roofline reads {share:.1f} %: the floor of "
            "harness/mla_cost.py counts too much, or the decode kernel's "
            "time leaves out work")
    return share
