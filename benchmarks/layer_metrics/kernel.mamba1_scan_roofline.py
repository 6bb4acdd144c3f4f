"""Kernels, for a decoder with Mamba-1 layers: the least time the chip
could take for the scan kernel's work in the slice — for the (token,
Mamba layer) pairs the slice's join programs scanned (the `scan_tokens`
of its `segment` spans, pads left out), `c`, `dt` and `y` of d_inner
float32 values and `B`, `C` of d_state a token, and the state once in
and once out a page of them (harness/mamba1_cost.py) — over the device
time of the kernel the program names `mamba1_scan`. On paper the bytes
bound it (the operations are 1/25 of them at the bfloat16 peak); in
truth the kernel is bound by the vector and transcendental units, which
`peaks.json` does not know, so the share's ceiling is well under 100 and
it is reported to be watched. A share over 100 says the floor counts too
much or the time leaves out work: it is an error, not a value."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import kernel_cost, loopspans, mamba1_cost  # noqa: E402


def read(ctx):
    trace, sl, config = ctx["trace"], ctx["slice"], ctx["config"]
    if not trace or not sl or not mamba1_cost.is_mamba1(config):
        return None
    spans = loopspans.slice_spans(ctx)
    if spans is None:
        return None
    tokens = sum(r.get("attrs", {}).get("scan_tokens", 0) for r in spans
                 if r["rung"] == "segment")
    seconds = mamba1_cost.kernel_seconds(trace["op_seconds"],
                                         mamba1_cost.KERNEL)
    if tokens <= 0 or seconds <= 0:
        return None
    share = 100.0 * kernel_cost.least_seconds(
        mamba1_cost.scan_floor(config, tokens),
        ctx["peaks"])["seconds"] / seconds
    if share > 100.0:
        raise RuntimeError(
            f"kernel.mamba1_scan_roofline reads {share:.1f} %: the floor "
            "of harness/mamba1_cost.py counts too much, or the scan "
            "kernel's time leaves out work")
    return share
