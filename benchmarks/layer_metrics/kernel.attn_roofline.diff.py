"""Kernels, for differential attention over a pool that holds a kv PAIR
of 64-wide heads a 128-lane row: the least time the chip could take for
the pages the decode walk read in the slice — for every token decoded
there at context C, keys and values of min(C, window) positions in every
window layer and of C positions in the full layer and in EVERY cross
layer (which read the full layer's pool: one pool, several readers), at
their real width (5120 B a position a reading layer), and the model's
operations over them (a 64-wide score and a 128-wide weighted sum a
query head: harness/sambay_cost.py) — over the device time of the Mosaic
calls the program names `paged_decode_attention` with the
configuration's pool among their operands. The cross layers' walk above
the seam of a join runs under the same name at one row a sequence (under
a hundredth of the decode program's rows): its time is in, its reads are
not, so the share reads that much low. The joins' ragged walk is left
out: its reads depend on how runs fall into query blocks. A share over
100 says the floor counts too much or the time leaves out work: it is
an error, not a value."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import kernel_cost, sambay_cost  # noqa: E402


def read(ctx):
    trace, sl, config = ctx["trace"], ctx["slice"], ctx["config"]
    if not trace or not sl or not sambay_cost.is_sambay(config):
        return None
    seconds = sambay_cost.decode_walk_seconds(trace["op_seconds"], config)
    contexts = kernel_cost.decoded_in(ctx["rows"], sl["start"], sl["end"])
    if seconds <= 0 or not contexts:
        return None
    share = 100.0 * kernel_cost.least_seconds(
        sambay_cost.decode_walk_floor(config, contexts),
        ctx["peaks"])["seconds"] / seconds
    if share > 100.0:
        raise RuntimeError(
            f"kernel.attn_roofline.diff reads {share:.1f} %: the floor of "
            "harness/sambay_cost.py counts too much, or the walk's time "
            "leaves out work")
    return share
