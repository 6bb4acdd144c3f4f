"""Kernels, for attention layers whose heads are narrower than a lane
row (64 wide): the least time the chip could take for the pages the
decode walk read in the slice — for every token decoded there, the keys
and values of its context once in every attention layer AT THEIR REAL
WIDTH (kv heads x head_dim x (k, v) x 2 B a position a layer: 2048 B,
harness/shortconv_cost.py), and the scores and the weighted sum over
them — over the device time of the Mosaic calls the program names
`paged_decode_attention` with the configuration's pool among their
operands. The pool holds two heads of one token a 128-lane row, so the
walk copies exactly those bytes and multiplies twice the operations (a
query row carries its head's half and zeros): the bytes bound it, and
what the share loses to the doubled products and to the walk's fixed
work a trip is what 64-wide heads cost. The joins' walk
(`ragged_paged_attention`) is left out: its reads depend on how runs
fall into query blocks. A share over 100 says the floor counts too much
or the time leaves out work: it is an error, not a value."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import kernel_cost, shortconv_cost  # noqa: E402


def read(ctx):
    trace, sl, config = ctx["trace"], ctx["slice"], ctx["config"]
    if not trace or not sl or not shortconv_cost.is_shortconv(config):
        return None
    seconds = shortconv_cost.decode_walk_seconds(trace["op_seconds"],
                                                 config)
    contexts = kernel_cost.decoded_in(ctx["rows"], sl["start"], sl["end"])
    if seconds <= 0 or not contexts:
        return None
    share = 100.0 * kernel_cost.least_seconds(
        shortconv_cost.decode_walk_floor(config, contexts),
        ctx["peaks"])["seconds"] / seconds
    if share > 100.0:
        raise RuntimeError(
            f"kernel.attn_roofline.d64 reads {share:.1f} %: the floor of "
            "harness/shortconv_cost.py counts too much, or the walk's "
            "time leaves out work")
    return share
