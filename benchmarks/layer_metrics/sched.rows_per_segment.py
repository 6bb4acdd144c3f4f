"""Scheduler: mean live rows per decode step over the window — decode
tokens emitted over decode steps run. A plain segment is up to 64 steps
(serving_loop.DECODE_SEGMENT), a ragged dispatch one; `occupancy_mean`
is a mean over a recent deque, not over the window, so it is not used."""

DECODE_SEGMENT = 64


def read(ctx):
    a, b = (ctx["counters"][k]["scheduler"] for k in ("start", "end"))
    steps = ((b["segments"] - a["segments"]) * DECODE_SEGMENT
             + (b["ragged_segments"] - a["ragged_segments"]))
    tokens = b["segment_decode_tokens"] - a["segment_decode_tokens"]
    return tokens / steps if steps > 0 else None
