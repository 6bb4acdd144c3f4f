"""Scheduler: the share of the traced slice the scheduler's thread spent
in `wait` — no row live and none admissible. In a closed loop that is
the lockstep's pause: every row of a round has retired and the clients'
next requests have not arrived."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import loopspans  # noqa: E402


def read(ctx):
    seconds = loopspans.loop_seconds(ctx)
    if seconds is None:
        return None
    return 100.0 * seconds.get("wait", 0.0) / loopspans.slice_seconds(ctx)
