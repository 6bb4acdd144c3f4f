"""Scheduler: the share of the traced slice the scheduler's thread spent
on host work — every phase of its loop clock but `sync` and `admit_sync`
(blocked because the device works) and `wait` (nothing to run). With
segments dispatched one ahead, host work overlaps device work, so this
is not the device's idle share: it is what the host would have to lose
for the idle share to fall."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import loopspans  # noqa: E402

NOT_HOST_WORK = ("sync", "admit_sync", "wait")


def read(ctx):
    seconds = loopspans.loop_seconds(ctx)
    if seconds is None:
        return None
    host = sum(s for phase, s in seconds.items()
               if phase not in NOT_HOST_WORK)
    return 100.0 * host / loopspans.slice_seconds(ctx)
