"""Scheduler: the share of the traced slice in which the scheduler's
loop had no step program of its own outstanding on the device and was
not in `wait` — the device starved by the host's own work (the loop
clock's feed bit: every `loop.<phase>` span says `fed` 0 or 1). What a
change to the host at a round's start can give back to the device."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import feedspans  # noqa: E402


def read(ctx):
    return feedspans.starved_share(ctx)
