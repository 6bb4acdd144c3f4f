"""Compile watch: wall seconds of the set-up less the four stages and
less the phases `init`, `quantize` and `pools` — the warm programs' own
runs, the warm-up sessions' serving, and whatever no mark names yet:
the number that must not grow unnoticed."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import setuptable  # noqa: E402


def read(ctx):
    return setuptable.unstaged_seconds()
