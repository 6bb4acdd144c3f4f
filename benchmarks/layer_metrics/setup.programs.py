"""Compile watch: programs the set-up lowered, from the hooks going in
to the scheduler declaring its warm-up complete — the work count of a
start. The same on every run of one tree: more means a program was
added, or one is lowered a second time."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import setuptable  # noqa: E402


def read(ctx):
    return setuptable.count("programs")
