"""Compile watch: thread-seconds the set-up spent fetching programs
from the persistent cache and compiling those it did not hold
(`stages.retrieve + stages.compile`)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import setuptable  # noqa: E402


def read(ctx):
    return setuptable.stage_seconds("retrieve", "compile")
