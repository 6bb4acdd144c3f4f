"""Compile watch: thread-seconds the set-up spent tracing programs and
lowering them to modules (`stages.trace + stages.lower`) — what no
compile cache spares, and what lowering a kernel once for all layers
would take away."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import setuptable  # noqa: E402


def read(ctx):
    return setuptable.stage_seconds("trace", "lower")
