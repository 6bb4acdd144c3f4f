"""Client: the 95th percentile over the window's rows of (last - first
token time) / (tokens - 1), as the load generator saw them on its side
of the gateway socket — how fast the slowest knights speak. Read in the
traced run; the same arithmetic as the end-to-end metrics
(`harness/endtoend.py`), so a failed row stays in at the time it was
waited for."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import endtoend  # noqa: E402


def read(ctx):
    return endtoend.of_context(ctx).get("tpot_p95_ms")
