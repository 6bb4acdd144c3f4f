"""Client: the 95th percentile over the window's rows of the time from
when a row was due to its first token event, as the load generator saw
it on its side of the gateway socket. Read in the traced run; the same
arithmetic as the end-to-end metrics (`harness/endtoend.py`), and like
them only from 200 samples."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import endtoend  # noqa: E402


def read(ctx):
    return endtoend.of_context(ctx).get("ttft_p95_ms")
