"""Where the benchmark lives, for its tests."""
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
for p in (REPO, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
