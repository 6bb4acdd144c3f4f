"""Compile watch: of the programs the set-up brought up, those compiled
fresh and not fetched from the persistent cache. 0 on a cache that held
the cell; what a `setup_s` that swings at fixed code swings with."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import setuptable  # noqa: E402


def read(ctx):
    return setuptable.count("cache_misses")
