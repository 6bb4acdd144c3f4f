"""Compile watch: programs compiled (or fetched from the persistent
cache) between the window's start and its end. Should read 0."""


def read(ctx):
    a, b = ctx["counters"]["start"], ctx["counters"]["end"]
    return float(b["compiles"] - a["compiles"])
