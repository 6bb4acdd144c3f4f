"""Front door: the median of a request's admission and placement stages,
from the gateway's own RequestTrace records of the window."""
import statistics


def read(ctx):
    vals = [(t["stages"].get("admission", 0.0)
             + t["stages"].get("placement", 0.0)) * 1e3
            for t in ctx["request_traces"] if t.get("kind") == "request"]
    return statistics.median(vals) if vals else None
