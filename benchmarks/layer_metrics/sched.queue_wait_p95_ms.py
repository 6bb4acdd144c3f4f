"""Scheduler: the 95th percentile of the time requests spent queued
before admission (RequestTrace stage `queue_wait`; a request that never
queued counts as 0)."""


def read(ctx):
    vals = sorted(t["stages"].get("queue_wait", 0.0) * 1e3
                  for t in ctx["request_traces"]
                  if t.get("kind") == "request")
    if len(vals) < 20:
        return None
    return vals[min(int(len(vals) * 0.95), len(vals) - 1)]
