"""Device: of the traced slice's idle seconds (its length less the
device's busy seconds), the share that `idle_gaps` puts under a name
that names no host activity — `none`, or a span held open across
scheduler ticks (`rt:request`, `rt:resume`, `rt:turn`: a request was
open, which says nothing of what the host did). How much of the idle
time the tracing still cannot explain. `idle_gaps` keeps the ten names
with most seconds, so a name left out of it counts for nothing here."""

UNNAMED = ("none", "rt:request", "rt:resume", "rt:turn")


def read(ctx):
    trace = ctx["trace"] or {}
    if not trace.get("idle_gaps"):
        return None
    idle = trace["window_s"] - trace["busy_s"]
    if idle <= 0:
        return None
    return 100.0 * sum(s for name, s in trace["idle_gaps"]
                       if name in UNNAMED) / idle
