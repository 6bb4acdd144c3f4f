"""Device: the share of the traced slice in which no operation ran on
the device (1 - union of the operation intervals over the slice), in
closed-loop cells, where there is always work to do."""


def read(ctx):
    idle = (ctx["trace"] or {}).get("idle_share")
    return 100.0 * idle if idle is not None else None
