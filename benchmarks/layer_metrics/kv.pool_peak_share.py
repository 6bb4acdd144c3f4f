"""KV manager: peak pages in use over the pool's usable pages, sampled
every 50 ms through the window."""


def read(ctx):
    pages = ctx["counters"]["end"]["pool"]["pages"]
    return 100.0 * ctx["pool_peak_in_use"] / pages if pages else None
