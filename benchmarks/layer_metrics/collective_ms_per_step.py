"""Time of collective operations per traced step, averaged over the chips."""


def read(ctx):
    t = ctx["trace"]
    return t["collective_s"] * 1e3 / ctx["counters"]["trace_steps"] if t else None
