"""The part of the collective time during which no compute ran on that chip, per traced step."""


def read(ctx):
    t = ctx["trace"]
    return t["collective_exposed_s"] * 1e3 / ctx["counters"]["trace_steps"] if t else None
