"""How late the open-loop generator sent its requests (99th percentile): a starved generator reads as a fast server."""

from benchmarks import readers


def read(ctx):
    return readers.percentile_ms(ctx["counters"].get("late_s"), 99)
