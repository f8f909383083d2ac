"""Decode steps the engine took per second of the window (engine_stats deltas)."""

from benchmarks import readers


def read(ctx):
    return readers.engine_rate(ctx, "steps")
