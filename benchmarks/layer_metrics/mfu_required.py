"""Model FLOP/s utilization on required operations."""

from benchmarks import readers


def read(ctx):
    return readers.mfu_required(ctx)
