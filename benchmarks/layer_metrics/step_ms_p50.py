"""Median measured step: host clock from asking for the batch to block_until_ready on the step's metrics."""

from benchmarks import readers


def read(ctx):
    return readers.median_ms(ctx["counters"].get("step_s"))
