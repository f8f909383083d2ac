"""Median gap between chunks of one stream, all streams pooled: one decode step as the client sees it, with no prefill in it."""

from benchmarks import readers


def read(ctx):
    return readers.median_ms(ctx["counters"].get("gaps_s"))
