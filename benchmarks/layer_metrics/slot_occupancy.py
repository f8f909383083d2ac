"""Share of the decode steps' rows that emitted a token."""

from benchmarks import readers


def read(ctx):
    return readers.slot_occupancy(ctx)
