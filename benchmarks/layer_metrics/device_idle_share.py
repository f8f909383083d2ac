"""Share of the traced window (train: the traced steps) in which no operation ran on the device."""

from benchmarks import readers


def read(ctx):
    return readers.idle_share(ctx)
