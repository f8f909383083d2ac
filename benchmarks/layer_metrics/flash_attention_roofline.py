"""Roofline share of the Pallas attention kernels."""

from benchmarks import readers


def read(ctx):
    return readers.flash_roofline(ctx)
