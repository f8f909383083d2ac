"""Median device duration of the jitted decode program in the replica's trace."""

from benchmarks import readers


def read(ctx):
    p = readers.program(ctx, readers.DECODE_PROGRAM)
    return p["p50_s"] * 1e3 if p else None
