"""Mean device duration of the jitted prefill programs (all buckets of the cell) per admitted request, in the replica's trace."""

from benchmarks import readers


def read(ctx):
    p = readers.program(ctx, readers.PREFILL_PROGRAM)
    return p["total_s"] * 1e3 / p["count"] if p else None
