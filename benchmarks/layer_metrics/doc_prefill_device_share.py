"""Share of the traced window the device spent in prefill programs."""

from benchmarks import readers


def read(ctx):
    p = readers.program(ctx, readers.PREFILL_PROGRAM)
    return 100.0 * p["total_s"] / ctx["trace"]["window_s"] if p else None
