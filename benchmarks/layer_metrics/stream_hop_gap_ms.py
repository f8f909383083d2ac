"""Median gap between chunks at the client, less the engine's mean step period: what replica -> proxy -> client adds."""

from benchmarks import readers


def read(ctx):
    c = ctx["counters"]
    rate = readers.engine_rate(ctx, "steps")
    gap = readers.median_ms(c.get("gaps_s"))
    return gap - 1000.0 / rate if rate and gap is not None else None
