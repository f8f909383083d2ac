"""Median gap between chunks at the client, less the engine's own step period in the traced window (`engine_step_period_ms`): what replica -> proxy -> client adds."""

from benchmarks import program_spans, readers


def read(ctx):
    gap = readers.median_ms(ctx["counters"].get("gaps_s"))
    period = program_spans.read(ctx, program_spans.step_period_ms)
    return gap - period if gap is not None and period is not None else None
