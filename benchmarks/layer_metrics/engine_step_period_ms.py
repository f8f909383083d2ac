"""Median time between the starts of consecutive `ray_tpu.engine.step` spans that reached the decode dispatch, with no `engine.idle` between them."""

from benchmarks import program_spans


def read(ctx):
    return program_spans.read(ctx, program_spans.step_period_ms)
