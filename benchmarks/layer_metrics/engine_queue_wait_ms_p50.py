"""Median `queued_ms` (submit to admit, the engine's clock) of the `ray_tpu.engine.admit` spans in the traced window."""

from benchmarks import program_spans


def read(ctx):
    return program_spans.read(ctx, program_spans.stat_median,
                              program_spans.ADMIT, "queued_ms")
