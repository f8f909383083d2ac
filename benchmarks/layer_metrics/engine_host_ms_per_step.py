"""Median over traced decode steps of the `ray_tpu.engine.step` span less its `sample_sync` and `first_token_sync` children: what the host does itself each step."""

from benchmarks import program_spans


def read(ctx):
    return program_spans.read(ctx, program_spans.host_ms_per_step)
