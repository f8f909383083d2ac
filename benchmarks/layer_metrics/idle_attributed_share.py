"""Share of the device's idle time in the traced window that a `ray_tpu.engine.*` span of the pump thread covers."""

from benchmarks import program_spans


def read(ctx):
    return program_spans.read(ctx, program_spans.idle_attributed_share)
