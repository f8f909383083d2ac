"""Mean `ray_tpu.engine.admit` span: what one admission costs every stream, as the pump thread sees it (`prefill_device_ms_per_req` is the device's part of it)."""

from benchmarks import program_spans


def read(ctx):
    return program_spans.read(ctx, program_spans.mean_ms, program_spans.ADMIT)
