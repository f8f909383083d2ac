"""The pump thread's own CPU time a decode step: `ContinuousBatcher.stats["pump_cpu_s"]` over `steps`, between the first and the last booking of the pump's clocks that the traced window's `ray_tpu.engine.step` spans show (the spans carry the counters)."""

from benchmarks import stream_spans


def read(ctx):
    return stream_spans.read_counter(ctx, stream_spans.pump_cpu_ms_per_step)
