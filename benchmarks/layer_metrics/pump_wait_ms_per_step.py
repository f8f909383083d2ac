"""The pump ready and not running, a decode step: (`pump_step_s` - `pump_sync_s` - `pump_cpu_s`) over `steps` between the first and the last booking of the pump's clocks that the traced window's `ray_tpu.engine.step` spans show: the GIL, or a core the thread did not get."""

from benchmarks import stream_spans


def read(ctx):
    return stream_spans.read_counter(ctx, stream_spans.pump_wait_ms_per_step)
