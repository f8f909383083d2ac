"""Mean `ray_tpu.worker.stream_yield` span: serialising one streamed item, the blocking StreamingYield call to the caller and the release."""

from benchmarks import program_spans


def read(ctx):
    return program_spans.read(ctx, program_spans.mean_ms,
                              program_spans.STREAM_YIELD)
