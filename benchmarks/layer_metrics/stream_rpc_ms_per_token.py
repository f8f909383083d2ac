"""Mean `ray_tpu.worker.stream_rpc` span: the blocking StreamingYield call of one streamed item (the wire, the caller's handler, the ack back), apart from serialising and releasing it; the part of `stream_yield_ms_per_token` in which the handler thread holds no GIL."""

from benchmarks import program_spans, stream_spans


def read(ctx):
    return program_spans.read(ctx, program_spans.mean_ms,
                              stream_spans.STREAM_RPC)
