"""Median, over `ray_tpu.replica.detokenize` spans with `backlog` 0, of the span's start less the start of the pump's latest `ray_tpu.engine.emit` before it: how long a token that exists waits for its handler thread to run. Read where the handlers keep up with the pump (the chat cell): in the two long-output cells a stream's yield outlasts a step, its queue is hardly ever empty when an id is taken, and there is nothing to read (PERF.md section 6, PR 36)."""

from benchmarks import program_spans, stream_spans


def read(ctx):
    return program_spans.read(ctx, stream_spans.handoff_ms_p50)
