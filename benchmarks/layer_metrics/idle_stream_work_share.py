"""Of the device's idle time in the traced window, the share during which at least one handler thread is inside `ray_tpu.replica.detokenize` or inside `ray_tpu.worker.stream_yield` but outside its `stream_rpc` child; the same share of the whole traced window, the baseline, goes to stderr."""

from benchmarks import harness, program_spans, stream_spans


def read(ctx):
    share = program_spans.read(ctx, stream_spans.idle_stream_work_share)
    if not share:
        return None
    harness.say("stream_work", of_idle=round(share["idle"], 2),
                of_window=round(share["window"], 2))
    return share["idle"]
