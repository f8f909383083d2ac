"""Mean `ray_tpu.replica.detokenize` span: what one streamed token's text costs its handler thread; since PR 57 a window of the answer's last few ids decoded (the span's `decoded`), not the whole answer so far (its `ids`) again. The median `ids` and the `backlog`'s median, largest and share of zeros go to stderr."""

import statistics

from benchmarks import harness, program_spans, stream_spans


def read(ctx):
    parsed = program_spans.load(ctx)
    value = program_spans.mean_ms(parsed, stream_spans.DETOKENIZE) \
        if parsed else None
    if value is not None:
        backlogs = [s[4]["backlog"] for s in program_spans.named(
            parsed, stream_spans.DETOKENIZE)]
        harness.say("detokenize", n=len(backlogs),
                    ids_median=program_spans.stat_median(
                        parsed, stream_spans.DETOKENIZE, "ids"),
                    backlog_median=statistics.median(backlogs),
                    backlog_max=max(backlogs),
                    backlog_zero_share=round(
                        backlogs.count(0) / len(backlogs), 4))
    return value
