"""Roofline share of the prefills' expert matmuls: the required work of the traced admits' real prompt tokens (`engine.admit` spans' `prompt_len`; pad rows are not required) over the time the matmuls took."""

import statistics

from benchmarks import moe_cost, program_spans


def read(ctx):
    parsed = program_spans.load(ctx)
    lens = [s[4]["prompt_len"] for s in program_spans.named(
        parsed, program_spans.ADMIT) if "prompt_len" in s[4]] if parsed else []
    return moe_cost.experts_roofline(
        ctx, statistics.fmean(float(n) for n in lens) if lens else None)
