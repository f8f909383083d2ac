"""Roofline share of the decode step's attention over the latent cache: the k and v rows the traced steps' active sequences hold (`engine.decode_dispatch` spans' `rows`, median) over the time the operations under `cca.attend` took."""

from benchmarks import cca_cost, program_spans


def read(ctx):
    return cca_cost.attention_roofline(ctx, program_spans.read(
        ctx, program_spans.stat_median, program_spans.DECODE_DISPATCH, "rows"))
