"""Roofline share of the decode step's absorbed latent attention: the latent rows the traced steps' sequences hold (`engine.decode_dispatch` spans' `rows`, median), 576 values each, read once a latent-attention layer, over the time the operations under `mla.attend` took."""

from benchmarks import kimi_linear_cost


def read(ctx):
    return kimi_linear_cost.latent_attention_roofline(ctx)
