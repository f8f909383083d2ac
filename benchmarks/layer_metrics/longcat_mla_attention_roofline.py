"""Roofline share of the decode step's absorbed latent attention in a configuration of double layers: the latent rows the traced steps' sequences hold (`engine.decode_dispatch` spans' `rows`, median), 576 values each, read once an attention SUBLAYER (two a double layer), over the time the operations under `mla.attend` took."""

from benchmarks import longcat_cost


def read(ctx):
    return longcat_cost.latent_attention_roofline(ctx)
