"""Roofline share of the decode step's absorbed latent attention: the latent rows the traced steps' sequences hold (`engine.decode_dispatch` spans' `rows`, median), 576 values each, read once a latent-attention layer (once an attention SUBLAYER where layers are double: two a layer), over the time the operations under `mla.attend` took. The configuration's cost module counts the layers, named by `answers/<runner>.py`."""

from benchmarks import costs


def read(ctx):
    return costs.ask(ctx, "latent_attention_roofline")
