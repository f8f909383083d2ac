"""Roofline share of the decode step's state update: the traced steps' active sequences' matrix states (`engine.decode_dispatch` spans' `active`, median), float32, read once and written once a linear-attention layer, over the time the operations under `kda.state` took."""

from benchmarks import kimi_linear_cost


def read(ctx):
    return kimi_linear_cost.state_roofline(ctx)
