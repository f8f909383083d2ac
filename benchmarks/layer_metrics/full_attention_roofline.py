"""Roofline share of the decode step's full layers: the K and V rows the traced steps' sequences hold in the slots (`engine.decode_dispatch` spans' `rows`, median) over the time the operations under `attn.full` took."""

from benchmarks import laguna_cost


def read(ctx):
    return laguna_cost.attention_roofline(ctx, "full")
