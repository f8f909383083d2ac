"""Roofline share of the decode step's window layers: the K and V rows the traced steps' sequences hold in a ring (`engine.decode_dispatch` spans' `window_rows`, median) over the time the operations under `attn.window` took."""

from benchmarks import laguna_cost


def read(ctx):
    return laguna_cost.attention_roofline(ctx, "window")
