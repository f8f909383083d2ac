"""Roofline share of the decode step's gated grouped attention beside delta-rule layers: the K and V rows the traced steps' sequences hold (`engine.decode_dispatch` spans' median `rows`; 8 bfloat16 heads of 128 each: 4 KiB a position a layer), read once a layer at the HBM's peak, over the time of the operations under `gqa.attend` (the row write and `decode_attention`; `solar_open2_cost.py`)."""

from benchmarks import solar_open2_cost


def read(ctx):
    return solar_open2_cost.attention_roofline(ctx)
