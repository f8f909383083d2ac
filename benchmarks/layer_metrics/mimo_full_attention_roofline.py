"""Roofline share of the decode step's full layers where keys are wider than values and the KV heads differ by kind: the K (192) and V (128) rows the traced steps' sequences hold in them (`engine.decode_dispatch` spans' `rows`, median) over the time the operations under `attn.full` took."""

from benchmarks import mimo_cost


def read(ctx):
    return mimo_cost.decode_attention_roofline(ctx, "full")
