"""Roofline share of the decode step's full layers: the K and V rows the traced steps' sequences hold in the slots (`engine.decode_dispatch` spans' `rows`, median), read once a layer, over the time the operations under `attn.full` took. What a row is (K and V of one width, or keys of 192 beside values of 128 and a KV-head count by kind) is the configuration's cost module's to count, named by `answers/<runner>.py`."""

from benchmarks import costs


def read(ctx):
    return costs.ask(ctx, "decode_attention_roofline", "full")
