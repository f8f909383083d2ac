"""Roofline share of the decode step's window layers: the K and V rows the traced steps' sequences hold in them (`engine.decode_dispatch` spans' `window_rows`, median; at most `sliding_window` a sequence), read once a layer, over the time the operations under `attn.window` took. What a row is (K and V of one width, or keys of 192 beside values of 128 and a KV-head count by kind) is the configuration's cost module's to count, named by `answers/<runner>.py`."""

from benchmarks import costs


def read(ctx):
    return costs.ask(ctx, "decode_attention_roofline", "window")
