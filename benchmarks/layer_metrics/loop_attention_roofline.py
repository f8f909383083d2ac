"""Roofline share of a looped model's decode attention: every held row's K and V of passes x layers cache layers read once (the traced steps' median `rows`), at the HBM's peak, over `loop_attention_ms_per_decode_step`: `decode_attention` at this depth (`ouro_cost.py`)."""

from benchmarks import ouro_cost


def read(ctx):
    return ouro_cost.attention_roofline(ctx)
