"""Roofline share of a looped model's decode step outside the head and the sampling: the ONE stack's weights once a pass, every held row of passes x layers cache layers (the traced steps' `engine.decode_dispatch` spans' median `rows`) and the active sequences' new rows, at the HBM's peak, over `loop_layers_ms_per_decode_step`. The memory binds it (`ouro_cost.py`)."""

from benchmarks import ouro_cost


def read(ctx):
    return ouro_cost.layers_roofline(ctx)
