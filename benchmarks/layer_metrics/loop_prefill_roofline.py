"""Roofline share of a looped model's prefill: the captured prompt's REAL rows' matrix products through every layer of every pass, their causal attention and the head at one position, beside the weights' read once a pass, the larger of the two bounds (at 192 rows the memory's), over the capture's device time (`ouro_cost.py`: a bucket's pads are computed and not required)."""

from benchmarks import ouro_cost


def read(ctx):
    return ouro_cost.prefill_roofline(ctx)
