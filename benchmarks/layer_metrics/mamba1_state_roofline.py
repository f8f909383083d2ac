"""Roofline share of the decode step's state update: the traced steps' active sequences' states (`engine.decode_dispatch` spans' `active`, median), float32, read once and written once a Mamba-1 mixer, and the rates once, over the time the operations under `ssm1.state` took. Of the HBM bound, which binds it (`jamba_cost.py`)."""

from benchmarks import jamba_cost


def read(ctx):
    return jamba_cost.state_roofline(ctx)
