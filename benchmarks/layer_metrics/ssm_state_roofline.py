"""Roofline share of the decode step's state update: the traced steps' active sequences' states (`engine.decode_dispatch` spans' `active`, median), float32, read once and written once a state-space mixer, over the time the operations under `ssm.state` took."""

from benchmarks import nemotron_h_cost


def read(ctx):
    return nemotron_h_cost.state_roofline(ctx)
