"""Roofline share of the decode step's grouped expert matmuls: the weights of the held experts a step's real rows reached (`moe_experts_reached` over the window's steps) over the `ragged-dot`s' time in a traced decode step."""

from benchmarks import laguna_cost


def read(ctx):
    return laguna_cost.held_experts_roofline(ctx)
