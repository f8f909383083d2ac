"""Roofline share of the decode step's grouped expert matmuls where an expert is TWO matrices in a latent: the weights of the held experts a step's real rows reached (`moe_experts_reached` over the window's steps, `nemotron_h_cost.held_experts_cost`) over the `ragged_dot`s' time in a traced decode step."""

from benchmarks import nemotron_h_cost


def read(ctx):
    return nemotron_h_cost.held_experts_roofline(ctx)
