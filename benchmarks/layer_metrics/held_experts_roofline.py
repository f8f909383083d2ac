"""Roofline share of the decode step's grouped expert matmuls: the weights of the held experts a step's real rows reached (`moe_experts_reached` over the window's steps) over the grouped matmuls' time in a traced decode step. What a held expert weighs (three matrices of `hidden_size x moe_intermediate_size`, or TWO of `moe_latent_size x moe_intermediate_size` where experts work in a latent) is the configuration's cost module's to count, named by `answers/<runner>.py`."""

from benchmarks import costs


def read(ctx):
    return costs.ask(ctx, "held_experts_roofline")
