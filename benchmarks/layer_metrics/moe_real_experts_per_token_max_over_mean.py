"""The most routed (not zero-compute) experts one real row chose in one layer, a program (`moe_routed_most` over the window's decode steps and prefills), over the mean a row and layer: tokens of this architecture cost different amounts of expert work."""

from benchmarks import longcat_cost


def read(ctx):
    return longcat_cost.real_experts_max_over_mean(ctx)
