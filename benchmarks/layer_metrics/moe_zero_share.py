"""% of the window's real rows' router choices that fell on zero-compute outputs (`moe_assignments_zero / moe_assignments`): a third when routing is even over 512 routed and 256 zero-compute outputs; such a choice costs one multiply a token."""

from benchmarks import longcat_cost


def read(ctx):
    return longcat_cost.zero_share(ctx)
