"""Roofline share of the two-width flash forward kernel in ONE warmed prefill of the cell's bucket (the full layers' causal attention from position 0: keys of 192, values of 128, 16 query heads a KV head), from the profiler capture a traced run makes of that one call before its window: the causal half of the two products over the kernels' device time."""

from benchmarks import mimo_cost


def read(ctx):
    return mimo_cost.prefill_attention_roofline(ctx)
