"""Device time of ONE warmed prefill of the cell's bucket, whole (192 tokens through the 256 bucket: four passes of 48 layers, a flash forward each, the pass ends, the head): what an admit phase is 8 of. From the profiler capture a traced run makes of that one call before its window."""

from benchmarks import ouro_cost


def read(ctx):
    return ouro_cost.prefill_ms(ctx)
