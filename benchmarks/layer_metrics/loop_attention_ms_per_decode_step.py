"""Self time of the decode program's operations under `attend_cached` (`decode_attention` over the held rows of cache layer t * layers + i), all passes' layers, per traced decode step of a looped model."""

from benchmarks import ouro_cost


def read(ctx):
    return ouro_cost.attention_ms(ctx)
