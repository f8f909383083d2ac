"""Device time of a traced decode step outside `lm_head` and `sample`: the 192 layer calls of a looped model's four passes (each pass reads the ONE stack's weights again) and the four pass ends (the final norm). The decode program's device time per run less the operations under the two scopes."""

from benchmarks import ouro_cost


def read(ctx):
    return ouro_cost.layers_ms(ctx)
