"""Self time of the decode program's operations under `attn.gqa` (an attention layer's projections, the slot's row write, attention over the slots' held rows, `wo`; no rotation), all attention layers, per traced decode step."""

from benchmarks import readers, scope_ops


def read(ctx):
    return scope_ops.ms_per_run(ctx, readers.DECODE_PROGRAM, ("attn.gqa",))
