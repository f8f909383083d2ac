"""Self time of the decode program's operations under `moe_router` (the linear router over all the published experts, the softmax, the top-k and its renormalisation), all sparse layers, per traced decode step."""

from benchmarks import readers, scope_ops


def read(ctx):
    return scope_ops.ms_per_run(ctx, readers.DECODE_PROGRAM, ("moe_router",))
