"""Self time of the decode program's operations under `zaya.router` (the projection, the carried sum, the MLP, the softmax and the choice), per traced decode step."""

from benchmarks import readers, scope_ops


def read(ctx):
    return scope_ops.ms_per_run(ctx, readers.DECODE_PROGRAM, ("zaya.router",))
