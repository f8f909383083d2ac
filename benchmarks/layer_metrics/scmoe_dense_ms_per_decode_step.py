"""Self time of the decode program's operations under `scmoe.dense` (a double layer's two dense SwiGLU MLPs), all double layers, per traced decode step."""

from benchmarks import readers, scope_ops


def read(ctx):
    return scope_ops.ms_per_run(ctx, readers.DECODE_PROGRAM, ("scmoe.dense",))
