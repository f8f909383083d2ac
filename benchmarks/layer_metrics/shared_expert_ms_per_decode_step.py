"""Self time of the decode program's operations under `moe.shared` (the shared expert's SwiGLU), all sparse layers, per traced decode step."""

from benchmarks import readers, scope_ops


def read(ctx):
    return scope_ops.ms_per_run(ctx, readers.DECODE_PROGRAM, ("moe.shared",))
