"""Self time of the decode program's operations under `kda.state` (the matrix states' decay, rank-one update and read-out: every active sequence's state read and rewritten), all linear-attention layers, per traced decode step."""

from benchmarks import readers, scope_ops


def read(ctx):
    return scope_ops.ms_per_run(ctx, readers.DECODE_PROGRAM, ("kda.state",))
