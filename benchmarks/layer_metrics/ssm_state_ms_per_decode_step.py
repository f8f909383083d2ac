"""Self time of the decode program's operations under `ssm.state` (the states' decay, rank-one update and read-out: every active sequence's states read and rewritten, `D x`), all state-space mixers, per traced decode step."""

from benchmarks import readers, scope_ops


def read(ctx):
    return scope_ops.ms_per_run(ctx, readers.DECODE_PROGRAM, ("ssm.state",))
