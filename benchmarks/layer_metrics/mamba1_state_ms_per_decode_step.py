"""Self time of the decode program's operations under `ssm1.state` (every active sequence's states decayed by channel and state index, the decay formed inside the kernel, one rank-one term added, read out, `D x`: the stack read once and written once), all Mamba-1 mixers, per traced decode step."""

from benchmarks import readers, scope_ops


def read(ctx):
    return scope_ops.ms_per_run(ctx, readers.DECODE_PROGRAM, ("ssm1.state",))
