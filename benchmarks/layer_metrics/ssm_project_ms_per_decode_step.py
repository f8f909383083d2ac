"""Self time of the decode program's operations under `ssm.project`, `ssm.conv`, `ssm.norm` and `ssm.out` (the input projection, the convolution with its window, the gate and the norm a group, the output projection), all state-space mixers, per traced decode step."""

from benchmarks import readers, scope_ops


def read(ctx):
    return scope_ops.ms_per_run(
        ctx, readers.DECODE_PROGRAM,
        ("ssm.project", "ssm.conv", "ssm.norm", "ssm.out"))
