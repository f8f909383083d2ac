"""Self time of the decode program's operations under `ssm1.project`, `ssm1.conv`, `ssm1.gate` and `ssm1.out` (`W_in`, the convolution with its window, `W_x` with the three small norms, `W_dt` and the softplus, the gate, `W_out`), all Mamba-1 mixers, per traced decode step."""

from benchmarks import readers, scope_ops


def read(ctx):
    return scope_ops.ms_per_run(
        ctx, readers.DECODE_PROGRAM,
        ("ssm1.project", "ssm1.conv", "ssm1.gate", "ssm1.out"))
