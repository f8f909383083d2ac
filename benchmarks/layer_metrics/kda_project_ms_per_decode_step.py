"""Self time of the decode program's operations under `kda.project`, `kda.conv`, `kda.gate` and `kda.out` (the q, k, v projections, the three convolutions with their windows, the decay, step and output gates, the head norm and `wo`), all linear-attention layers, per traced decode step."""

from benchmarks import readers, scope_ops


def read(ctx):
    return scope_ops.ms_per_run(
        ctx, readers.DECODE_PROGRAM,
        ("kda.project", "kda.conv", "kda.gate", "kda.out"))
