"""Self time of the decode program's operations under `cca.project` and `cca.conv` (the latent projections, the two convolutions, the value shift, the state's read and write), per traced decode step."""

from benchmarks import readers, scope_ops


def read(ctx):
    return scope_ops.ms_per_run(ctx, readers.DECODE_PROGRAM,
                                ("cca.project", "cca.conv"))
