"""Self time of the decode program's operations under `cca.attend` (the normalisation, RoPE, the row write, attention over the latent cache, `wo`), per traced decode step."""

from benchmarks import readers, scope_ops


def read(ctx):
    return scope_ops.ms_per_run(ctx, readers.DECODE_PROGRAM, ("cca.attend",))
