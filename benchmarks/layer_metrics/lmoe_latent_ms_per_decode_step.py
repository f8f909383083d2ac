"""Self time of the decode program's operations under `lmoe.down` and `lmoe.up` (the projection of the normed stream into the latent the experts work in, and their weighted sum's way back), all expert layers, per traced decode step."""

from benchmarks import readers, scope_ops


def read(ctx):
    return scope_ops.ms_per_run(ctx, readers.DECODE_PROGRAM,
                                ("lmoe.down", "lmoe.up"))
