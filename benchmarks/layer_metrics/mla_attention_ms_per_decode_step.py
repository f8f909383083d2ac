"""Self time of the decode program's operations under `mla.attend` (the absorbed attention over the held latent rows), all latent-attention layers (a cell of double layers counts its 2 x `num_layers` attention sublayers), per traced decode step."""

from benchmarks import readers, scope_ops


def read(ctx):
    return scope_ops.ms_per_run(ctx, readers.DECODE_PROGRAM, ("mla.attend",))
