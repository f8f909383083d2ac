"""Self time of the decode program's operations under `mla.attend` (the absorbed attention over the held latent rows, 64 heads against rows of 576), all 2 x `num_layers` attention sublayers, per traced decode step. Named in full: the cell of double layers counts its cache layers by sublayer."""

from benchmarks import readers, scope_ops


def read(ctx):
    if "num_layers" not in ctx["cell"]["config"]:
        return None
    return scope_ops.ms_per_run(ctx, readers.DECODE_PROGRAM, ("mla.attend",))
