"""Self time of the decode program's operations under `mla.project` and `mla.rotate` (the query's and the latent's projections and norms, the row's write into the cache, the absorption of the key expansion into the query; where the configuration rotates, the rotation of the query's and the shared key's rope part), all latent-attention sublayers, per traced decode step."""

from benchmarks import readers, scope_ops


def read(ctx):
    return scope_ops.ms_per_run(ctx, readers.DECODE_PROGRAM,
                                ("mla.project", "mla.rotate"))
