"""Self time of the decode program's operations under `gqa.project` (a gated grouped-attention layer's q, k and v projections AND its output gate's: where the gate's 33.6M-parameter matrix is read), all such layers, per traced decode step."""

from benchmarks import readers, scope_ops


def read(ctx):
    return scope_ops.ms_per_run(ctx, readers.DECODE_PROGRAM, ("gqa.project",))
