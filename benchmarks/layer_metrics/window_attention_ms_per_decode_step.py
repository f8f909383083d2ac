"""Self time of the decode program's operations under `attn.window` (a window layer's projections, RoPE, the ring's row write, attention over the ring, the gate, `wo`), all window layers, per traced decode step."""

from benchmarks import readers, scope_ops


def read(ctx):
    return scope_ops.ms_per_run(ctx, readers.DECODE_PROGRAM, ("attn.window",))
