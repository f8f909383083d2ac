"""Self time of the decode program's operations under `attn.full` (a full layer's projections, RoPE, the slot's row write, attention over the slots at full length, the gate, `wo`), all full layers, per traced decode step."""

from benchmarks import readers, scope_ops


def read(ctx):
    return scope_ops.ms_per_run(ctx, readers.DECODE_PROGRAM, ("attn.full",))
