"""Self time of the grouped expert matmuls inside the decode program, per traced decode step."""

from benchmarks import moe_cost, readers


def read(ctx):
    return moe_cost.expert_ms_per_run(ctx, readers.DECODE_PROGRAM)
