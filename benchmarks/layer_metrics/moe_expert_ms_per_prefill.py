"""Self time of the grouped expert matmuls inside the prefill programs, per traced prefill."""

from benchmarks import moe_cost, readers


def read(ctx):
    return moe_cost.expert_ms_per_run(ctx, readers.PREFILL_PROGRAM)
