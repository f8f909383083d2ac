"""Rows the window's programs gathered for their grouped expert matmuls (`moe_rows_gathered`: k rows a row computed, whatever it chose) over the real rows' assignments that met a held expert's weights (`moe_assignments_held`): what the static layout costs where most choices are absent or zero-compute."""

from benchmarks import longcat_cost


def read(ctx):
    return longcat_cost.rows_gathered_per_computed(ctx)
