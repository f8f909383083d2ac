"""Device self time of the operations under `ssm1.prefill_scan` (the selective scan's kernel, the rates, `dt x`, B and C spread for it, `D x`), all Mamba-1 mixers, in ONE warmed prefill of the cell's bucket, from the profiler capture a traced run makes of that one call before its window (the window's trace lies inside a decode phase and holds no prefill)."""

from benchmarks import jamba_cost


def read(ctx):
    return jamba_cost.prefill_scan_ms(ctx)
