"""Roofline share of a prefill's selective scan: the captured prompt's real rows' `dt`, `dt x`, B, C in and read-out out, float32, the rates and the state once a mixer, over the time under `ssm1.prefill_scan` in the capture of ONE warmed prefill. Of the HBM bound: `peaks.py` has no published peak for the vector unit, which binds this kernel, so the share reads well under 100 and never over (`jamba_cost.py`)."""

from benchmarks import jamba_cost


def read(ctx):
    return jamba_cost.prefill_scan_roofline(ctx)
