"""Device self time of the operations under the `ssm.*` scopes (the chunked scan `ssm.prefill_scan`, the projections, the convolution, the gate and norm) in ONE warmed prefill of the cell's bucket, from the profiler capture a traced run makes of that one call before its window (the window's trace lies inside a decode phase and holds no prefill)."""


def read(ctx):
    return (ctx["counters"].get("ssm_prefill") or {}).get("ms_per_req")
