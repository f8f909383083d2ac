"""Device self time of the operations under the `kda.*` scopes (the chunked scan `kda.prefill_scan`, the projections, convolutions and gates) in ONE warmed prefill of the cell's bucket, from the profiler capture a traced run makes of that one call before its window (the window's trace lies inside a decode phase and holds no prefill)."""


def read(ctx):
    return (ctx["counters"].get("kda_prefill") or {}).get("ms_per_req")
