"""Device time of ONE warmed prefill of the cell's bucket, whole (6,000 tokens through the 8,192 bucket: 26 selective scans, their projections, 2 flash forwards of 20 heads on one KV head, 28 MLPs, the head): what an admit phase is 16 of. From the profiler capture a traced run makes of that one call before its window."""


def read(ctx):
    return (ctx["counters"].get("mamba1_prefill") or {}).get("ms_per_req")
