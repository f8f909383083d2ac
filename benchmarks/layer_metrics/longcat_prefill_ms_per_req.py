"""Device time of ONE warmed prefill of the cell's bucket (3,000 tokens through the 4,096 bucket: the blocked attention of 8 sublayers, the dense MLPs, the expert layer over 12 gathered rows a token), from the profiler capture a traced run makes of that one call before its window (the window's trace lies inside a decode phase and holds no prefill)."""


def read(ctx):
    return (ctx["counters"].get("longcat_prefill") or {}).get("ms_per_req")
