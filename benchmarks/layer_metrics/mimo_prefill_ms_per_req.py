"""Device time of ONE warmed prefill of the cell's bucket (6,000 tokens through the 8,192 bucket: the band attention of 9 window layers, the flash forward of 2 full layers, the dense MLP and the expert layers 1,024 rows at a time), from the profiler capture a traced run makes of that one call before its window (the window's trace lies inside a decode phase and holds no prefill)."""


def read(ctx):
    return (ctx["counters"].get("mimo_prefill") or {}).get("ms_per_req")
