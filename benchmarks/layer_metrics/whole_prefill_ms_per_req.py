"""Device time of ONE warmed prefill of the cell's bucket, whole (the traffic's first `warmup_prompt_tokens` through its bucket: every layer, the head): an admit phase is one of these a client. From the profiler capture a traced run makes of that one call before its window (the window's trace lies inside a decode phase and holds no prefill); the runner's record holds it under the key `answers/<runner>.py` names. (Before PR 69: `longcat_`, `mimo_`, `mamba1_` and `loop_prefill_ms_per_req`.)"""

from benchmarks import costs


def read(ctx):
    return costs.captured_ms(ctx, "whole_prefill")
