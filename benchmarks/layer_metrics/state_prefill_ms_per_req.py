"""Device self time of the operations under the recurrent kind's scopes (`kda.*`, `ssm.*`: the chunked scan `prefill_scan`, the projections, convolutions, gates and norms) in ONE warmed prefill of the cell's bucket, from the profiler capture a traced run makes of that one call before its window; the runner's record holds it under the key `answers/<runner>.py` names. (Before PR 69: `kda_` and `ssm_prefill_ms_per_req`.)"""

from benchmarks import costs


def read(ctx):
    return costs.captured_ms(ctx, "state_prefill")
