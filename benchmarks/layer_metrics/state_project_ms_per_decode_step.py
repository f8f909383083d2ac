"""Self time of the decode program's operations under the recurrent kind's projection, convolution, gate and output scopes (`kda.project` / `.conv` / `.gate` / `.out`; `ssm.project` / `.conv` / `.norm` / `.out`; `ssm1.project` / `.conv` / `.gate` / `.out`: `answers/<runner>.py`), all recurrent layers, per traced decode step. (Before PR 69: `kda_`, `ssm_` and `mamba1_project_ms_per_decode_step`.)"""

from benchmarks import costs


def read(ctx):
    return costs.scopes_ms(ctx, "project_scopes")
