"""Self time of the decode program's operations under the recurrent kind's state scope (`kda.state`, `ssm.state`, `ssm1.state`: every active sequence's states decayed, one rank-one term added, read out; the stack read once and written once), all recurrent layers, per traced decode step. (Before PR 69: `kda_`, `ssm_` and `mamba1_state_ms_per_decode_step`.)"""

from benchmarks import costs


def read(ctx):
    return costs.scopes_ms(ctx, "state_scopes")
