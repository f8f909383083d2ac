"""Roofline share of the decode step's recurrent state update: the traced steps' active sequences' states (`engine.decode_dispatch` spans' `active`, median), float32, read once and written once a recurrent layer (a delta-rule layer's matrix states, a Mamba-2 mixer's, a Mamba-1 mixer's with its rates once), over the time the operations under the kind's state scope took (`kda.state`, `ssm.state`, `ssm1.state`). The configuration's cost module counts a state's elements, named by `answers/<runner>.py`. (Before PR 69: `kda_state_roofline`, `ssm_state_roofline`, `mamba1_state_roofline`.)"""

from benchmarks import costs


def read(ctx):
    return costs.ask(ctx, "state_update_roofline")
