"""What a cell of the `serve_solar_open2` runner answers to the questions
several configurations share (`costs.py`)."""

from benchmarks import laguna_cost, solar_open2_cost

ANSWERS = {
    # an expert layer of three matrices of `hidden_size x
    # moe_intermediate_size`, as Laguna's
    "held_experts_roofline": laguna_cost.held_experts_roofline,
    # `kimi_linear_cost.state_roofline` over the layers that are no
    # `gqa_layers` (joined in PR 69)
    "state_update_roofline": solar_open2_cost.state_roofline,
    "state_scopes": ("kda.state",),
    "project_scopes": ("kda.project", "kda.conv", "kda.gate", "kda.out"),
    "state_prefill": "kda_prefill",
}
