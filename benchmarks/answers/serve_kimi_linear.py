"""What a cell of the `serve_kimi_linear` runner answers to the questions
several configurations share (`costs.py`)."""

from benchmarks import kimi_linear_cost, laguna_cost

ANSWERS = {
    # an expert layer of three matrices of `hidden_size x
    # moe_intermediate_size`, as Laguna's
    "held_experts_roofline": laguna_cost.held_experts_roofline,
    "latent_attention_roofline": kimi_linear_cost.latent_attention_roofline,
    "state_update_roofline": kimi_linear_cost.state_roofline,
    "state_scopes": ("kda.state",),
    "project_scopes": ("kda.project", "kda.conv", "kda.gate", "kda.out"),
    "state_prefill": "kda_prefill",
}
