"""What a cell of the `serve_nemotron_h` runner answers to the questions
several configurations share (`costs.py`)."""

from benchmarks import nemotron_h_cost

ANSWERS = {
    "held_experts_roofline": nemotron_h_cost.held_experts_roofline,
    "state_update_roofline": nemotron_h_cost.state_roofline,
    "state_scopes": ("ssm.state",),
    "project_scopes": ("ssm.project", "ssm.conv", "ssm.norm", "ssm.out"),
    "state_prefill": "ssm_prefill",
}
