"""What a cell of the `serve_jamba` runner answers to the questions several
configurations share (`costs.py`)."""

from benchmarks import jamba_cost

ANSWERS = {
    "state_update_roofline": jamba_cost.state_roofline,
    "state_scopes": ("ssm1.state",),
    "project_scopes": ("ssm1.project", "ssm1.conv", "ssm1.gate", "ssm1.out"),
    "whole_prefill": "mamba1_prefill",
}
