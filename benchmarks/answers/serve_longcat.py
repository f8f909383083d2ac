"""What a cell of the `serve_longcat` runner answers to the questions several
configurations share (`costs.py`)."""

from benchmarks import laguna_cost, longcat_cost

ANSWERS = {
    # an expert layer of three matrices of `hidden_size x
    # moe_intermediate_size`, as Laguna's
    "held_experts_roofline": laguna_cost.held_experts_roofline,
    "latent_attention_roofline": longcat_cost.latent_attention_roofline,
    "whole_prefill": "longcat_prefill",
}
