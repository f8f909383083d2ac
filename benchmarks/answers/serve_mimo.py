"""What a cell of the `serve_mimo` runner answers to the questions several
configurations share (`costs.py`)."""

from benchmarks import laguna_cost, mimo_cost

ANSWERS = {
    # an expert layer of three matrices of `hidden_size x
    # moe_intermediate_size`, as Laguna's
    "held_experts_roofline": laguna_cost.held_experts_roofline,
    "decode_attention_roofline": mimo_cost.decode_attention_roofline,
    "whole_prefill": "mimo_prefill",
}
