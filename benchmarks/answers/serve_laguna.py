"""What a cell of the `serve_laguna` runner answers to the questions several
configurations share (`costs.py`)."""

from benchmarks import laguna_cost

ANSWERS = {
    "held_experts_roofline": laguna_cost.held_experts_roofline,
    "decode_attention_roofline": laguna_cost.attention_roofline,
}
