"""What a cell of the `serve_ouro` runner answers to the questions several
configurations share (`costs.py`)."""

ANSWERS = {"whole_prefill": "loop_prefill"}
