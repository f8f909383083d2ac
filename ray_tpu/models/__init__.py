"""Model zoo for the TPU framework (flagship: Llama-family decoder LM).

The reference has no native models (SURVEY.md §2.4 — Train/Serve wrap
torch/vLLM); here models are in-framework so Train/Serve/bench drive one
code path. One record configures every model (`TransformerConfig`) and one
table says which module runs it (`families.py`), each family's imported
where a configuration first asks for it.
"""

from ray_tpu.models.transformer import (
    PRESETS,
    TransformerConfig,
    config,
    forward,
    init_params,
    loss_fn,
    param_axes,
    trainable_mask,
)

__all__ = [
    "PRESETS",
    "TransformerConfig",
    "config",
    "forward",
    "init_params",
    "loss_fn",
    "param_axes",
    "trainable_mask",
]
