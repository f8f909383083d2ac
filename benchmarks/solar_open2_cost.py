"""What the grouped-attention layers of a configuration with delta-rule
layers beside K/V rows require of the chip in one decode step. The yardstick
of ``gqa_attention_roofline``; the held experts keep the yardstick they have
(``laguna_cost.held_experts_cost``), and so do the delta-rule layers' states
(``kimi_linear_cost.state_update_cost``, which counts
``linear_attn_config.kda_layers``: a key this configuration does not publish
and its file, which holds the published group whole, does not add, so
``with_kda_layers`` hands that arithmetic the layers that are no
``gqa_layers``; since PR 69, ``state_update_roofline`` in this cell).
A decode step is memory bound at these shapes.

Required work counts the published mathematics only, and only bytes that are
moved in the time they are divided by (PERF.md section 6, PR 34: a layer's
projection weights, the gate's matrix among them, are not its attention's:
``gqa.project`` has them). Grouped attention: the K and the V row of every
position that the step's active sequences HOLD (the new token's own among
them), ``num_key_value_heads x head_dim`` bfloat16 values each, read ONCE a
layer: 4 KiB a position a layer at 8 heads of 128. Rows beyond a sequence's
length, the granule a slot's last rows are copied in and free slots are not
required work, so a roofline share from these numbers cannot pass 100%.
"""

from __future__ import annotations

from benchmarks import (kimi_linear_cost, laguna_cost, program_spans,
                        readers, scope_ops)

ROW_BYTES = 2  # the K/V rows are bfloat16


def gqa_layers(config: dict) -> int:
    """The grouped-attention layers the file's depth holds."""
    return sum(layer < config["num_hidden_layers"]
               for layer in config["gqa_layers"])


def position_bytes(config: dict) -> int:
    """A position's K and V rows in ONE layer."""
    return 2 * config["num_key_value_heads"] * config["head_dim"] * ROW_BYTES


def gqa_attention_cost(config: dict, rows: float) -> dict:
    """Operations and bytes of ALL grouped-attention layers' attention for
    one decode step whose active sequences hold ``rows`` positions in all:
    every held position's K and V read once a layer; per query head a dot
    product over the head and a weighted sum over it."""
    layers = gqa_layers(config)
    return {"flops": layers * rows * config["num_attention_heads"]
            * 4 * config["head_dim"],
            "bytes": layers * rows * position_bytes(config)}


def attention_ms(ctx):
    return scope_ops.ms_per_run(ctx, readers.DECODE_PROGRAM, ("gqa.attend",))


def attention_roofline(ctx):
    """The least time for the K/V rows the traced steps' sequences hold
    (`engine.decode_dispatch` spans' median ``rows``), read once a layer,
    over the time of the operations under ``gqa.attend``."""
    rows = program_spans.read(ctx, program_spans.stat_median,
                              program_spans.DECODE_DISPATCH, "rows")
    if not rows or "gqa_layers" not in ctx["cell"]["config"]:
        return None
    return laguna_cost._share(
        ctx, gqa_attention_cost(ctx["cell"]["config"], rows),
        attention_ms(ctx))


def with_kda_layers(config: dict) -> dict:
    """The configuration as ``kimi_linear_cost`` reads one: the layers under
    the file's depth that ``gqa_layers`` does not name are the delta-rule
    layers (6 of the 8 kept), listed where a ``kimi_linear`` file lists
    them. Nothing else is touched, and the file is not."""
    kda = [layer for layer in range(config["num_hidden_layers"])
           if layer not in config["gqa_layers"]]
    return dict(config, linear_attn_config=dict(
        config["linear_attn_config"], kda_layers=kda))


def state_roofline(ctx):
    """``kimi_linear_cost.state_roofline`` over this configuration's
    delta-rule layers: the same count an element and layer, the same scope
    (``kda.state``), the same spans."""
    if "gqa_layers" not in ctx["cell"]["config"]:
        return None
    cell = dict(ctx["cell"], config=with_kda_layers(ctx["cell"]["config"]))
    return kimi_linear_cost.state_roofline(dict(ctx, cell=cell))
