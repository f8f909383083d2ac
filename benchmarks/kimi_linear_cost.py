"""What the sublayers of a configuration with delta-rule linear-attention
layers beside latent-attention layers, and a held share of its experts,
require of the chip in one decode step. The yardstick of
``kda_state_roofline`` and ``mla_attention_roofline`` (and, through
``laguna_cost``, of ``held_experts_roofline`` in this cell); a decode step is
memory bound at these shapes.

Required work counts the published mathematics only, and only bytes that are
moved in the time they are divided by (PERF.md section 6, PR 34: a layer's
projection weights are not its attention's). The state update: the matrix
state of every ACTIVE sequence, float32, read once and written once in every
linear-attention layer; a free slot's state is not required work. Latent
attention: the ``kv_lora_rank + qk_rope_head_dim`` values a position that the
step's active sequences HOLD, read ONCE a layer (the row serves the scores
and the values); the lanes a row is padded to, rows beyond a sequence's
length and free slots are not required work. So a roofline share from these
numbers cannot pass 100%.
"""

from __future__ import annotations

from benchmarks import laguna_cost, peaks, program_spans, readers, scope_ops

STATE_BYTES = 4  # the matrix state is float32
ROW_BYTES = 2  # the latent rows are bfloat16
held_experts_cost = laguna_cost.held_experts_cost  # the same expert layer


def state_update_cost(config: dict, active: float) -> dict:
    """Operations and bytes of ALL linear-attention layers' state update for
    one decode step of ``active`` sequences: ``num_heads`` states of
    ``head_dim x head_dim`` float32 each, read once and written once; the
    decay, k^T S, the rank-one update and S^T q are 2 operations each an
    element."""
    lin = config["linear_attn_config"]
    elements = len(lin["kda_layers"]) * active * lin["num_heads"] \
        * lin["head_dim"] ** 2
    return {"flops": elements * 8, "bytes": elements * 2 * STATE_BYTES}


def latent_attention_cost(config: dict, rows: float) -> dict:
    """Operations and bytes of ALL latent-attention layers' absorbed
    attention for one decode step whose active sequences hold ``rows``
    positions in all (the new token's own among them): a row of
    ``kv_lora_rank + qk_rope_head_dim`` values read once a layer; per head a
    dot product over the whole row and a weighted sum over its latent
    part."""
    layers = len(config["linear_attn_config"]["full_attn_layers"])
    lat, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    return {"flops": layers * rows * config["num_attention_heads"]
            * (2 * (lat + rope) + 2 * lat),
            "bytes": layers * rows * (lat + rope) * ROW_BYTES}


def _share(ctx, cost, scopes):
    took_ms = scope_ops.ms_per_run(ctx, readers.DECODE_PROGRAM, scopes)
    if ctx["cell"]["toy"] or not took_ms:
        return None
    least, _ = peaks.roofline_seconds(cost["flops"], cost["bytes"],
                                      ctx["device"]["kind"])
    return 100.0 * least * 1e3 / took_ms


def _dispatched(ctx, stat):
    """Median ``stat`` of the traced ``engine.decode_dispatch`` spans."""
    return program_spans.read(ctx, program_spans.stat_median,
                              program_spans.DECODE_DISPATCH, stat)


def state_roofline(ctx):
    """The least time for the traced steps' active sequences' states, read
    and written once a layer, over the time of the operations under
    ``kda.state``."""
    active = _dispatched(ctx, "active")
    if not active:
        return None
    return _share(ctx, state_update_cost(ctx["cell"]["config"], active),
                  ("kda.state",))


def latent_attention_roofline(ctx):
    """The least time for the latent rows the traced steps' sequences hold,
    read once a layer, over the time of the operations under
    ``mla.attend``."""
    rows = _dispatched(ctx, "rows")
    if not rows:
        return None
    return _share(ctx, latent_attention_cost(ctx["cell"]["config"], rows),
                  ("mla.attend",))
