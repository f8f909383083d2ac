"""What the sublayers of a configuration of double layers (two latent
attentions, two dense MLPs and one expert layer on a shortcut) require of the
chip in one decode step, and what its expert counters say. The yardstick of
``longcat_mla_attention_roofline`` and ``scmoe_dense_roofline`` (and, through
``laguna_cost``, of ``held_experts_roofline`` in this cell); a decode step is
memory bound at these shapes.

Required work counts the published mathematics only, and only bytes that are
moved in the time they are divided by. Latent attention: the ``kv_lora_rank +
qk_rope_head_dim`` values a position that the step's active sequences HOLD,
read ONCE a SUBLAYER (two a double layer; the row serves the scores and the
values); the lanes a row is padded to, rows beyond a sequence's length and
free slots are not required work. The dense MLPs: their three matrices each,
read once a step whatever the batch; the rows' own operations (32 rows) lie
far under the memory bound and are counted beside them. So a roofline share
from these numbers cannot pass 100%.
"""

from __future__ import annotations

from benchmarks.kimi_linear_cost import _dispatched, _share  # the same

BYTES = 2  # weights and latent rows are bfloat16


def sublayers(config: dict) -> int:
    """Attention sublayers, so layers of the latent cache: two a double
    layer."""
    return 2 * config["num_layers"]


def latent_attention_cost(config: dict, rows: float) -> dict:
    """Operations and bytes of ALL attention sublayers' absorbed attention
    for one decode step whose active sequences hold ``rows`` positions in all
    (the new token's own among them): a row of ``kv_lora_rank +
    qk_rope_head_dim`` values read once a sublayer; per head a dot product
    over the whole row and a weighted sum over its latent part."""
    lat, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    n = sublayers(config)
    return {"flops": n * rows * config["num_attention_heads"]
            * (2 * (lat + rope) + 2 * lat),
            "bytes": n * rows * (lat + rope) * BYTES}


def dense_cost(config: dict, active: float) -> dict:
    """Operations and bytes of ALL the dense SwiGLU MLPs (two a double layer)
    for one decode step of ``active`` sequences: three matrices of
    ``hidden_size x ffn_hidden_size`` each, read once; 2 operations an
    element and row."""
    elements = sublayers(config) * 3 * config["hidden_size"] \
        * config["ffn_hidden_size"]
    return {"flops": 2 * active * elements, "bytes": elements * BYTES}


def latent_attention_roofline(ctx):
    """The least time for the latent rows the traced steps' sequences hold,
    read once a sublayer, over the time of the operations under
    ``mla.attend``."""
    rows = _dispatched(ctx, "rows")
    if not rows or "num_layers" not in ctx["cell"]["config"]:
        return None
    return _share(ctx, latent_attention_cost(ctx["cell"]["config"], rows),
                  ("mla.attend",))


def dense_roofline(ctx):
    """The least time for the dense MLPs' weights, read once, over the time
    of the operations under ``scmoe.dense``."""
    active = _dispatched(ctx, "active")
    if not active or "ffn_hidden_size" not in ctx["cell"]["config"]:
        return None
    return _share(ctx, dense_cost(ctx["cell"]["config"], active),
                  ("scmoe.dense",))


def _moe(ctx, *needed):
    moe = ctx["counters"].get("moe") or {}
    return moe if all(moe.get(k) for k in needed) else None


def zero_share(ctx):
    """% of the window's real rows' choices that fell on zero-compute
    outputs."""
    moe = _moe(ctx, "moe_assignments")
    if not moe or "moe_assignments_zero" not in moe:
        return None
    return 100.0 * moe["moe_assignments_zero"] / moe["moe_assignments"]


def real_experts_max_over_mean(ctx):
    """The most routed (not zero-compute) experts one real row chose in one
    layer, averaged over the window's programs (its decode steps and
    prefills), over the mean a row and layer: how unevenly the tokens' real
    expert work is spread."""
    moe = _moe(ctx, "moe_assignments", "moe_rows", "moe_routed_most")
    engine = ctx["counters"].get("engine") or {}
    programs = engine.get("steps", 0) + engine.get("admitted", 0)
    if not moe or not programs:
        return None
    mean = (moe["moe_assignments"] - moe["moe_assignments_zero"]) / (
        moe["moe_rows"] * moe["layers"])
    return moe["moe_routed_most"] / programs / mean if mean else None


def rows_gathered_per_computed(ctx):
    """Rows the window's programs gathered for their grouped matmuls (k a
    row they computed, a pad row's and a free slot's too: the layout is
    static) over the real rows' assignments that met a held expert's
    weights."""
    moe = _moe(ctx, "moe_rows_gathered", "moe_assignments_held")
    return moe["moe_rows_gathered"] / moe["moe_assignments_held"] \
        if moe else None
