"""What the sublayers of a configuration of Mamba-2 state-space mixers,
attentions and expert layers whose experts work in a latent, with a held
share of them, require of the chip in one decode step. The yardstick of
``ssm_state_roofline`` and ``lmoe_held_experts_roofline``; a decode step is
memory bound at these shapes.

Required work counts the published mathematics only, and only bytes that are
moved in the time they are divided by (PERF.md section 6, PR 34). The state
update: the states of every ACTIVE sequence, float32, read once and written
once in every mixer; a free slot's state is not required work. The held
experts: the TWO matrices of ``moe_latent_size x moe_intermediate_size`` of
each held expert that at least one real row REACHED, read once a layer
(``laguna_cost.held_experts_cost`` counts three of ``hidden_size x
moe_intermediate_size``, six times what exists here). The mixers'
projections: the input and output matrices, read once a layer whatever the
batch. So a roofline share from these numbers cannot pass 100%.
"""

from __future__ import annotations

from benchmarks import laguna_cost, moe_cost, program_spans, readers, scope_ops

STATE_BYTES = 4  # the state is float32
BYTES = 2  # weights are bfloat16
MIXER = "M"  # a mixer's letter in ``hybrid_override_pattern``


def mixers(config: dict) -> int:
    return config["hybrid_override_pattern"].count(MIXER)


def state_update_cost(config: dict, active: float) -> dict:
    """Operations and bytes of ALL mixers' state update for one decode step
    of ``active`` sequences: ``mamba_num_heads`` states of ``mamba_head_dim x
    ssm_state_size`` float32 each, read once and written once; the decay,
    the rank-one term and the read-out are 2 operations each an element."""
    elements = mixers(config) * active * config["mamba_num_heads"] \
        * config["mamba_head_dim"] * config["ssm_state_size"]
    return {"flops": elements * 6, "bytes": elements * 2 * STATE_BYTES}


def held_experts_cost(config: dict, reached: float) -> dict:
    """Bytes of one decode step's grouped matmuls when its real rows reach
    ``reached`` held experts, summed over the expert layers: two matrices of
    ``moe_latent_size x moe_intermediate_size`` each. The rows' own
    operations (a few hundred rows) are far under the memory bound and left
    at zero."""
    return {"flops": 0.0, "bytes": reached * 2 * config["moe_latent_size"]
            * config["moe_intermediate_size"] * BYTES}


def projection_cost(config: dict, active: float) -> dict:
    """Operations and bytes of ALL mixers' input and output projections for
    one decode step of ``active`` sequences: ``hidden_size`` -> gate,
    convolution channels and steps, and ``mamba_num_heads x mamba_head_dim``
    -> ``hidden_size``, each matrix read once a layer."""
    inner = config["mamba_num_heads"] * config["mamba_head_dim"]
    columns = 2 * inner + 2 * config["n_groups"] * config["ssm_state_size"] \
        + config["mamba_num_heads"]
    weights = mixers(config) * config["hidden_size"] * (columns + inner)
    return {"flops": 2 * active * weights, "bytes": weights * BYTES}


def state_roofline(ctx):
    """The least time for the traced steps' active sequences' states (the
    ``engine.decode_dispatch`` spans' median ``active``), read and written
    once a mixer, over the time of the operations under ``ssm.state``."""
    active = program_spans.read(
        ctx, program_spans.stat_median, program_spans.DECODE_DISPATCH,
        "active")
    if not active or "ssm_state_size" not in ctx["cell"]["config"]:
        return None
    return laguna_cost._share(
        ctx, state_update_cost(ctx["cell"]["config"], active),
        scope_ops.ms_per_run(ctx, readers.DECODE_PROGRAM, ("ssm.state",)))


def held_experts_roofline(ctx):
    """The least time for the weights of the held experts a step reached
    (the window's ``moe_experts_reached`` over its decode steps) over the time
    the grouped matmuls took in a traced decode step."""
    moe = ctx["counters"].get("moe") or {}
    steps = (ctx["counters"].get("engine") or {}).get("steps")
    if not moe.get("moe_experts_reached") or not steps \
            or "moe_latent_size" not in ctx["cell"]["config"]:
        return None
    return laguna_cost._share(
        ctx, held_experts_cost(ctx["cell"]["config"],
                               moe["moe_experts_reached"] / steps),
        moe_cost.expert_ms_per_run(ctx, readers.DECODE_PROGRAM))
