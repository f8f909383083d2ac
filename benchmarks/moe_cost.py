"""What the sparse expert layer of a configuration requires of the chip, and
where its time is in a reduced trace. The yardstick of the ``moe_*`` metrics.

Required work counts the published mathematics only: every REAL token runs
``num_experts_per_tok`` experts of three matmuls each. Rows that pad a prompt
to its bucket are computed by the program and are not required work, so a
roofline share from these numbers cannot pass 100%.
"""

from __future__ import annotations

import re

from benchmarks import peaks, readers

# The three grouped matmuls of models/transformer.py::moe_dropless reach the
# device trace under the HLO names XLA gives a `lax.ragged_dot`: on the TPU
# `ragged-dot-none[.N]` (tpu_custom_call/7in/1out) and one
# `ragged-dot-metadata` a layer. Selected by program and operation name
# (trace_reduce keys op_self_s that way), never by a tpu_custom_call tag
# alone: tags say nothing of the program, and the flash kernels have them too.
EXPERT_OP = re.compile(r"ragged[-_]dot", re.IGNORECASE)


def expert_ffn_cost(config: dict, real_tokens: float) -> dict:
    """Operations and bytes the expert FFNs of ALL layers require for one
    prefill of ``real_tokens`` tokens: 3 matmuls x 2 x hidden x expert width
    for each of the token's experts; every expert's weights read once per
    layer (a 2048-bucket prompt reaches all 64), the routed rows read once
    and written once in bfloat16."""
    h, m = config["hidden_size"], config["intermediate_size"]
    k, e = config["num_experts_per_tok"], config["num_experts"]
    layers = config["num_hidden_layers"]
    rows = real_tokens * k
    return {"flops": layers * 3 * 2 * h * m * rows,
            "bytes": layers * (3 * e * h * m * 2 + 2 * rows * h * 2)}


def expert_seconds(ctx, program_pattern: str):
    """Self seconds of the expert matmuls inside the programs whose name
    holds ``program_pattern``, over the traced window; None where the trace
    has no such operation (a dense model, the parent of the PR that added
    the layer, a CPU whose trace names them otherwise)."""
    trace = ctx["trace"]
    if not trace:
        return None
    total = 0.0
    for name, seconds in trace.get("op_self_s", {}).items():
        program, _, op = name.rpartition("/")  # no program: not ours
        if program_pattern in program and EXPERT_OP.search(op):
            total += seconds
    return total or None


def expert_ms_per_run(ctx, program_pattern: str):
    seconds = expert_seconds(ctx, program_pattern)
    program = readers.program(ctx, program_pattern)
    if not seconds or not program:
        return None
    return seconds * 1e3 / program["count"]


def experts_roofline(ctx, mean_prompt_tokens):
    """The least time the chip could take for the expert FFNs of one traced
    prefill (the larger of the compute and the memory bound), over the time
    its expert matmuls took."""
    took_ms = expert_ms_per_run(ctx, readers.PREFILL_PROGRAM)
    if ctx["cell"]["toy"] or not took_ms or not mean_prompt_tokens:
        return None
    cost = expert_ffn_cost(ctx["cell"]["config"], mean_prompt_tokens)
    least, _ = peaks.roofline_seconds(cost["flops"], cost["bytes"],
                                      ctx["device"]["kind"])
    return 100.0 * least * 1e3 / took_ms
