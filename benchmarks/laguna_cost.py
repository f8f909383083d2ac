"""What the sublayers of a configuration with window layers beside full ones,
and a held share of its experts, require of the chip in one decode step. The
yardstick of ``window_attention_roofline``, ``full_attention_roofline`` and
``held_experts_roofline``; a decode step is memory bound at these shapes.

Required work counts the published mathematics only. Attention of one kind:
the K and V rows the step's active sequences HOLD in that kind of layer, read
once: every position in a full layer, at most ``sliding_window`` in a window
layer. The program reads every slot's ``max_len`` rows (the whole ring) under
a mask; rows beyond a sequence's length and free slots are not required work.
The layers' projection weights are NOT counted, although the scope that is
timed runs the projections: the compiled step moves part of them into the
chip's fast memory with asynchronous copies that no scope names (my chip
runs, PR 34: with them counted the window layers read 94% of the peak), so
their bytes are not all moved inside the time they would be divided by.
Experts: the weights of the held experts that at least one real row REACHED,
read once a layer. So a roofline share from these numbers cannot pass 100%.
"""

from __future__ import annotations

from benchmarks import moe_cost, peaks, program_spans, readers, scope_ops

BYTES = 2  # weights and cache are bfloat16
KIND = {"full": "full_attention", "window": "sliding_attention"}
SCOPE = {"full": "attn.full", "window": "attn.window"}


def layers_of(config: dict, kind: str) -> list:
    """Indices of the configuration's layers of ``kind`` that run."""
    return [l for l, t in enumerate(
        config["layer_types"][:config["num_hidden_layers"]])
        if t == KIND[kind]]


def decode_attention_cost(config: dict, kind: str, rows: float) -> dict:
    """Operations and bytes of ALL of ``kind``'s layers' attention for one
    decode step whose active sequences hold ``rows`` positions in such a
    layer in all (the new token's own among them): k and v of
    ``num_key_value_heads x head_dim`` each, read once a layer; a dot product
    and a weighted sum of ``head_dim`` terms per query head and position."""
    d, kv = config["head_dim"], config["num_key_value_heads"]
    flops = bytes_ = 0
    for l in layers_of(config, kind):
        bytes_ += rows * 2 * kv * d * BYTES
        flops += rows * config["num_attention_heads_per_layer"][l] * d * 2 * 2
    return {"flops": flops, "bytes": bytes_}


def held_experts_cost(config: dict, reached: float) -> dict:
    """Bytes of one decode step's grouped matmuls when its real rows reach
    ``reached`` held experts, summed over the sparse layers: three matrices
    of ``hidden_size x moe_intermediate_size`` each. The rows' own operations
    (a few hundred rows) are far under the memory bound and left at zero."""
    return {"flops": 0.0, "bytes": reached * 3 * config["hidden_size"]
            * config["moe_intermediate_size"] * BYTES}


def _share(ctx, cost, took_ms):
    if ctx["cell"]["toy"] or not took_ms:
        return None
    least, _ = peaks.roofline_seconds(cost["flops"], cost["bytes"],
                                      ctx["device"]["kind"])
    return 100.0 * least * 1e3 / took_ms


def attention_roofline(ctx, kind: str):
    """The least time the chip could take for one traced decode step's
    attention of ``kind`` over the time the operations under its scope took.
    The rows are the traced steps' (`engine.decode_dispatch` spans' median
    ``rows`` for full layers, ``window_rows`` for window layers)."""
    rows = program_spans.read(
        ctx, program_spans.stat_median, program_spans.DECODE_DISPATCH,
        "rows" if kind == "full" else "window_rows")
    if not rows:
        return None
    return _share(ctx, decode_attention_cost(ctx["cell"]["config"], kind, rows),
                  scope_ops.ms_per_run(ctx, readers.DECODE_PROGRAM,
                                       (SCOPE[kind],)))


def held_experts_roofline(ctx):
    """The least time for the weights of the held experts a step reached
    (the window's ``moe_experts_reached`` over its decode steps) over the time
    the grouped matmuls took in a traced decode step."""
    moe = ctx["counters"].get("moe") or {}
    steps = (ctx["counters"].get("engine") or {}).get("steps")
    if not moe.get("moe_experts_reached") or not steps:
        return None
    return _share(
        ctx, held_experts_cost(ctx["cell"]["config"],
                               moe["moe_experts_reached"] / steps),
        moe_cost.expert_ms_per_run(ctx, readers.DECODE_PROGRAM))
