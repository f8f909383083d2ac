"""What a looped configuration (``model_type`` ouro) requires of the chip: the
bytes a decode step has to read and a prefill's operations. The yardstick of
``loop_layers_roofline``, ``loop_attention_roofline`` and
``loop_prefill_roofline``.

Required work counts the published mathematics only, and only bytes that are
moved in the time they are divided by (PERF.md section 6, PR 34). A decode
step: the ONE stack's weights once a PASS (``total_ut_steps`` times a step,
whatever the batch: nothing of a pass's read is left in fast memory for the
next, 103 MB a layer against 128 MiB of it in all), every held row of
``total_ut_steps x num_hidden_layers`` cache layers once (K and V, the step's
own rows written besides), and the head's matrix once. The memory binds all
three. A prefill: the matrix products of the prompt's REAL rows (a bucket's
pads are computed and not required) through every layer of every pass, its
causal attention, and the head at the ONE position whose logits are read,
beside the weights' read once a pass: ``peaks.roofline_seconds`` takes the
larger bound, and at the cell's 192 real rows that is the memory's (the
weights four times, 24 ms, outlast 3.8e12 operations' 19 ms; from about 250
rows on the matrix unit binds).
"""

from __future__ import annotations

from benchmarks import (harness, laguna_cost, program_spans, readers,
                        scope_ops)

BYTES = 2  # weights and cache rows are bfloat16


def passes(config: dict) -> int:
    return int(config["total_ut_steps"])


def cache_layers(config: dict) -> int:
    """K/V layers a sequence keeps: a layer a pass and layer."""
    return passes(config) * config["num_hidden_layers"]


def row_bytes(config: dict) -> int:
    """One position of one cache layer: a K and a V row."""
    return 2 * config["num_key_value_heads"] * config["head_dim"] * BYTES


def layer_params(config: dict) -> int:
    """One layer's matrices and its four norms."""
    h, m = config["hidden_size"], config["intermediate_size"]
    heads = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    return 2 * h * heads + 2 * h * kv + 3 * h * m + 4 * h


def stack_bytes(config: dict) -> int:
    """The stacked layers' weights: what ONE pass reads."""
    return config["num_hidden_layers"] * layer_params(config) * BYTES


def head_bytes(config: dict) -> int:
    return config["hidden_size"] * config["vocab_size"] * BYTES


def decode_attention_cost(config: dict, rows: float) -> dict:
    """Operations and bytes of ALL cache layers' attention in one decode step
    whose sequences hold ``rows`` rows in all: every held row's K and V read
    once a cache layer; a multiply-add a value for the scores and one for
    the weighted sum."""
    held = cache_layers(config) * rows
    width = config["num_attention_heads"] * config["head_dim"]
    return {"flops": 4 * held * width, "bytes": held * row_bytes(config)}


def decode_layers_cost(config: dict, rows: float, active: float) -> dict:
    """Operations and bytes of one decode step OUTSIDE the head and the
    sampling: the stack's weights once a pass, every held row of every cache
    layer, the ``active`` sequences' new rows written; a multiply-add a
    weight and active sequence."""
    attention = decode_attention_cost(config, rows)
    weights = passes(config) * stack_bytes(config)
    written = cache_layers(config) * active * row_bytes(config)
    return {"flops": 2 * active * weights / BYTES + attention["flops"],
            "bytes": weights + attention["bytes"] + written}


def decode_step_bytes(config: dict, rows: float, active: float) -> int:
    """Everything a decode step has to read: the layers' and the head's."""
    return decode_layers_cost(config, rows, active)["bytes"] \
        + head_bytes(config)


def prefill_cost(config: dict, rows: float) -> dict:
    """Operations and bytes of one prompt of ``rows`` real positions through
    every pass: the layers' products, causal attention (half the square),
    the head at one position; the weights once a pass and the rows written."""
    weights = passes(config) * stack_bytes(config)
    width = config["num_attention_heads"] * config["head_dim"]
    attention = cache_layers(config) * 4 * width * rows * (rows + 1) / 2
    return {"flops": 2 * rows * weights / BYTES + attention
            + 2 * head_bytes(config) / BYTES,
            "bytes": weights + head_bytes(config)
            + cache_layers(config) * rows * row_bytes(config)}


def _traced_step(ctx):
    """(rows held in all, active sequences) of the traced decode steps: the
    ``engine.decode_dispatch`` spans' medians; None where the configuration
    is no looped one or the trace has no such span."""
    if "total_ut_steps" not in ctx["cell"]["config"]:
        return None
    rows, active = (program_spans.read(
        ctx, program_spans.stat_median, program_spans.DECODE_DISPATCH, stat)
        for stat in ("rows", "active"))
    return (rows, active) if rows and active else None


def layers_ms(ctx):
    """Device milliseconds of a traced decode step outside ``lm_head`` and
    ``sample``: the layer calls of every pass and the pass ends."""
    program = readers.program(ctx, readers.DECODE_PROGRAM)
    if not program or "total_ut_steps" not in ctx["cell"]["config"]:
        return None
    outside = scope_ops.ms_per_run(ctx, readers.DECODE_PROGRAM,
                                   ("lm_head", "sample"))
    if outside is None:  # no map of operations to scopes: nothing to take off
        return None
    whole = program["total_s"] * 1e3 / program["count"]
    # where the step goes, on stderr: the scopes, and outside every scope the
    # projections, the rotation, six norms a layer and the row writes
    harness.say("loop_step", device_ms=whole, **{
        scope: scope_ops.ms_per_run(ctx, readers.DECODE_PROGRAM, (scope,))
        for scope in ("attend_cached", "mlp", "loop.pass_end", "lm_head",
                      "sample")})
    return whole - outside


def attention_ms(ctx):
    if "total_ut_steps" not in ctx["cell"]["config"]:
        return None
    return scope_ops.ms_per_run(ctx, readers.DECODE_PROGRAM,
                                ("attend_cached",))


def layers_roofline(ctx):
    step = _traced_step(ctx)
    if not step:
        return None
    return laguna_cost._share(
        ctx, decode_layers_cost(ctx["cell"]["config"], *step), layers_ms(ctx))


def attention_roofline(ctx):
    step = _traced_step(ctx)
    if not step:
        return None
    return laguna_cost._share(
        ctx, decode_attention_cost(ctx["cell"]["config"], step[0]),
        attention_ms(ctx))


def prefill_ms(ctx):
    return (ctx["counters"].get("loop_prefill") or {}).get("ms_per_req")


def prefill_roofline(ctx):
    """The least time for the captured prompt's real rows (the traffic's
    first ``warmup_prompt_tokens``) over the capture's device time."""
    if "total_ut_steps" not in ctx["cell"]["config"]:
        return None
    rows = ctx["cell"]["traffic"]["warmup_prompt_tokens"][0]
    return laguna_cost._share(
        ctx, prefill_cost(ctx["cell"]["config"], rows), prefill_ms(ctx))
