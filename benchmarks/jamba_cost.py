"""What the Mamba-1 mixers of a ``jamba`` configuration require of the chip:
the decode step's state update and a prefill's selective scan. The yardstick
of ``mamba1_state_roofline`` and ``mamba1_prefill_scan_roofline``.

Required work counts the published mathematics only, and only bytes that are
moved in the time they are divided by (PERF.md section 6, PR 34). The state
update: the states of every ACTIVE sequence, float32, read once and written
once in every mixer (a free slot's state is not required work), and the rates
``A`` once a mixer. The scan: for the prompt's REAL rows (a bucket's pads are
computed and not required) the channels' ``dt``, ``dt x`` and read-out,
float32, once each, ``B`` and ``C`` once, the rates and the state in and out
once a mixer: a kernel that keeps the state in fast memory moves nothing
else. Both are elementwise work on the VECTOR unit: an exponential, two
multiply-adds and a share of a sum over the states a state element.
``peaks.py`` has no published peak for the vector unit, so both shares are of
the HBM bound (``peaks.roofline_seconds`` puts the operations against the
matrix unit's peak, where they are nothing): the state update, which the
memory does bind, can come near 100; the scan, which the vector unit binds,
reads well under it, and never over: the bytes are a floor of what is moved.
"""

from __future__ import annotations

from benchmarks import laguna_cost, program_spans, readers, scope_ops

STATE_BYTES = 4  # the state, dt, dt x, B, C and the read-out are float32
OPS = 6  # an element: the decay's product and exponential, two multiply-adds,
# the read-out's product and sum


def mixers(config: dict) -> int:
    """Layers that are no attention layer (``attn_layer_period`` /
    ``attn_layer_offset``)."""
    return sum(i % config["attn_layer_period"] != config["attn_layer_offset"]
               for i in range(config["num_hidden_layers"]))


def channels(config: dict) -> int:
    return config["mamba_expand"] * config["hidden_size"]


def state_update_cost(config: dict, active: float) -> dict:
    """Operations and bytes of ALL mixers' state update for one decode step
    of ``active`` sequences: ``channels x mamba_d_state`` float32 a sequence
    and mixer, read once and written once, and the rates once a mixer."""
    a_mixer = channels(config) * config["mamba_d_state"]
    elements = mixers(config) * active * a_mixer
    return {"flops": elements * OPS,
            "bytes": (2 * elements + mixers(config) * a_mixer) * STATE_BYTES}


def scan_cost(config: dict, rows: float) -> dict:
    """Operations and bytes of ALL mixers' selective scan over one prompt of
    ``rows`` real positions: ``dt``, ``dt x`` in and the read-out out, a value
    a channel and position each; ``B`` and ``C`` a value a state and position;
    the rates once, the state in and out once."""
    c, n = channels(config), config["mamba_d_state"]
    per_mixer = rows * (3 * c + 2 * n) + 3 * c * n
    return {"flops": mixers(config) * rows * c * n * OPS,
            "bytes": mixers(config) * per_mixer * STATE_BYTES}


def state_roofline(ctx):
    """The least time for the traced steps' active sequences' states (the
    ``engine.decode_dispatch`` spans' median ``active``) over the time of the
    operations under ``ssm1.state``."""
    active = program_spans.read(
        ctx, program_spans.stat_median, program_spans.DECODE_DISPATCH,
        "active")
    if not active or "mamba_d_state" not in ctx["cell"]["config"]:
        return None
    return laguna_cost._share(
        ctx, state_update_cost(ctx["cell"]["config"], active),
        scope_ops.ms_per_run(ctx, readers.DECODE_PROGRAM, ("ssm1.state",)))


def prefill_scan_ms(ctx):
    """Milliseconds under ``ssm1.prefill_scan`` in the capture of ONE warmed
    prefill (all mixers); None without one."""
    captured = ctx["counters"].get("mamba1_prefill") or {}
    return (captured.get("by_scope_ms") or {}).get("ssm1.prefill_scan")


def prefill_scan_roofline(ctx):
    """The least time for the captured prompt's real rows (the traffic's
    first ``warmup_prompt_tokens``) through every mixer's scan, over the time
    under ``ssm1.prefill_scan`` in that capture."""
    if "mamba_d_state" not in ctx["cell"]["config"]:
        return None
    rows = ctx["cell"]["traffic"]["warmup_prompt_tokens"][0]
    return laguna_cost._share(
        ctx, scan_cost(ctx["cell"]["config"], rows), prefill_scan_ms(ctx))
