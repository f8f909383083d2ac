"""Arithmetic the per-layer readers share. A reader (``layer_metrics/
<name>.py``) gets ``ctx``: the cell, the runner's ``counters`` (host clocks
and program counters of the whole window), the reduced ``trace`` of the
traced sub-window (empty without one) and the ``device``. It returns a
number, or None when there is nothing to read; the harness then leaves the
metric out of the line.
"""

from __future__ import annotations

import statistics

from benchmarks import flops, harness, peaks, trace_reduce

# The three Pallas attention kernels in the device trace. ops/attention.py
# gives its pallas_calls no name and the HLO names them after whatever jaxpr
# they sat in (closed_call.9, checkpoint.21), so trace_reduce tags every
# tpu_custom_call with its operand and result counts: the forward kernel
# takes q, k, v; the two backward kernels take q, k, v, dO, lse, delta.
FLASH_KERNELS = ("tpu_custom_call/",)
FLASH_FORWARD = ("tpu_custom_call/3in/",)
FLASH_BACKWARD = ("tpu_custom_call/6in/",)
DECODE_PROGRAM = "_decode_impl"
PREFILL_PROGRAM = "_prefill_impl"


def median_ms(values):
    return statistics.median(values) * 1e3 if values else None


def mean_ms(values):
    return statistics.fmean(values) * 1e3 if values else None


def percentile_ms(values, q):
    return harness.percentile(values, q) * 1e3 if values else None


def idle_share(ctx):
    t = ctx["trace"]
    if not t or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def kernel_seconds(ctx, tag_prefixes):
    """Self seconds in the traced window of the Pallas kernels so tagged."""
    if not ctx["trace"]:
        return None
    return trace_reduce.kernel_self_s(ctx["trace"], tag_prefixes) or None


def program(ctx, pattern):
    """Stats of the jitted program whose name holds ``pattern``."""
    t = ctx["trace"]
    if not t:
        return None
    found = [v for n, v in t["programs"].items() if pattern in n]
    if not found:
        return None
    return {"count": sum(v["count"] for v in found),
            "total_s": sum(v["total_s"] for v in found),
            "p50_s": max(found, key=lambda v: v["count"])["p50_s"]}


def engine_rate(ctx, key):
    c = ctx["counters"]
    return c["engine"][key] / c["window_s"] if "engine" in c else None


def slot_occupancy(ctx):
    """Tokens the decode steps emitted over the rows they computed. The
    first token of a request comes from its prefill, not from a step."""
    e = ctx["counters"].get("engine")
    if not e or not e["steps"]:
        return None
    return 100.0 * (e["tokens_out"] - e["admitted"]) / (
        e["steps"] * ctx["counters"]["slots"])


def proxy_refused_share(ctx):
    p = ctx["counters"].get("proxy")
    if not p or not p["requests"]:
        return None
    return 100.0 * (p["shed"] + p["deadline_exceeded"]) / p["requests"]


def mfu_required(ctx):
    """Tokens per second and chip, times the operations a token requires
    (flops.py: no recomputation, no frozen dW), over the chip's published
    peak. Not a kernel's roofline share, and it says nothing of idle time."""
    if ctx["cell"]["toy"]:
        return None  # no published peak for a CPU
    c = ctx["counters"]
    per_token = flops.train_flops_per_token(
        ctx["cell"]["config"], c["seq"], c["lora_rank"])
    peak = peaks.peaks_for(ctx["device"]["kind"])["bf16_flops_per_s"]
    # from the median step, not the window's rate: in a traced run the
    # window also holds the profiler's start and stop
    rate = c["tokens_per_step"] / statistics.median(c["step_s"]) \
        / ctx["device"]["count"]
    return 100.0 * rate * per_token / peak


def flash_roofline(ctx):
    """The least time the chip could take for the three attention kernels'
    required operations and bytes, over the time they took in the trace."""
    if ctx["cell"]["toy"]:
        return None
    fwd, bwd = kernel_seconds(ctx, FLASH_FORWARD), kernel_seconds(ctx, FLASH_BACKWARD)
    if not fwd or not bwd:
        return None
    c = ctx["counters"]
    cost = flops.flash_kernel_cost(c["batch"], c["seq"], ctx["cell"]["config"],
                                   ctx["device"]["count"])
    # with remat the forward kernel runs twice a step: the recomputation is
    # not required work, so one forward's cost stands against both runs
    least = sum(peaks.roofline_seconds(cost[k]["flops"], cost[k]["bytes"],
                                       ctx["device"]["kind"])[0]
                for k in ("forward", "backward"))
    return 100.0 * least / ((fwd + bwd) / c["trace_steps"])
