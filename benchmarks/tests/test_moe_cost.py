"""The arithmetic of moe_cost.py, by hand, and what its trace selection takes."""

import json
import os

from benchmarks import harness, moe_cost

CONF = harness.load_json(os.path.join(
    harness.HERE, "configs", "olmoe-1b-7b-serve-d8.json"))


def test_expert_ffn_cost_by_hand():
    # one 2048-token prompt, one layer: 2048 x 8 rows through three
    # 2048 x 1024 matmuls = 206.2 GFLOP (ISSUE 25's table), times 8 layers
    cost = moe_cost.expert_ffn_cost(CONF, 2048)
    assert cost["flops"] == 8 * 3 * 2 * 2048 * 1024 * 2048 * 8
    assert round(cost["flops"] / 8 / 1e9, 1) == 206.2
    # a layer's expert weights once (805 MB) + 16,384 rows in and out in bf16
    assert cost["bytes"] == 8 * (3 * 64 * 2048 * 1024 * 2
                                 + 2 * 2048 * 8 * 2048 * 2)
    assert round(3 * 64 * 2048 * 1024 * 2 / 1e6) == 805
    # required work follows the REAL tokens, not the bucket
    half = moe_cost.expert_ffn_cost(CONF, 1024)
    assert half["flops"] * 2 == cost["flops"]


def _ctx(op_self_s, programs, toy=False):
    return {"cell": {"toy": toy, "config": CONF},
            "trace": {"op_self_s": op_self_s, "programs": programs},
            "device": {"kind": "TPU v5 lite"}}


def test_selection_is_by_program_and_operation_name():
    ops = {"_prefill_impl/ragged-dot.3": 0.030, "_prefill_impl/ragged-dot.4": 0.010,
           "_prefill_impl/fusion.7": 0.5, "_decode_impl/ragged-dot.3": 0.004,
           "_decode_impl/custom-call.9": 0.2, "ragged-dot.1": 9.0}
    programs = {"_prefill_impl": {"count": 4, "total_s": 1.0, "p50_s": 0.25},
                "_decode_impl": {"count": 8, "total_s": 0.4, "p50_s": 0.05}}
    ctx = _ctx(ops, programs)
    assert moe_cost.expert_ms_per_run(ctx, "_prefill_impl") == 10.0
    assert moe_cost.expert_ms_per_run(ctx, "_decode_impl") == 0.5
    # nothing to read: a dense model, or the parent of the PR that added it
    assert moe_cost.expert_ms_per_run(_ctx({"_decode_impl/fusion.1": 1.0},
                                           programs), "_decode_impl") is None
    assert moe_cost.expert_ms_per_run(
        {"cell": {"toy": False}, "trace": {}}, "_decode_impl") is None


def test_roofline_share_from_real_tokens():
    programs = {"_prefill_impl": {"count": 1, "total_s": 0.1, "p50_s": 0.1}}
    cost = moe_cost.expert_ffn_cost(CONF, 1500)
    least = max(cost["flops"] / 197e12, cost["bytes"] / 819e9)
    ctx = _ctx({"_prefill_impl/ragged-dot.1": 2 * least}, programs)
    assert abs(moe_cost.experts_roofline(ctx, 1500) - 50.0) < 1e-9
    assert moe_cost.experts_roofline(ctx, None) is None
    assert moe_cost.experts_roofline(_ctx({}, programs), 1500) is None
    toy = _ctx({"_prefill_impl/ragged-dot.1": 1.0}, programs, toy=True)
    assert moe_cost.experts_roofline(toy, 1500) is None  # no peak for a CPU
