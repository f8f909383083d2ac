"""The arithmetic of laguna_cost.py, by hand; the configuration file against
what ISSUE 34 states of it; the runner's model configuration; and each new
reader on a recorded fixture."""

import os

import pytest

from benchmarks import harness, laguna_cost

CONF = harness.load_json(os.path.join(
    harness.HERE, "configs", "laguna-s-2.1-serve-ep2-d5.json"))
CELL = "serve-window-moe-code-long-out"


def test_the_configuration_is_the_published_one_but_for_its_three_cuts():
    assert CONF["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert CONF["published"] == {"num_hidden_layers": 48, "num_experts": 256,
                                 "vocab_size": 100352}
    assert (CONF["num_hidden_layers"], CONF["num_experts"],
            CONF["vocab_size"]) == (5, 128, 50176)
    assert (CONF["hidden_size"], CONF["intermediate_size"], CONF["head_dim"],
            CONF["num_key_value_heads"], CONF["moe_intermediate_size"],
            CONF["shared_expert_intermediate_size"],
            CONF["num_experts_per_tok"]) == (3072, 12288, 128, 8, 1024, 1024, 10)
    assert CONF["layer_types"][:5] == ["full_attention"] + \
        ["sliding_attention"] * 3 + ["full_attention"]
    assert len(CONF["layer_types"]) == 48  # copied whole
    assert CONF["num_attention_heads_per_layer"][:5] == [48, 72, 72, 72, 48]
    assert CONF["sliding_window"] == 512 and CONF["gating"] == "per-head"
    assert CONF["rope_parameters"]["full_attention"]["factor"] == 128
    for key in ("source", "assumed", "deployment", "runner", "serve"):
        assert CONF[key]
    # every number of the catalog's entry, but the three that are reduced
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        import json

        row = next(json.loads(line) for line in open(catalog)
                   if '"Laguna-S-2.1"' in line)
        assert CONF["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in CONF["reduced"]:
                assert CONF[key] == value, key


def test_the_runner_builds_the_pattern_from_the_file():
    runner = harness.load_module("runners", "serve_laguna")
    cfg = runner.laguna_model_config(CONF)
    assert cfg.layer_kinds == ("window", "window", "window", "full")
    assert (cfg.layers, cfg.periods, cfg.full_layers, cfg.window_layers,
            cfg.sparse_layers) == (5, 1, 2, 3, 4)
    assert (cfg.heads, cfg.window_heads, cfg.kv_heads, cfg.hd, cfg.window) == \
        (48, 72, 8, 128, 512)
    assert (cfg.num_experts, cfg.experts_held, cfg.experts_per_token,
            cfg.routed_scale) == (256, (0, 128), 10, 2.5)
    # ISSUE 34's arithmetic: 5.57B parameters held, 11.14 GB in bfloat16
    assert round(cfg.num_params() / 1e9, 2) == 5.57
    assert round(cfg.num_params() * 2 / 1e9, 2) == 11.14
    # --toy keeps every mechanism at debug widths, in one dictionary for
    # the program and the reference
    toy = runner.toy_config(dict(CONF, **harness.TOY_MODEL))
    tcfg = runner.laguna_model_config(toy)
    assert (tcfg.layers, tcfg.heads, tcfg.window_heads, tcfg.window,
            tcfg.num_experts, tcfg.experts_held, tcfg.experts_per_token) == \
        (5, 4, 6, 8, 16, (0, 8), 4)
    assert toy["num_attention_heads_per_layer"][:5] == [4, 6, 6, 6, 4]
    broken = dict(CONF, mlp_only_layers=[0, 1])
    with pytest.raises(ValueError, match="one leading full layer"):
        runner.laguna_model_config(broken)


def test_decode_attention_cost_by_hand():
    # a position's k and v: 2 x 8 heads x 128 x 2 bytes = 4 KB a layer
    window = laguna_cost.decode_attention_cost(CONF, "window", 32 * 512)
    full = laguna_cost.decode_attention_cost(CONF, "full", 32 * 4096)
    assert laguna_cost.layers_of(CONF, "window") == [1, 2, 3]
    assert laguna_cost.layers_of(CONF, "full") == [0, 4]
    # the rings whole are 0.20 GB, the slots whole 1.07 GB
    assert window["bytes"] == 3 * 32 * 512 * 4096
    assert full["bytes"] == 2 * 32 * 4096 * 4096
    assert round(window["bytes"] / 1e9, 2) == 0.20
    assert round(full["bytes"] / 1e9, 2) == 1.07
    # 72 / 48 query heads x (a dot product + a weighted sum) of 128 terms
    assert laguna_cost.decode_attention_cost(CONF, "window", 1)["flops"] == \
        3 * 72 * 128 * 4
    assert laguna_cost.decode_attention_cost(CONF, "full", 1)["flops"] == \
        2 * 48 * 128 * 4
    # memory bound
    assert full["flops"] / 197e12 < full["bytes"] / 819e9 / 20


def test_held_experts_cost_by_hand():
    # one expert is 3 x 3072 x 1024 = 9.437M parameters = 18.87 MB
    assert round(laguna_cost.held_experts_cost(CONF, 1)["bytes"] / 1e6, 2) == \
        18.87
    # all 128 held in 4 layers: 9.66 GB
    assert round(laguna_cost.held_experts_cost(CONF, 512)["bytes"] / 1e9, 2) \
        == 9.66


SCOPES = {"_decode_impl": {
    "attn.window": ["fusion.1", "fusion.2"], "attn.full": ["fusion.3"],
    "moe.shared": ["fusion.4"], "moe_router": ["fusion.5"],
    "moe_experts": ["ragged-dot-none.1", "sort.2"], "lm_head": ["fusion.6"],
    "sample": ["fusion.7"]}}
OPS = {"_decode_impl/fusion.1": 0.010, "_decode_impl/fusion.2": 0.006,
       "_decode_impl/fusion.3": 0.020, "_decode_impl/fusion.4": 0.002,
       "_decode_impl/fusion.5": 0.001, "_decode_impl/ragged-dot-none.1": 0.100,
       "_decode_impl/sort.2": 0.001, "_decode_impl/fusion.6": 0.004,
       "_decode_impl/fusion.7": 0.0005, "_prefill_impl/fusion.1": 5.0}


def _ctx(toy=False, spans=True, moe=True, scopes=SCOPES):
    dispatch = [["ray_tpu.engine.decode_dispatch", i * 1000, 10, 7,
                 {"active": 32, "rows": 32 * 2000 + i, "window_rows": 32 * 512}]
                for i in range(3)]
    trace = {"op_self_s": OPS, "programs": {
        "_decode_impl": {"count": 10, "total_s": 0.15, "p50_s": 0.015}}}
    if spans:
        trace["program_spans"] = {"spans": dispatch, "busy": {}, "window": {}}
    counters = {"reference_check": {"op_scopes": scopes},
                "engine": {"steps": 100, "tokens_out": 3200, "admitted": 0}}
    if moe:
        counters["moe"] = {
            "moe_assignments": 128000, "moe_rows": 3200, "layers": 4,
            "moe_assignments_held": 64500, "moe_experts_reached": 36400}
    return {"cell": {"toy": toy, "config": CONF, "name": CELL},
            "trace": trace, "counters": counters,
            "device": {"kind": "TPU v5 lite"}}


@pytest.mark.parametrize("metric, want", [
    ("window_attention_ms_per_decode_step", 1.6),
    ("full_attention_ms_per_decode_step", 2.0),
    ("shared_expert_ms_per_decode_step", 0.2),
    ("moe_router_ms_per_decode_step", 0.1),
    ("moe_expert_ms_per_decode_step", 10.0),
    ("head_sample_ms_per_decode_step", 0.45),
    ("moe_assignments_per_token", 10.0),
    ("moe_held_share", 64500 / 128000),
    ("tput_decode_step_device_ms", 15.0),
])
def test_each_reader_on_a_recorded_run(metric, want):
    read = harness.load_reader(metric).read
    assert read(_ctx()) == pytest.approx(want)
    # the parent of the PR has no such scope, counter or trace: nothing is
    # read, nothing raises, the line leaves the metric out
    bare = {"cell": {"toy": False, "config": CONF}, "trace": {},
            "counters": {}, "device": {"kind": "TPU v5 lite"}}
    assert read(bare) is None


def test_the_new_readers_have_files_of_their_own():
    for metric in ("moe_router_ms_per_decode_step", "moe_held_share",
                   "window_attention_roofline", "full_attention_roofline",
                   "held_experts_roofline"):
        assert harness.load_reader(metric).__file__.endswith(
            os.path.join("layer_metrics", metric + ".py"))


def test_roofline_shares_from_what_the_steps_hold_and_reach():
    ctx = _ctx()
    window = laguna_cost.decode_attention_cost(CONF, "window", 32 * 512)
    full = laguna_cost.decode_attention_cost(CONF, "full", 32 * 2000 + 1)
    held = laguna_cost.held_experts_cost(CONF, 364.0)
    got = {m: harness.load_reader(m).read(ctx) for m in (
        "window_attention_roofline", "full_attention_roofline",
        "held_experts_roofline")}
    assert got["window_attention_roofline"] == pytest.approx(
        100 * window["bytes"] / 819e9 / 1.6e-3)
    assert got["full_attention_roofline"] == pytest.approx(
        100 * full["bytes"] / 819e9 / 2.0e-3)
    assert got["held_experts_roofline"] == pytest.approx(
        100 * held["bytes"] / 819e9 / 10.0e-3)
    assert all(0 < v < 100 for v in got.values()), got
    for m in got:  # a CPU has no published peak; the parent has no span,
        read = harness.load_reader(m).read  # no counter and no scope
        assert read(_ctx(toy=True)) is None
        assert read(_ctx(spans=False, moe=False)) is None
        if m != "held_experts_roofline":  # by operation name, no scope
            assert read(_ctx(scopes={})) is None
