"""The arithmetic of kimi_linear_cost.py, by hand; the configuration file
against what ISSUE 38 states of it; the runner's model configuration; and each
new reader on a recorded fixture."""

import os

import pytest

from benchmarks import harness, kimi_linear_cost

CONF = harness.load_json(os.path.join(
    harness.HERE, "configs", "kimi-linear-48b-a3b-serve-ep16.json"))
CELL = "serve-kda-mla-rollout-long-out"
NEW = ("state_update_ms_per_decode_step", "state_project_ms_per_decode_step",
       "mla_attention_ms_per_decode_step",
       "state_prefill_ms_per_req", "state_update_roofline",
       "mla_attention_roofline")


def test_the_configuration_is_the_published_one_but_for_its_two_cuts():
    assert CONF["reduced"] == ["num_experts", "vocab_size"]
    assert CONF["published"] == {"num_experts": 256, "vocab_size": 163840}
    assert (CONF["num_hidden_layers"], CONF["num_experts"],
            CONF["vocab_size"]) == (27, 16, 20480)  # depth is not cut
    lin = CONF["linear_attn_config"]
    assert len(lin["kda_layers"]) == 20 and lin["kda_layers"][:4] == [1, 2, 3, 5]
    assert lin["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert (lin["num_heads"], lin["head_dim"],
            lin["short_conv_kernel_size"]) == (32, 128, 4)
    assert (CONF["hidden_size"], CONF["intermediate_size"],
            CONF["moe_intermediate_size"], CONF["kv_lora_rank"],
            CONF["qk_nope_head_dim"], CONF["qk_rope_head_dim"],
            CONF["v_head_dim"], CONF["num_experts_per_token"],
            CONF["routed_scaling_factor"]) == (
        2304, 9216, 1024, 512, 128, 64, 128, 8, 2.446)
    for key in ("source", "assumed", "deployment", "runner", "serve"):
        assert CONF[key]
    # every number of the catalog's entry, but the two that are reduced
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        import json

        row = next(json.loads(line) for line in open(catalog)
                   if '"Kimi-Linear-48B-A3B-Instruct"' in line)
        assert CONF["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in CONF["reduced"]:
                assert CONF[key] == value, key
    traffic = harness.load_json(harness.find_data_file(
        "traffic", "rollout-long-out"))
    assert (traffic["loop"], traffic["clients"], traffic["new_tokens"],
            traffic["max_requests_per_s"], traffic["ramp_s"],
            traffic["repeat_every"], traffic["repeat_prompt_tokens"],
            traffic["trace_after_s"], traffic["trace_s"], traffic["path"]) == (
        "closed", 32, 1024, 6.0, 6.0, 40, 1500, 5.0, 2.0,
        "/llm/generate_stream")
    assert traffic["prompt_tokens"] == {"dist": "uniform", "min": 1040,
                                        "max": 2000}


def test_the_runner_builds_the_pattern_from_the_file():
    runner = harness.load_module("runners", "serve_kimi_linear")
    cfg = runner.kimi_model_config(CONF)
    assert (cfg.lead_kind, cfg.layer_kinds, cfg.tail_kinds) == (
        "kda", ("kda", "kda", "mla", "kda"), ("kda", "mla"))
    assert (cfg.layers, cfg.periods, cfg.layers_of("kda"),
            cfg.layers_of("mla"), cfg.sparse_layers) == (27, 6, 20, 7, 26)
    assert [l + 1 for l, k in enumerate(cfg.kinds) if k == "mla"] == \
        CONF["linear_attn_config"]["full_attn_layers"]
    assert (cfg.heads, cfg.hd, cfg.kda_conv, cfg.mla_latent, cfg.mla_rope_dim,
            cfg.latent_row) == (32, 128, 4, 512, 64, 640)
    assert (cfg.num_experts, cfg.experts_held, cfg.experts_per_token,
            cfg.routed_scale, cfg.router_score) == (
        256, (0, 16), 8, 2.446, "sigmoid")
    assert cfg.keeps == ("mat", "conv", "latent") and cfg.stateful
    # ISSUE 38's arithmetic: 4.30B parameters held, 8.59 GB in bfloat16
    assert round(cfg.num_params() / 1e9, 2) == 4.30
    assert round(cfg.num_params() * 2 / 1e9, 2) == 8.59
    # --toy keeps every mechanism at debug widths
    tcfg = runner.kimi_model_config(
        runner.toy_config(dict(CONF, **harness.TOY_MODEL)))
    assert (tcfg.layers, tcfg.periods, tcfg.tail_kinds, tcfg.heads, tcfg.hd,
            tcfg.num_experts, tcfg.experts_held, tcfg.experts_per_token) == \
        (11, 2, ("kda", "mla"), 4, 16, 16, (0, 8), 4)
    with pytest.raises(ValueError, match="one leading layer"):
        runner.kimi_model_config(dict(CONF, first_k_dense_replace=2))
    broken = dict(CONF, linear_attn_config=dict(
        CONF["linear_attn_config"], full_attn_layers=[4, 5]))
    with pytest.raises(ValueError, match="both or neither"):
        runner.kimi_model_config(broken)


def test_costs_by_hand():
    # a sequence's state in one layer: 32 x 128 x 128 x 4 B = 2.10 MB; all 32
    # slots, 20 layers, read and written: 2 x 1.342 GB
    cost = kimi_linear_cost.state_update_cost(CONF, 32)
    assert cost["bytes"] == 2 * 20 * 32 * 32 * 128 * 128 * 4
    assert round(cost["bytes"] / 1e9, 2) == 2.68
    assert cost["flops"] / 197e12 < cost["bytes"] / 819e9 / 20  # memory bound
    # a position's latent row: 576 x 2 B = 1,152 B a layer, 7 layers
    rows = kimi_linear_cost.latent_attention_cost(CONF, 32 * 4096)
    assert rows["bytes"] == 7 * 32 * 4096 * 576 * 2
    assert round(rows["bytes"] / 1e9, 2) == 1.06
    one = kimi_linear_cost.latent_attention_cost(CONF, 1)
    assert one["flops"] == 7 * 32 * (2 * 576 + 2 * 512)
    # one expert is 3 x 2304 x 1024 = 7.08M parameters = 14.16 MB
    assert round(kimi_linear_cost.held_experts_cost(CONF, 1)["bytes"] / 1e6,
                 2) == 14.16


SCOPES = {"_decode_impl": {
    "kda.project": ["fusion.1"], "kda.conv": ["fusion.2"],
    "kda.gate": ["fusion.3"], "kda.state": ["fusion.4", "fusion.5"],
    "kda.out": ["fusion.6"], "mla.project": ["fusion.7"],
    "mla.attend": ["latent_decode_attention.1"], "mla.out": ["fusion.8"],
    "moe.shared": ["fusion.9"], "moe_router": ["fusion.10"],
    "moe_experts": ["ragged-dot-none.1"], "lm_head": ["fusion.11"],
    "sample": ["fusion.12"]}}
OPS = {"_decode_impl/fusion.1": 0.020, "_decode_impl/fusion.2": 0.004,
       "_decode_impl/fusion.3": 0.003, "_decode_impl/fusion.4": 0.040,
       "_decode_impl/fusion.5": 0.020, "_decode_impl/fusion.6": 0.003,
       "_decode_impl/fusion.7": 0.006,
       "_decode_impl/latent_decode_attention.1": 0.012,
       "_decode_impl/fusion.8": 0.002, "_decode_impl/fusion.9": 0.008,
       "_decode_impl/fusion.10": 0.004,
       "_decode_impl/ragged-dot-none.1": 0.050,
       "_decode_impl/fusion.11": 0.002, "_decode_impl/fusion.12": 0.0005,
       "_prefill_impl/fusion.4": 5.0}


def _ctx(toy=False, spans=True, scopes=SCOPES, prefill=True):
    dispatch = [["ray_tpu.engine.decode_dispatch", i * 1000, 10, 7,
                 {"active": 32, "rows": 32 * 2000 + i}] for i in range(3)]
    trace = {"op_self_s": OPS, "programs": {
        "_decode_impl": {"count": 10, "total_s": 0.2, "p50_s": 0.02}}}
    if spans:
        trace["program_spans"] = {"spans": dispatch, "busy": {}, "window": {}}
    counters = {"reference_check": {"op_scopes": scopes},
                "engine": {"steps": 100, "tokens_out": 3200, "admitted": 0},
                "moe": {"moe_assignments": 665600, "moe_rows": 3200,
                        "layers": 26, "moe_assignments_held": 41500,
                        "moe_experts_reached": 26200}}
    if prefill:
        counters["kda_prefill"] = {"ms_per_req": 61.5, "prefill_ms": 150.0}
    return {"cell": {"toy": toy, "config": CONF, "name": CELL},
            "trace": trace, "counters": counters,
            "device": {"kind": "TPU v5 lite"}}


@pytest.mark.parametrize("metric, want", [
    ("state_update_ms_per_decode_step", 6.0),
    ("state_project_ms_per_decode_step", 3.0),
    ("mla_attention_ms_per_decode_step", 1.2),
    ("state_prefill_ms_per_req", 61.5),
    ("shared_expert_ms_per_decode_step", 0.8),
    ("moe_router_ms_per_decode_step", 0.4),
    ("moe_expert_ms_per_decode_step", 5.0),
    ("head_sample_ms_per_decode_step", 0.25),
    ("moe_assignments_per_token", 8.0),
    ("moe_held_share", 41500 / 665600),
    ("tput_decode_step_device_ms", 20.0),
])
def test_each_reader_on_a_recorded_run(metric, want):
    read = harness.load_reader(metric).read
    assert read(_ctx()) == pytest.approx(want)
    # the parent of the PR has no such scope, counter or trace: nothing is
    # read, nothing raises, the line leaves the metric out
    bare = {"cell": {"toy": False, "config": CONF}, "trace": {},
            "counters": {}, "device": {"kind": "TPU v5 lite"}}
    assert read(bare) is None


def test_the_cell_is_on_every_entry_read_here():
    # which entries are this cell's ALONE, and how many there are, is
    # `test_per_layer_entries.py`'s to say (PR 69: an entry is a question,
    # and other configurations' cells answer these too)
    bench = harness.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for metric in NEW:
        assert harness.load_reader(metric).__file__.endswith(
            os.path.join("layer_metrics", metric + ".py"))
        assert CELL in by_name[metric]["workloads"]
    mine = [m for m in bench["per_layer"] if CELL in m.get("workloads", [])]
    assert len(mine) > len(NEW)  # the shared readers' lists, joined
    assert all(m["moves"] in ("out_tokens_per_s", "setup_s") for m in mine)
    for m in mine:  # every entry finds its reader, a prefixed one its words'
        harness.load_reader(m["name"])


def test_roofline_shares_from_what_the_steps_hold_and_reach():
    ctx = _ctx()
    state = kimi_linear_cost.state_update_cost(CONF, 32)
    rows = kimi_linear_cost.latent_attention_cost(CONF, 32 * 2000 + 1)
    held = kimi_linear_cost.held_experts_cost(CONF, 262.0)
    got = {m: harness.load_reader(m).read(ctx) for m in (
        "state_update_roofline", "mla_attention_roofline",
        "held_experts_roofline")}
    assert got["state_update_roofline"] == pytest.approx(
        100 * state["bytes"] / 819e9 / 6.0e-3)
    assert got["mla_attention_roofline"] == pytest.approx(
        100 * rows["bytes"] / 819e9 / 1.2e-3)
    assert got["held_experts_roofline"] == pytest.approx(
        100 * held["bytes"] / 819e9 / 5.0e-3)
    assert all(0 < v < 100 for v in got.values()), got
    for m in got:  # a CPU has no published peak; the parent has no span,
        read = harness.load_reader(m).read  # no counter and no scope
        assert read(_ctx(toy=True)) is None
        if m != "held_experts_roofline":
            assert read(_ctx(spans=False)) is None
            assert read(_ctx(scopes={})) is None
