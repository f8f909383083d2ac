"""The arithmetic of nemotron_h_cost.py, by hand; the configuration file
against what ISSUE 54 states of it; the runner's model configuration; and each
new reader on a recorded fixture."""

import os

import pytest

from benchmarks import harness, laguna_cost, nemotron_h_cost

CONF = harness.load_json(os.path.join(
    harness.HERE, "configs", "nemotron-3-super-serve-ep8-d22.json"))
CELL = "serve-ssm-lmoe-reason-long-out"
NEW = ("state_update_ms_per_decode_step", "state_update_roofline",
       "state_project_ms_per_decode_step", "state_prefill_ms_per_req",
       "lmoe_latent_ms_per_decode_step", "held_experts_roofline",
       "gqa_attention_ms_per_decode_step")
JOINED = ("tput_decode_steps_per_s", "tput_slot_occupancy",
          "tput_device_idle_share", "tput_engine_host_ms_per_step",
          "tput_stream_yield_ms_per_token", "tput_decode_step_device_ms",
          "tput_engine_step_period_ms", "tput_pump_cpu_ms_per_step",
          "tput_pump_wait_ms_per_step", "tput_stream_items_per_call",
          "tput_proxy_forward_ms_per_item", "moe_expert_ms_per_decode_step",
          "moe_assignments_per_token", "moe_router_ms_per_decode_step",
          "moe_held_share", "head_sample_ms_per_decode_step",
          "shared_expert_ms_per_decode_step")


def test_the_configuration_is_the_published_one_but_for_its_cuts():
    assert CONF["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                               "n_routed_experts", "vocab_size"]
    published = CONF["published"]
    assert (published["num_hidden_layers"], published["n_routed_experts"],
            published["vocab_size"]) == (88, 512, 131072)
    whole = published["hybrid_override_pattern"]
    assert len(whole) == 88 and [whole.count(c) for c in "M*E"] == [40, 8, 40]
    # the segments between the eight attentions: it is not periodic
    assert [len(run) for run in whole.split("*")] == [7, 8, 8, 10, 10, 10,
                                                      10, 8, 9]
    prefix = CONF["hybrid_override_pattern"]
    assert prefix == whole[:22] == "MEMEMEM*EMEMEMEM*EMEME"
    assert [prefix.count(c) for c in "M*E"] == [10, 2, 10]  # 5 : 1 : 5
    assert (CONF["num_hidden_layers"], CONF["n_routed_experts"],
            CONF["vocab_size"]) == (22, 64, 16384)
    assert (CONF["hidden_size"], CONF["mamba_num_heads"],
            CONF["mamba_head_dim"], CONF["n_groups"], CONF["ssm_state_size"],
            CONF["conv_kernel"], CONF["chunk_size"], CONF["moe_latent_size"],
            CONF["moe_intermediate_size"],
            CONF["moe_shared_expert_intermediate_size"],
            CONF["num_experts_per_tok"], CONF["routed_scaling_factor"],
            CONF["num_attention_heads"], CONF["num_key_value_heads"],
            CONF["head_dim"]) == (
        4096, 128, 64, 8, 128, 4, 128, 1024, 2688, 5376, 22, 5, 32, 2, 128)
    assert "mtp" in CONF["left_out"] and CONF["num_nextn_predict_layers"] == 1
    for key in ("source", "assumed", "deployment", "runner", "serve"):
        assert CONF[key]
    assert "v5e-32" in CONF["deployment"] and "4 pipeline stages of 8" in \
        CONF["deployment"]
    # every number of the catalog's entry, but those that are reduced
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        import json

        row = next(json.loads(line) for line in open(catalog)
                   if '"NVIDIA-Nemotron-3-Super-120B-A12B-BF16"' in line)
        assert CONF["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in CONF["reduced"]:
                assert CONF[key] == value, key
            else:
                assert published[key] == value, key
    traffic = harness.load_json(harness.find_data_file(
        "traffic", "reason-short-in-long-out"))
    assert (traffic["loop"], traffic["clients"], traffic["new_tokens"],
            traffic["max_requests_per_s"], traffic["ramp_s"],
            traffic["repeat_every"], traffic["repeat_prompt_tokens"],
            traffic["warmup_prompt_tokens"],
            traffic["reference_prompt_tokens"],
            traffic["reference_new_tokens"], traffic["trace_after_s"],
            traffic["trace_s"], traffic["path"]) == (
        "closed", 32, 1024, 6.0, 6.0, 40, 400, [400], 400, 8, 5.0, 2.0,
        "/llm/generate_stream")
    assert traffic["prompt_tokens"] == {"dist": "uniform", "min": 272,
                                        "max": 496}
    assert CONF["serve"]["cache_slots"] == traffic["clients"]
    assert CONF["serve"]["max_len"] == 2048


def test_the_runner_builds_the_pattern_from_the_file():
    runner = harness.load_module("runners", "serve_nemotron_h")
    cfg = runner.nemotron_model_config(CONF)
    assert cfg.lead_kind == "" and cfg.tail_kinds == ()
    assert "".join({"ssm": "M", "gqa": "*", "lmoe": "E"}[k]
                   for k in cfg.kinds) == CONF["hybrid_override_pattern"]
    assert (cfg.layers, cfg.layers_of("ssm"), cfg.layers_of("gqa"),
            cfg.layers_of("lmoe"), cfg.sparse_layers) == (22, 10, 2, 10, 10)
    assert (cfg.heads, cfg.kv_heads, cfg.hd, cfg.ssm_heads, cfg.ssm_head_dim,
            cfg.ssm_groups, cfg.ssm_state, cfg.ssm_conv, cfg.ssm_chunk) == (
        32, 2, 128, 128, 64, 8, 128, 4, 128)
    assert (cfg.num_experts, cfg.experts_held, cfg.experts_per_token,
            cfg.routed_scale, cfg.router_score, cfg.moe_latent,
            cfg.expert_act, cfg.mlp_hidden, cfg.shared_expert_hidden) == (
        512, (0, 64), 22, 5.0, "sigmoid", 1024, "relu2", 2688, 5376)
    assert cfg.keeps == ("k", "v", "mat", "conv") and cfg.stateful
    kept = {k.fields: (k.layers, k.rows, k.shape) for k in cfg.kept(2048)}
    assert kept == {("k", "v"): (2, 2048, (2, 128)),
                    ("mat",): (10, None, (128, 8192)),
                    ("conv",): (10, None, (3 * 10240,))}
    # ISSUE 54's arithmetic: 5.370B parameters held, 10.74 GB in bfloat16
    assert round(cfg.num_params() / 1e9, 3) == 5.370
    assert round(cfg.num_params() * 2 / 1e9, 2) == 10.74
    # the whole layout's rows are gathered: an eighth held is no thin share
    from ray_tpu.models import transformer as T

    assert T.held_rows_cap(cfg, 32 * 22) is None
    # --toy keeps every mechanism at debug widths
    tcfg = runner.nemotron_model_config(
        runner.toy_config(dict(CONF, **harness.TOY_MODEL)))
    assert (tcfg.layers, tcfg.layers_of("ssm"), tcfg.layers_of("gqa"),
            tcfg.layers_of("lmoe"), tcfg.heads, tcfg.kv_heads, tcfg.hd,
            tcfg.num_experts, tcfg.experts_held, tcfg.experts_per_token,
            tcfg.moe_latent) == (11, 5, 2, 4, 4, 2, 16, 16, (0, 8), 4, 32)
    with pytest.raises(ValueError, match="one letter of hybrid_override"):
        runner.nemotron_model_config(dict(CONF, num_hidden_layers=23))
    with pytest.raises(ValueError, match="ReLU\\^2 experts"):
        runner.nemotron_model_config(dict(CONF, mlp_hidden_act="silu"))
    with pytest.raises(ValueError, match="M, \\* or E"):
        runner.nemotron_model_config(dict(
            CONF, hybrid_override_pattern="MEMEMEM-EMEMEMEM*EMEME"))


def test_costs_by_hand():
    # a sequence's states in one mixer: 128 x 64 x 128 x 4 B = 4.19 MB; all
    # 32 slots, 10 mixers, read and written: 2 x 1.342 GB
    cost = nemotron_h_cost.state_update_cost(CONF, 32)
    assert cost["bytes"] == 2 * 10 * 32 * 128 * 64 * 128 * 4
    assert round(cost["bytes"] / 1e9, 2) == 2.68
    assert cost["flops"] / 197e12 < cost["bytes"] / 819e9 / 20  # memory bound
    # one expert is 2 x 1024 x 2688 = 5.505M parameters = 11.01 MB: a sixth
    # of what the three-matrix count on the full width would say
    one = nemotron_h_cost.held_experts_cost(CONF, 1)
    assert one["bytes"] == 2 * 1024 * 2688 * 2
    assert round(one["bytes"] / 1e6, 2) == 11.01
    assert laguna_cost.held_experts_cost(CONF, 1)["bytes"] == 6 * one["bytes"]
    # a mixer's projections: 4096 x 18,560 in and 8192 x 4096 out, 10 mixers
    proj = nemotron_h_cost.projection_cost(CONF, 32)
    assert proj["bytes"] == 10 * (4096 * 18560 + 8192 * 4096) * 2
    assert round(proj["bytes"] / 1e9, 2) == 2.19
    assert proj["flops"] == 32 * proj["bytes"]  # 2 a weight and sequence


SCOPES = {"_decode_impl": {
    "ssm.project": ["fusion.1"], "ssm.conv": ["fusion.2"],
    "ssm.state": ["ssm_state_update.1", "fusion.3"], "ssm.norm": ["fusion.4"],
    "ssm.out": ["fusion.5"], "attn.gqa": ["fusion.6", "decode_attention.1"],
    "lmoe.down": ["fusion.7"], "lmoe.up": ["fusion.8"],
    "moe.shared": ["fusion.9"], "moe_router": ["fusion.10"],
    "moe_experts": ["ragged_dot_rows.1", "ragged_dot_rows.2"],
    "lm_head": ["fusion.11"], "sample": ["fusion.12"]}}
OPS = {"_decode_impl/fusion.1": 0.030, "_decode_impl/fusion.2": 0.004,
       "_decode_impl/ssm_state_update.1": 0.040,
       "_decode_impl/fusion.3": 0.002, "_decode_impl/fusion.4": 0.003,
       "_decode_impl/fusion.5": 0.013, "_decode_impl/fusion.6": 0.004,
       "_decode_impl/decode_attention.1": 0.002,
       "_decode_impl/fusion.7": 0.003, "_decode_impl/fusion.8": 0.002,
       "_decode_impl/fusion.9": 0.011, "_decode_impl/fusion.10": 0.006,
       "_decode_impl/ragged_dot_rows.1": 0.035,
       "_decode_impl/ragged_dot_rows.2": 0.035,
       "_decode_impl/fusion.11": 0.002, "_decode_impl/fusion.12": 0.0005,
       "_prefill_impl/fusion.3": 5.0}


def _ctx(toy=False, spans=True, scopes=SCOPES, prefill=True):
    dispatch = [["ray_tpu.engine.decode_dispatch", i * 1000, 10, 7,
                 {"active": 32, "rows": 32 * 900 + i}] for i in range(3)]
    trace = {"op_self_s": OPS, "programs": {
        "_decode_impl": {"count": 10, "total_s": 0.2, "p50_s": 0.02}}}
    if spans:
        trace["program_spans"] = {"spans": dispatch, "busy": {}, "window": {}}
    counters = {"reference_check": {"op_scopes": scopes},
                "engine": {"steps": 100, "tokens_out": 3200, "admitted": 0},
                "moe": {"moe_assignments": 704000, "moe_rows": 3200,
                        "layers": 10, "moe_assignments_held": 88100,
                        "moe_experts_reached": 48000,
                        "state_bytes_rewritten": 100 * 32 * 10 * 2 * 4255744}}
    if prefill:
        counters["ssm_prefill"] = {"ms_per_req": 21.5, "prefill_ms": 45.0}
    return {"cell": {"toy": toy, "config": CONF, "name": CELL},
            "trace": trace, "counters": counters,
            "device": {"kind": "TPU v5 lite"}}


@pytest.mark.parametrize("metric, want", [
    ("state_update_ms_per_decode_step", 4.2),
    ("state_project_ms_per_decode_step", 5.0),
    ("state_prefill_ms_per_req", 21.5),
    ("lmoe_latent_ms_per_decode_step", 0.5),
    ("gqa_attention_ms_per_decode_step", 0.6),
    ("shared_expert_ms_per_decode_step", 1.1),
    ("moe_router_ms_per_decode_step", 0.6),
    ("moe_expert_ms_per_decode_step", 7.0),
    ("head_sample_ms_per_decode_step", 0.25),
    ("moe_assignments_per_token", 22.0),
    ("moe_held_share", 88100 / 704000),
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


def test_the_new_readers_have_files_of_their_own():
    for metric in NEW:
        assert harness.load_reader(metric).__file__.endswith(
            os.path.join("layer_metrics", metric + ".py"))
    bench = harness.load_benchmark()
    mine = [m for m in bench["per_layer"] if CELL in m.get("workloads", [])]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert all(CELL in by_name[metric]["workloads"] for metric in NEW)
    assert {m["name"] for m in mine} >= set(JOINED)
    # (which entries are this cell's ALONE, and how many entries and cells
    # there are, is `test_per_layer_entries.py`'s and `test_contract.py`'s to
    # say: an entry is a question since PR 69, and the next cell's PR edits
    # no file the benchmark has, this one among them)
    # the prefill's count of three matrices on the full width is not this
    # cell's (the decode step's is `nemotron_h_cost`'s two in a latent:
    # `answers/serve_nemotron_h.py` hands `held_experts_roofline` to it here)
    assert CELL not in by_name["moe_experts_roofline"]["workloads"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONF["name"], "reason-short-in-long-out", 1)
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert config["reduced"] == CONF["reduced"]
    assert config["source"] == CONF["source"]
    for line in (config["why"], config["source"], cell["why"]):
        assert 1 <= len(line) <= 200 and line.isprintable() and line.isascii()
    tput = next(m for m in bench["end_to_end"]
                if m["name"] == "out_tokens_per_s")
    assert CELL in tput["workloads"] and tput["bound"] == 0.055
    assert all(m["moves"] in ("out_tokens_per_s", "setup_s") for m in mine)
    for m in mine:  # every entry finds its reader, a prefixed one its words'
        harness.load_reader(m["name"])


def test_roofline_shares_from_what_the_steps_hold_and_reach():
    ctx = _ctx()
    state = nemotron_h_cost.state_update_cost(CONF, 32)
    held = nemotron_h_cost.held_experts_cost(CONF, 480.0)
    got = {m: harness.load_reader(m).read(ctx) for m in (
        "state_update_roofline", "held_experts_roofline")}
    assert got["state_update_roofline"] == pytest.approx(
        100 * state["bytes"] / 819e9 / 4.2e-3)
    assert got["held_experts_roofline"] == pytest.approx(
        100 * held["bytes"] / 819e9 / 7.0e-3)
    assert all(0 < v < 100 for v in got.values()), got
    for m in got:  # a CPU has no published peak; the parent has no span,
        read = harness.load_reader(m).read  # no counter and no scope
        assert read(_ctx(toy=True)) is None
        other = dict(_ctx(), cell={"toy": False, "config": {}, "name": CELL})
        assert read(other) is None  # another family's keys: nothing to read
    read = harness.load_reader("state_update_roofline").read
    assert read(_ctx(spans=False)) is None and read(_ctx(scopes={})) is None
