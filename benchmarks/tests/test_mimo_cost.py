"""The arithmetic of mimo_cost.py, by hand at the published widths; the
configuration file against what ISSUE 58 states of it; the runner's model
configuration; and each new reader on a recorded fixture."""

import json
import os

import pytest

from benchmarks import harness, laguna_cost, mimo_cost

CONF = harness.load_json(os.path.join(
    harness.HERE, "configs", "mimo-v2-flash-serve-ep16-d11.json"))
CELL = "serve-sink-window-moe-agent-8k-in-2k-out"
NEW = ("window_attention_roofline", "full_attention_roofline",
       "mimo_prefill_attention_roofline", "whole_prefill_ms_per_req")
JOINED = ("tput_decode_steps_per_s", "tput_slot_occupancy",
          "tput_device_idle_share", "tput_engine_host_ms_per_step",
          "tput_stream_yield_ms_per_token", "tput_decode_step_device_ms",
          "tput_engine_step_period_ms", "tput_pump_cpu_ms_per_step",
          "tput_pump_wait_ms_per_step", "tput_stream_items_per_call",
          "tput_proxy_forward_ms_per_item", "moe_expert_ms_per_decode_step",
          "moe_assignments_per_token", "moe_router_ms_per_decode_step",
          "moe_held_share", "head_sample_ms_per_decode_step",
          "window_attention_ms_per_decode_step",
          "full_attention_ms_per_decode_step", "held_experts_roofline",
          "setup_cluster_start_s", "setup_serve_deploy_wait_s",
          "setup_worker_boot_s", "setup_backend_init_s",
          "setup_params_init_s", "setup_engine_build_s",
          "setup_program_trace_lower_s", "setup_program_first_run_s",
          "setup_attributed_share")


def test_the_configuration_is_the_published_one_but_for_its_three_cuts():
    assert CONF["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    assert CONF["published"] == {"num_hidden_layers": 48,
                                 "n_routed_experts": 256, "vocab_size": 152576}
    assert (CONF["num_hidden_layers"], CONF["n_routed_experts"],
            CONF["vocab_size"]) == (11, 16, 19072)
    assert CONF["vocab_size"] * 8 == CONF["published"]["vocab_size"]
    # every width is the published one
    assert (CONF["hidden_size"], CONF["num_attention_heads"], CONF["head_dim"],
            CONF["v_head_dim"], CONF["num_key_value_heads"],
            CONF["swa_num_key_value_heads"], CONF["sliding_window"],
            CONF["intermediate_size"], CONF["moe_intermediate_size"],
            CONF["num_experts_per_tok"], CONF["attention_value_scale"],
            CONF["partial_rotary_factor"], CONF["rope_theta"],
            CONF["swa_rope_theta"]) == (
        4096, 64, 192, 128, 4, 8, 128, 16384, 2048, 8, 0.707, 0.334, 5000000,
        10000)
    whole = CONF["hybrid_layer_pattern"]
    assert len(whole) == 48 and whole.count(0) == 9 and whole.count(1) == 39
    assert [i for i, k in enumerate(whole) if not k] == [
        0, 5, 11, 17, 23, 29, 35, 41, 47]  # no whole periods
    assert whole[:11] == [0, 1, 1, 1, 1, 0, 1, 1, 1, 1, 1]  # 2 full : 9 window
    assert CONF["moe_layer_freq"] == [0] + [1] * 47
    assert "multi_token_prediction" in CONF["left_out"]
    for key in ("source", "assumed", "deployment", "runner", "serve"):
        assert CONF[key]
    for said in ("16 v5e chips", "4 pipeline stages of 16", "5.42B",
                 "10.84 GB", "16 times its share"):
        assert said in CONF["deployment"], said
    # every number of the catalog's entry, but those that are reduced
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(json.loads(line) for line in open(catalog)
                   if '"name": "MiMo-V2-Flash"' in line)
        assert CONF["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in CONF["reduced"]:
                assert CONF[key] == value, key
            else:
                assert CONF["published"][key] == value, key
    traffic = harness.load_json(harness.find_data_file(
        "traffic", "agent-8k-in-2k-out"))
    assert (traffic["loop"], traffic["clients"], traffic["new_tokens"],
            traffic["max_requests_per_s"], traffic["ramp_s"],
            traffic["repeat_every"], traffic["repeat_prompt_tokens"],
            traffic["warmup_prompt_tokens"],
            traffic["reference_prompt_tokens"],
            traffic["reference_new_tokens"], traffic["trace_after_s"],
            traffic["trace_s"], traffic["path"]) == (
        "closed", 32, 2048, 6.0, 6.0, 40, 6000, [6000], 4800, 8, 12.0, 2.0,
        "/llm/generate_stream")
    assert traffic["prompt_tokens"] == {"dist": "uniform", "min": 4112,
                                        "max": 8000}
    assert CONF["serve"]["cache_slots"] == traffic["clients"]
    # the longest prompt and its answer fit a slot
    assert traffic["prompt_tokens"]["max"] + traffic["new_tokens"] \
        <= CONF["serve"]["max_len"] == 10240


def test_the_runner_builds_the_pattern_from_the_file():
    from ray_tpu.models import laguna

    runner = harness.load_module("runners", "serve_mimo")
    cfg = runner.mimo_model_config(CONF)
    assert cfg.lead_kind == "" and cfg.tail_kinds == ()
    assert [int(k == "window") for k in cfg.kinds] \
        == CONF["hybrid_layer_pattern"][:11]
    assert (cfg.layers, cfg.full_layers, cfg.window_layers,
            cfg.sparse_layers) == (11, 2, 9, 10)
    assert (cfg.heads, cfg.window_heads, cfg.kv_heads, cfg.window_kv_heads,
            cfg.hd, cfg.value_dim, cfg.window, cfg.window_sink,
            cfg.value_scale, cfg.head_gate, cfg.shared_expert_hidden) == (
        64, 64, 4, 8, 192, 128, 128, True, 0.707, False, 0)
    assert (cfg.num_experts, cfg.experts_held, cfg.experts_per_token,
            cfg.routed_scale, cfg.router_score, cfg.norm_topk_prob,
            cfg.mlp_hidden, cfg.dense_mlp_hidden) == (
        256, (0, 16), 8, 1.0, "sigmoid", True, 2048, 16384)
    # 64 of a head's 192 dimensions turn, in both kinds, by the kind's theta
    assert laguna.rope_table(cfg, "full")[0] == 64 \
        == laguna.rope_table(cfg, "window")[0]
    assert laguna.rope_table(cfg, "full")[1][1] \
        != laguna.rope_table(cfg, "window")[1][1]
    assert laguna.key_row(cfg) == 256
    kept = {k.fields: (k.layers, k.rows, k.shapes) for k in cfg.kept(10240)}
    assert kept == {
        ("k", "v"): (2, 10240, ((8, 128), (4, 128))),
        ("ring_k", "ring_v"): (9, 128, ((16, 128), (8, 128)))}
    # ISSUE 58's arithmetic: 5.42B parameters held, 10.84 GB in bfloat16
    assert cfg.num_params() == 5422283840
    assert round(cfg.num_params() / 1e9, 2) == 5.42
    assert round(cfg.num_params() * 2 / 1e9, 2) == 10.84
    # --toy keeps every mechanism at debug widths
    tcfg = runner.mimo_model_config(
        runner.toy_config(dict(CONF, **harness.TOY_MODEL)))
    assert (tcfg.layers, tcfg.full_layers, tcfg.window_layers, tcfg.heads,
            tcfg.kv_heads, tcfg.window_kv_heads, tcfg.hd, tcfg.value_dim,
            tcfg.window, tcfg.num_experts, tcfg.experts_held,
            tcfg.experts_per_token) == (7, 2, 5, 8, 2, 4, 24, 16, 8, 8,
                                        (0, 4), 2)
    for change in (dict(add_full_attention_sink_bias=True),
                   dict(n_shared_experts=1), dict(scoring_func="softmax"),
                   dict(swa_head_dim=128), dict(attention_bias=True)):
        with pytest.raises(ValueError, match="list form runs"):
            runner.mimo_model_config(dict(CONF, **change))


def test_costs_by_hand():
    # a position of a full layer: 4 KV heads x (192 + 128) x 2 B = 2,560 B;
    # of a window layer: 8 x 320 x 2 = 5,120 B (ISSUE 58's cache arithmetic)
    one = mimo_cost.decode_attention_cost(CONF, "full", 1)
    assert one["bytes"] == 2 * 2560
    ring = mimo_cost.decode_attention_cost(CONF, "window", 1)
    assert ring["bytes"] == 9 * 5120
    # 32 sequences of 7,500 positions: the two full layers read 1.23 GB
    full = mimo_cost.decode_attention_cost(CONF, "full", 32 * 7500)
    assert round(full["bytes"] / 1e9, 2) == 1.23
    assert full["flops"] == 2 * 32 * 7500 * 64 * 320 * 2
    # 32 full rings of 128: 0.19 GB
    rings = mimo_cost.decode_attention_cost(CONF, "window", 32 * 128)
    assert round(rings["bytes"] / 1e9, 2) == 0.19
    for cost in (full, rings):  # memory bound
        assert cost["flops"] / 197e12 < cost["bytes"] / 819e9
    # a prefill of 8,192 positions: the causal half of 64 heads' two products
    # in each of the two full layers = 2.75 TFLOP; q, k, v, o once
    pre = mimo_cost.prefill_attention_cost(CONF, 8192)
    assert pre["flops"] == 2 * 64 * (8192 * 8193 // 2) * 320 * 2
    assert round(pre["flops"] / 1e12, 2) == 2.75
    assert pre["bytes"] == 2 * 8192 * (64 + 4) * 320 * 2
    assert pre["flops"] / 197e12 > 10 * pre["bytes"] / 819e9  # compute bound
    # one held expert is 3 x 4096 x 2048 parameters = 50.33 MB, by the
    # reader this cell shares with the other held-expert families
    assert laguna_cost.held_experts_cost(CONF, 1)["bytes"] == 3 * 4096 * 2048 * 2
    assert mimo_cost.layers_of(CONF, "full") == 2
    assert mimo_cost.layers_of(CONF, "window") == 9


SCOPES = {"_decode_impl": {
    "attn.window": ["fusion.1", "decode_attention.3"],
    "attn.full": ["fusion.2", "decode_attention.1"],
    "moe_router": ["fusion.3"], "mlp": ["fusion.4"],
    "moe_experts": ["ragged_dot_rows.1", "ragged_dot_rows.2"],
    "lm_head": ["fusion.5"], "sample": ["fusion.6"]}}
OPS = {"_decode_impl/fusion.1": 0.020, "_decode_impl/decode_attention.3": 0.006,
       "_decode_impl/fusion.2": 0.004, "_decode_impl/decode_attention.1": 0.020,
       "_decode_impl/fusion.3": 0.005, "_decode_impl/fusion.4": 0.006,
       "_decode_impl/ragged_dot_rows.1": 0.040,
       "_decode_impl/ragged_dot_rows.2": 0.030,
       "_decode_impl/fusion.5": 0.002, "_decode_impl/fusion.6": 0.0005}


def _ctx(toy=False, spans=True, scopes=SCOPES, prefill=True):
    dispatch = [["ray_tpu.engine.decode_dispatch", i * 1000, 10, 7,
                 {"active": 32, "rows": 32 * 7000 + i,
                  "window_rows": 32 * 128}] for i in range(3)]
    trace = {"op_self_s": OPS, "programs": {
        "_decode_impl": {"count": 10, "total_s": 0.15, "p50_s": 0.015}}}
    if spans:
        trace["program_spans"] = {"spans": dispatch, "busy": {}, "window": {}}
    counters = {"reference_check": {"op_scopes": scopes},
                "engine": {"steps": 100, "tokens_out": 3200, "admitted": 0},
                "moe": {"moe_assignments": 256000, "moe_rows": 3200,
                        "layers": 10, "moe_assignments_held": 16100,
                        "moe_experts_reached": 100 * 96}}
    if prefill:
        counters["mimo_prefill"] = {"ms_per_req": 230.0, "flash_ms": 24.0,
                                    "bucket": 8192, "by_scope_ms": {}}
    return {"cell": {"toy": toy, "config": CONF, "name": CELL},
            "trace": trace, "counters": counters,
            "device": {"kind": "TPU v5 lite"}}


@pytest.mark.parametrize("metric, want", [
    ("whole_prefill_ms_per_req", 230.0),
    ("window_attention_ms_per_decode_step", 2.6),
    ("full_attention_ms_per_decode_step", 2.4),
    ("moe_router_ms_per_decode_step", 0.5),
    ("moe_expert_ms_per_decode_step", 7.0),
    ("head_sample_ms_per_decode_step", 0.25),
    ("moe_assignments_per_token", 8.0),
    ("moe_held_share", 16100 / 256000),
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


def test_roofline_shares_from_what_the_steps_hold():
    ctx = _ctx()
    got = {m: harness.load_reader(m).read(ctx) for m in NEW[:3] + (
        "held_experts_roofline",)}
    full = mimo_cost.decode_attention_cost(CONF, "full", 32 * 7000 + 1)
    ring = mimo_cost.decode_attention_cost(CONF, "window", 32 * 128)
    pre = mimo_cost.prefill_attention_cost(CONF, 8192)
    assert got["full_attention_roofline"] == pytest.approx(
        100 * full["bytes"] / 819e9 / 2.4e-3)
    assert got["window_attention_roofline"] == pytest.approx(
        100 * ring["bytes"] / 819e9 / 2.6e-3)
    assert got["mimo_prefill_attention_roofline"] == pytest.approx(
        100 * pre["flops"] / 197e12 / 24e-3)
    assert got["held_experts_roofline"] == pytest.approx(
        100 * 96 * 3 * 4096 * 2048 * 2 / 819e9 / 7.0e-3)
    assert all(0 < v < 100 for v in got.values()), got
    for m in NEW[:3]:  # a CPU has no published peak; the parent has no span,
        read = harness.load_reader(m).read  # no capture and no scope
        assert read(_ctx(toy=True)) is None
        assert read(_ctx(spans=False, prefill=False)) is None
        assert read(_ctx(scopes={}, prefill=False)) is None


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
    # no shared expert in this configuration: not this cell's
    assert CELL not in by_name["shared_expert_ms_per_decode_step"]["workloads"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONF["name"], "agent-8k-in-2k-out", 1)
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert config["reduced"] == CONF["reduced"]
    assert config["source"] == CONF["source"]
    assert config["file"] == "benchmarks/configs/" + CONF["name"] + ".json"
    for line in (config["why"], config["source"], cell["why"]):
        assert 1 <= len(line) <= 200 and line.isprintable() and line.isascii()
    tput = next(m for m in bench["end_to_end"]
                if m["name"] == "out_tokens_per_s")
    assert CELL in tput["workloads"] and tput["bound"] == 0.055
    assert all(m["moves"] in ("out_tokens_per_s", "setup_s") for m in mine)
    for m in mine:  # every entry finds its reader, a prefixed one its words'
        harness.load_reader(m["name"])
