"""The arithmetic of longcat_cost.py, by hand; the configuration file against
what ISSUE 44 states of it; the runner's model configuration; and each new
reader on a recorded fixture."""

import os

import pytest

from benchmarks import harness, longcat_cost

CONF = harness.load_json(os.path.join(
    harness.HERE, "configs", "longcat-flash-serve-ep32-d4.json"))
CELL = "serve-scmoe-mla-agent-long-ctx"
ROLLOUT = "serve-kda-mla-rollout-long-out"
OWN = ("mla_attention_ms_per_decode_step",
       "mla_attention_roofline", "scmoe_dense_ms_per_decode_step",
       "scmoe_dense_roofline", "moe_zero_share",
       "moe_real_experts_per_token_max_over_mean",
       "moe_rows_gathered_per_computed", "whole_prefill_ms_per_req")
NEW = OWN + ("mla_project_ms_per_decode_step",)


def test_the_configuration_is_the_published_one_but_for_its_three_cuts():
    assert CONF["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert CONF["published"] == {"num_layers": 28, "n_routed_experts": 512,
                                 "vocab_size": 131072}
    assert (CONF["num_layers"], CONF["n_routed_experts"],
            CONF["vocab_size"]) == (4, 16, 16384)
    assert (CONF["zero_expert_num"], CONF["moe_topk"]) == (256, 12)
    assert (CONF["hidden_size"], CONF["ffn_hidden_size"],
            CONF["expert_ffn_hidden_size"], CONF["num_attention_heads"],
            CONF["kv_lora_rank"], CONF["q_lora_rank"],
            CONF["qk_nope_head_dim"], CONF["qk_rope_head_dim"],
            CONF["v_head_dim"], CONF["routed_scaling_factor"],
            CONF["rope_theta"]) == (
        6144, 12288, 2048, 64, 512, 1536, 128, 64, 128, 6, 10000000)
    for key in ("source", "assumed", "deployment", "runner", "serve"):
        assert CONF[key]
    assert "32 chips" in CONF["deployment"]
    assert "7 pipeline stages" in CONF["deployment"]
    # every number of the catalog's entry, but the three that are reduced
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        import json

        row = next(json.loads(line) for line in open(catalog)
                   if '"LongCat-Flash-Chat"' in line)
        assert CONF["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in CONF["reduced"]:
                assert CONF[key] == value, key
    traffic = harness.load_json(harness.find_data_file(
        "traffic", "agent-long-ctx"))
    assert (traffic["loop"], traffic["clients"], traffic["new_tokens"],
            traffic["max_requests_per_s"], traffic["ramp_s"],
            traffic["repeat_every"], traffic["repeat_prompt_tokens"],
            traffic["warmup_prompt_tokens"],
            traffic["reference_prompt_tokens"],
            traffic["reference_new_tokens"], traffic["trace_s"],
            traffic["path"]) == (
        "closed", 32, 1024, 6.0, 6.0, 40, 3000, [3000], 2400, 8, 2.0,
        "/llm/generate_stream")
    assert traffic["prompt_tokens"] == {"dist": "uniform", "min": 2064,
                                        "max": 4000}
    assert traffic["clients"] == CONF["serve"]["cache_slots"] == 32
    assert traffic["prompt_tokens"]["max"] + traffic["new_tokens"] \
        <= CONF["serve"]["max_len"] == 5120


def test_the_runner_builds_the_double_layers_from_the_file():
    runner = harness.load_module("runners", "serve_longcat")
    cfg = runner.longcat_model_config(CONF)
    assert (cfg.layer_kinds, cfg.lead_kind, cfg.tail_kinds) == (
        ("scmoe",), "", ())
    assert (cfg.layers, cfg.periods, cfg.sparse_layers, cfg.latent_layers,
            cfg.layers_of("mla")) == (4, 4, 4, 8, 0)
    assert (cfg.heads, cfg.hd, cfg.mla_latent, cfg.mla_rope_dim,
            cfg.mla_q_rank, cfg.latent_row, cfg.mla_rotate) == (
        64, 128, 512, 64, 1536, 640, True)
    assert cfg.mla_scales == (2.0, pytest.approx(12 ** 0.5))
    assert (cfg.num_experts, cfg.zero_experts, cfg.router_outputs,
            cfg.experts_held, cfg.experts_per_token, cfg.routed_scale,
            cfg.norm_topk_prob, cfg.router_score) == (
        512, 256, 768, (0, 16), 12, 6.0, False, "softmax")
    assert cfg.keeps == ("latent",) and not cfg.stateful
    # ISSUE 44's arithmetic: 5.17B parameters held, 10.35 GB in bfloat16
    assert round(cfg.num_params() / 1e9, 2) == 5.17
    assert round(cfg.num_params() * 2 / 1e9, 2) == 10.35
    # --toy keeps every mechanism at debug widths
    tcfg = runner.longcat_model_config(
        runner.toy_config(dict(CONF, **harness.TOY_MODEL)))
    assert (tcfg.layers, tcfg.heads, tcfg.hd, tcfg.mla_q_rank,
            tcfg.num_experts, tcfg.zero_experts, tcfg.experts_held,
            tcfg.experts_per_token, tcfg.mla_rotate) == (
        3, 4, 16, 24, 16, 8, (0, 4), 4, True)
    for change in (dict(zero_expert_type="copy"), dict(router_bias=True),
                   dict(rope_scaling={"type": "yarn", "factor": 4}),
                   dict(q_lora_rank=None), dict(v_head_dim=192)):
        with pytest.raises(ValueError, match="models/longcat.py runs"):
            runner.longcat_model_config(dict(CONF, **change))


def test_costs_by_hand():
    # a position's latent row: 576 x 2 B = 1,152 B a sublayer, 8 sublayers
    rows = longcat_cost.latent_attention_cost(CONF, 32 * 5120)
    assert rows["bytes"] == 8 * 32 * 5120 * 576 * 2
    assert round(rows["bytes"] / 1e9, 2) == 1.51
    one = longcat_cost.latent_attention_cost(CONF, 1)
    assert one["flops"] == 8 * 64 * (2 * 576 + 2 * 512)
    # 64 heads on a row of 1,152 B: 121 operations a byte, under the chip's
    # ridge of 240: still memory bound
    assert round(one["flops"] / one["bytes"]) == 121
    assert one["flops"] / 197e12 < one["bytes"] / 819e9
    # one dense MLP: 3 x 6144 x 12288 = 226.5M parameters, 453 MB; 8 of them
    dense = longcat_cost.dense_cost(CONF, 32)
    assert dense["bytes"] == 8 * 3 * 6144 * 12288 * 2
    assert round(dense["bytes"] / 1e9, 2) == 3.62
    assert dense["flops"] == 2 * 32 * 8 * 3 * 6144 * 12288
    assert dense["flops"] / 197e12 < dense["bytes"] / 819e9  # memory bound
    # one expert is 3 x 6144 x 2048 = 37.75M parameters = 75.5 MB, under the
    # name the shared reader reads its width by
    from benchmarks import laguna_cost

    assert round(laguna_cost.held_experts_cost(CONF, 1)["bytes"] / 1e6,
                 1) == 75.5
    assert CONF["moe_intermediate_size"] == CONF["expert_ffn_hidden_size"]


SCOPES = {"_decode_impl": {
    "mla.project": ["fusion.1"], "mla.rotate": ["fusion.2"],
    "mla.attend": ["latent_decode_attention.1", "latent_decode_attention.2"],
    "mla.out": ["fusion.3"], "scmoe.dense": ["fusion.4", "fusion.5"],
    "moe_router": ["fusion.6"], "moe.zero": ["fusion.7"],
    "moe_experts": ["ragged-dot-none.1"], "lm_head": ["fusion.8"],
    "sample": ["fusion.9"]}}
OPS = {"_decode_impl/fusion.1": 0.010, "_decode_impl/fusion.2": 0.002,
       "_decode_impl/latent_decode_attention.1": 0.012,
       "_decode_impl/latent_decode_attention.2": 0.012,
       "_decode_impl/fusion.3": 0.004, "_decode_impl/fusion.4": 0.030,
       "_decode_impl/fusion.5": 0.030, "_decode_impl/fusion.6": 0.003,
       "_decode_impl/fusion.7": 0.001,
       "_decode_impl/ragged-dot-none.1": 0.020,
       "_decode_impl/fusion.8": 0.003, "_decode_impl/fusion.9": 0.0005}


def _ctx(toy=False, config=CONF):
    dispatch = [["ray_tpu.engine.decode_dispatch", i * 1000, 10, 7,
                 {"active": 32, "rows": 32 * 3500 + i}] for i in range(3)]
    trace = {"op_self_s": OPS, "programs": {
        "_decode_impl": {"count": 10, "total_s": 0.13, "p50_s": 0.013}},
        "program_spans": {"spans": dispatch, "busy": {}, "window": {}}}
    counters = {"reference_check": {"op_scopes": SCOPES},
                "engine": {"steps": 90, "tokens_out": 3200, "admitted": 10},
                "moe": {"moe_assignments": 153600, "moe_rows": 3200,
                        "layers": 4, "moe_assignments_held": 3200,
                        "moe_assignments_zero": 51200,
                        "moe_assignments_absent": 99200,
                        "moe_experts_reached": 1800,
                        "moe_rows_gathered": 160000, "moe_routed_most": 1200},
                "longcat_prefill": {"ms_per_req": 240.0}}
    return {"cell": {"toy": toy, "config": config, "name": CELL},
            "trace": trace, "counters": counters,
            "device": {"kind": "TPU v5 lite"}}


@pytest.mark.parametrize("metric, want", [
    ("mla_attention_ms_per_decode_step", 2.4),
    ("mla_project_ms_per_decode_step", 1.2),
    ("scmoe_dense_ms_per_decode_step", 6.0),
    ("whole_prefill_ms_per_req", 240.0),
    ("moe_zero_share", 100 * 51200 / 153600),
    # the most a row chose, 12 a program; the mean 8 a row and layer
    ("moe_real_experts_per_token_max_over_mean", 12 / 8),
    ("moe_rows_gathered_per_computed", 50.0),
    ("moe_router_ms_per_decode_step", 0.3),
    ("moe_expert_ms_per_decode_step", 2.0),
    ("head_sample_ms_per_decode_step", 0.35),
    ("moe_assignments_per_token", 12.0),
    ("moe_held_share", 3200 / 153600),
    ("tput_decode_step_device_ms", 13.0),
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
    rows = longcat_cost.latent_attention_cost(CONF, 32 * 3500 + 1)
    dense = longcat_cost.dense_cost(CONF, 32)
    got = {m: harness.load_reader(m).read(ctx) for m in (
        "mla_attention_roofline", "scmoe_dense_roofline",
        "held_experts_roofline")}
    assert got["mla_attention_roofline"] == pytest.approx(
        100 * rows["bytes"] / 819e9 / 2.4e-3)
    assert got["scmoe_dense_roofline"] == pytest.approx(
        100 * dense["bytes"] / 819e9 / 6.0e-3)
    assert got["held_experts_roofline"] == pytest.approx(
        100 * 20 * 3 * 6144 * 2048 * 2 / 819e9 / 2.0e-3)
    assert all(0 < v <= 100 for v in got.values())
    # a toy run has no published peak; another family's configuration has no
    # double layers to count
    assert harness.load_reader("scmoe_dense_roofline").read(
        _ctx(toy=True)) is None
    other = harness.load_json(os.path.join(
        harness.HERE, "configs", "kimi-linear-48b-a3b-serve-ep16.json"))
    assert harness.load_reader("scmoe_dense_roofline").read(
        _ctx(config=other)) is None
    # the shared entries answer for whichever configuration the cell names
    # (`costs.py`): that one's 7 latent layers' rows, not 8 sublayers'
    assert harness.load_reader("mla_attention_roofline").read(
        _ctx(config=other)) == pytest.approx(
        got["mla_attention_roofline"] * 7 / 8)


def test_every_new_entry_has_a_reader_a_unit_and_a_cell():
    for metric in NEW:
        assert harness.load_reader(metric).__file__.endswith(
            os.path.join("layer_metrics", metric + ".py"))
    bench = harness.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for metric in NEW:
        entry = by_name[metric]
        assert entry["unit"] and entry["layer"] == "model"
        assert entry["moves"] == "out_tokens_per_s"
        assert CELL in entry["workloads"]
    assert {ROLLOUT, CELL} <= set(
        by_name["mla_project_ms_per_decode_step"]["workloads"])
    mine = [m for m in bench["per_layer"] if CELL in m.get("workloads", [])]
    assert len(mine) > len(NEW)  # the shared readers' lists, joined
    # (which entries are this cell's ALONE, and how many entries and cells
    # there are, is `test_per_layer_entries.py`'s and `test_contract.py`'s to
    # say: an entry is a question since PR 69, and the next cell's PR edits
    # no file the benchmark has, this one among them)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "longcat-flash-serve-ep32-d4", "agent-long-ctx", 1)
    # the contract's one line of at most 200 printable characters: the
    # driver refuses the whole file for one `why` past it
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    for line in (config["why"], config["source"], cell["why"]):
        assert 1 <= len(line) <= 200 and line.isprintable() and line.isascii()
    tput = next(m for m in bench["end_to_end"]
                if m["name"] == "out_tokens_per_s")
    assert CELL in tput["workloads"] and tput["bound"] == 0.055
