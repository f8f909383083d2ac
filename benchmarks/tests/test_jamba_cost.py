"""The arithmetic of jamba_cost.py, by hand; the configuration file against
what ISSUE 60 states of it; the runner's model configuration; and each new
reader on a recorded fixture."""

import json
import os

import pytest

from benchmarks import harness, jamba_cost

CONF = harness.load_json(os.path.join(
    harness.HERE, "configs", "jamba2-3b-serve-whole.json"))
TRAFFIC = harness.load_json(harness.find_data_file(
    "traffic", "docreason-8k-in-long-out"))
CELL = "serve-mamba1-mqa-docreason-8k-in-long-out"
NEW = ("state_update_ms_per_decode_step", "state_update_roofline",
       "state_project_ms_per_decode_step", "mamba1_prefill_scan_ms_per_req",
       "mamba1_prefill_scan_roofline", "whole_prefill_ms_per_req")
JOINED = ("tput_decode_steps_per_s", "tput_slot_occupancy",
          "tput_device_idle_share", "tput_engine_host_ms_per_step",
          "tput_stream_yield_ms_per_token", "tput_decode_step_device_ms",
          "tput_engine_step_period_ms", "tput_pump_cpu_ms_per_step",
          "tput_pump_wait_ms_per_step", "tput_stream_items_per_call",
          "tput_proxy_forward_ms_per_item", "head_sample_ms_per_decode_step",
          "gqa_attention_ms_per_decode_step", "setup_cluster_start_s",
          "setup_serve_deploy_wait_s", "setup_worker_boot_s",
          "setup_backend_init_s", "setup_params_init_s",
          "setup_engine_build_s", "setup_program_trace_lower_s",
          "setup_program_first_run_s", "setup_attributed_share")


def test_the_configuration_is_the_published_one_uncut():
    assert CONF["reduced"] == []
    for key in ("source", "assumed", "deployment", "runner", "serve", "why"):
        assert CONF[key]
    assert "One v5e chip" in CONF["deployment"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(json.loads(line) for line in open(catalog)
                   if '"AI21-Jamba2-3B"' in line)
        assert CONF["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert CONF[key] == value, key
    assert (TRAFFIC["loop"], TRAFFIC["clients"], TRAFFIC["max_requests_per_s"],
            TRAFFIC["ramp_s"], TRAFFIC["repeat_every"],
            TRAFFIC["repeat_prompt_tokens"], TRAFFIC["warmup_prompt_tokens"],
            TRAFFIC["reference_prompt_tokens"],
            TRAFFIC["reference_new_tokens"], TRAFFIC["trace_after_s"],
            TRAFFIC["trace_s"], TRAFFIC["path"]) == (
        "closed", 16, 1.5, 6.0, 8, 6000, [6000], 4800, 8, 12.0, 2.0,
        "/llm/generate_stream")
    assert TRAFFIC["prompt_tokens"] == {"dist": "uniform", "min": 4112,
                                        "max": 8000}
    # ISSUE 60's one adjustment: multiples of 256 inside 1,536-3,584
    assert TRAFFIC["new_tokens"] % 256 == 0
    assert 1536 <= TRAFFIC["new_tokens"] <= 3584
    assert CONF["serve"]["cache_slots"] == TRAFFIC["clients"]
    assert CONF["serve"]["max_len"] == 12288 >= 8192 + 3584


def test_the_runner_builds_the_pattern_from_the_file():
    runner = harness.load_module("runners", "serve_jamba")
    cfg = runner.jamba_model_config(CONF)
    assert cfg.num_params() == 3_029_337_472  # ISSUE 60's count, key by key
    assert cfg.layer_kinds == (("ssm1", "mlp") * 7 + ("gqa", "mlp")
                               + ("ssm1", "mlp") * 6) * 2
    assert (cfg.layers_of("ssm1"), cfg.layers_of("gqa"),
            cfg.layers_of("mlp")) == (26, 2, 28)
    assert (cfg.heads, cfg.kv_heads, cfg.hd, cfg.ssm_heads * cfg.ssm_head_dim,
            cfg.ssm_state, cfg.ssm_conv, cfg.ssm_dt_rank, cfg.mlp_hidden,
            cfg.vocab_size, cfg.tie_embeddings) == (
        20, 1, 128, 5120, 16, 4, 160, 8192, 65536, True)
    kept = {k.fields: (k.layers, k.rows, k.shape) for k in cfg.kept(12288)}
    assert kept == {("k", "v"): (2, 12288, (1, 128)),
                    ("mat",): (26, None, (16, 5120)),
                    ("conv",): (26, None, (3 * 5120,))}
    tcfg = runner.jamba_model_config(
        runner.toy_config(dict(CONF, **harness.TOY_MODEL)))
    assert (tcfg.layers, tcfg.layers_of("ssm1"), tcfg.layers_of("gqa"),
            tcfg.heads, tcfg.kv_heads, tcfg.hd, tcfg.ssm_dt_rank) == (
        16, 6, 2, 4, 1, 32, 10)
    with pytest.raises(ValueError, match="num_experts 1"):
        runner.jamba_model_config(dict(CONF, num_experts=16))
    with pytest.raises(ValueError, match="the head tied"):
        runner.jamba_model_config(dict(CONF, tie_word_embeddings=False))


def test_costs_by_hand():
    assert jamba_cost.mixers(CONF) == 26 and jamba_cost.channels(CONF) == 5120
    # a sequence's state in one mixer: 5120 x 16 x 4 B = 327,680 B; 16 slots,
    # 26 mixers, read and written, and the rates once a mixer
    cost = jamba_cost.state_update_cost(CONF, 16)
    assert cost["bytes"] == 2 * 26 * 16 * 327680 + 26 * 327680
    assert round(cost["bytes"] / 1e9, 3) == 0.281
    assert cost["flops"] == 26 * 16 * 5120 * 16 * 6
    # a prompt of 6,000 rows in one mixer: dt, dt x, o 6000 x 5120 x 4 B
    # each, B and C 6000 x 16 x 4 B each, the rates and the state in and out
    scan = jamba_cost.scan_cost(CONF, 6000)
    a_mixer = 3 * 6000 * 5120 * 4 + 2 * 6000 * 16 * 4 + 3 * 327680
    assert scan["bytes"] == 26 * a_mixer
    assert round(scan["bytes"] / 1e9, 2) == 9.63
    assert scan["flops"] == 26 * 6000 * 5120 * 16 * 6  # 12.8 G updates x 6
    # against the matrix unit's peak they are nothing: the share is of HBM
    assert scan["flops"] / 197e12 < scan["bytes"] / 819e9


SCOPES = {"_decode_impl": {
    "ssm1.project": ["fusion.1"], "ssm1.conv": ["fusion.2"],
    "ssm1.state": ["selective_state_update.1", "fusion.3"],
    "ssm1.gate": ["fusion.4"], "ssm1.out": ["fusion.5"],
    "attn.gqa": ["fusion.6", "decode_attention.1"], "mlp": ["fusion.7"],
    "lm_head": ["fusion.11"], "sample": ["fusion.12"]}}
OPS = {"_decode_impl/fusion.1": 0.030, "_decode_impl/fusion.2": 0.004,
       "_decode_impl/selective_state_update.1": 0.005,
       "_decode_impl/fusion.3": 0.001, "_decode_impl/fusion.4": 0.003,
       "_decode_impl/fusion.5": 0.013, "_decode_impl/fusion.6": 0.004,
       "_decode_impl/decode_attention.1": 0.002,
       "_decode_impl/fusion.7": 0.040, "_decode_impl/fusion.11": 0.002,
       "_decode_impl/fusion.12": 0.0005, "_prefill_impl/fusion.3": 5.0}


def _ctx(toy=False, spans=True, scopes=SCOPES, prefill=True):
    dispatch = [["ray_tpu.engine.decode_dispatch", i * 1000, 10, 7,
                 {"active": 16, "rows": 16 * 9000 + i}] for i in range(3)]
    trace = {"op_self_s": OPS, "programs": {
        "_decode_impl": {"count": 10, "total_s": 0.1, "p50_s": 0.01}}}
    if spans:
        trace["program_spans"] = {"spans": dispatch, "busy": {}, "window": {}}
    counters = {"reference_check": {"op_scopes": scopes}}
    if prefill:
        counters["mamba1_prefill"] = {
            "ms_per_req": 520.0, "bucket": 8192,
            "by_scope_ms": {"ssm1.prefill_scan": 130.0, "mlp": 200.0}}
    return {"cell": {"toy": toy, "config": CONF, "traffic": TRAFFIC,
                     "name": CELL},
            "trace": trace, "counters": counters,
            "device": {"kind": "TPU v5 lite"}}


@pytest.mark.parametrize("metric, want", [
    ("state_update_ms_per_decode_step", 0.6),
    ("state_project_ms_per_decode_step", 5.0),
    ("mamba1_prefill_scan_ms_per_req", 130.0),
    ("whole_prefill_ms_per_req", 520.0),
    ("gqa_attention_ms_per_decode_step", 0.6),
    ("head_sample_ms_per_decode_step", 0.25),
    ("tput_decode_step_device_ms", 10.0),
])
def test_each_reader_on_a_recorded_run(metric, want):
    read = harness.load_reader(metric).read
    assert read(_ctx()) == pytest.approx(want)
    # the parent of the PR has no such scope, counter or trace: nothing is
    # read, nothing raises, the line leaves the metric out
    bare = {"cell": {"toy": False, "config": CONF, "traffic": TRAFFIC},
            "trace": {}, "counters": {}, "device": {"kind": "TPU v5 lite"}}
    assert read(bare) is None


def test_roofline_shares_of_the_hbm_bound():
    ctx = _ctx()
    state = jamba_cost.state_update_cost(CONF, 16)
    scan = jamba_cost.scan_cost(CONF, 6000)
    got = {m: harness.load_reader(m).read(ctx) for m in (
        "state_update_roofline", "mamba1_prefill_scan_roofline")}
    assert got["state_update_roofline"] == pytest.approx(
        100 * state["bytes"] / 819e9 / 0.6e-3)
    assert got["mamba1_prefill_scan_roofline"] == pytest.approx(
        100 * scan["bytes"] / 819e9 / 130e-3)
    assert all(0 < v < 100 for v in got.values()), got
    for m in got:  # a CPU has no published peak; the parent has no span,
        read = harness.load_reader(m).read  # no counter and no scope
        assert read(_ctx(toy=True)) is None
        other = dict(_ctx(), cell={"toy": False, "config": {},
                                   "traffic": TRAFFIC, "name": CELL})
        assert read(other) is None  # another family's keys: nothing to read
    read = harness.load_reader("state_update_roofline").read
    assert read(_ctx(spans=False)) is None and read(_ctx(scopes={})) is None
    read = harness.load_reader("mamba1_prefill_scan_roofline").read
    assert read(_ctx(prefill=False)) is None


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
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONF["name"], "docreason-8k-in-long-out", 1)
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert config["reduced"] == CONF["reduced"] == []
    assert config["source"] == CONF["source"]
    for line in (config["why"], config["source"], cell["why"]):
        assert 1 <= len(line) <= 200 and line.isprintable() and line.isascii()
    tput = next(m for m in bench["end_to_end"]
                if m["name"] == "out_tokens_per_s")
    assert CELL in tput["workloads"] and tput["bound"] == 0.055
    assert all(m["moves"] in ("out_tokens_per_s", "setup_s") for m in mine)
    for m in mine:  # every entry finds its reader, a prefixed one its words'
        harness.load_reader(m["name"])
