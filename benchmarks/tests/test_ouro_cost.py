"""The arithmetic of ouro_cost.py, by hand; the configuration file against
what ISSUE 64 states of it; the runner's model configuration; and each new
reader on a recorded fixture."""

import json
import os

import pytest

from benchmarks import harness, ouro_cost

CONF = harness.load_json(os.path.join(
    harness.HERE, "configs", "ouro-2.6b-serve-whole.json"))
TRAFFIC = harness.load_json(harness.find_data_file(
    "traffic", "problems-256-in-256-out"))
CELL = "serve-loop4-mha-problems-256-in-256-out"
NEW = ("loop_layers_ms_per_decode_step", "loop_layers_roofline",
       "loop_attention_ms_per_decode_step", "loop_attention_roofline",
       "whole_prefill_ms_per_req", "loop_prefill_roofline")
JOINED = ("tput_decode_steps_per_s", "tput_slot_occupancy",
          "tput_device_idle_share", "tput_engine_host_ms_per_step",
          "tput_stream_yield_ms_per_token", "tput_decode_step_device_ms",
          "tput_engine_step_period_ms", "tput_pump_cpu_ms_per_step",
          "tput_pump_wait_ms_per_step", "tput_stream_items_per_call",
          "tput_proxy_forward_ms_per_item", "head_sample_ms_per_decode_step",
          "setup_cluster_start_s", "setup_serve_deploy_wait_s",
          "setup_worker_boot_s", "setup_backend_init_s",
          "setup_params_init_s", "setup_engine_build_s",
          "setup_program_trace_lower_s", "setup_program_first_run_s",
          "setup_attributed_share")


def test_the_configuration_is_the_published_one_uncut():
    assert CONF["reduced"] == []
    for key in ("source", "assumed", "deployment", "runner", "serve", "why"):
        assert CONF[key]
    assert "One v5e chip" in CONF["deployment"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(json.loads(line) for line in open(catalog)
                   if '"Ouro-2.6B"' in line)
        assert CONF["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert CONF[key] == value, key
    assert (TRAFFIC["loop"], TRAFFIC["clients"], TRAFFIC["max_requests_per_s"],
            TRAFFIC["new_tokens"], TRAFFIC["ramp_s"], TRAFFIC["repeat_every"],
            TRAFFIC["repeat_prompt_tokens"], TRAFFIC["warmup_prompt_tokens"],
            TRAFFIC["reference_prompt_tokens"],
            TRAFFIC["reference_new_tokens"], TRAFFIC["trace_after_s"],
            TRAFFIC["trace_s"], TRAFFIC["path"]) == (
        "closed", 8, 2.5, 256, 6.0, 16, 192, [192], 192, 8, 5.0, 2.0,
        "/llm/generate_stream")
    assert TRAFFIC["prompt_tokens"] == {"dist": "uniform", "min": 136,
                                        "max": 248}
    assert TRAFFIC["toy_serve"] == {"max_len": 128, "cache_slots": 4}
    assert CONF["serve"]["cache_slots"] == TRAFFIC["clients"] == 8
    assert CONF["serve"]["max_len"] == 512 >= 248 + 256


def test_the_runner_builds_the_looped_block_from_the_file():
    runner = harness.load_module("runners", "serve_ouro")
    cfg = runner.ouro_model_config(CONF)
    assert cfg.num_params() == 2_667_974_657  # ISSUE 64's count, key by key
    assert (cfg.layers, cfg.loop_steps, cfg.sandwich, cfg.exit_threshold,
            cfg.heads, cfg.kv_heads, cfg.hd, cfg.hidden, cfg.mlp_hidden,
            cfg.vocab_size, cfg.tie_embeddings, cfg.rope_theta,
            cfg.norm_eps, cfg.max_seq) == (
        48, 4, True, 1.0, 16, 16, 128, 2048, 5632, 49152, False, 1e6, 1e-6,
        65536)
    (kept,) = cfg.kept(512)
    assert (kept.fields, kept.layers, kept.rows, kept.shape) == (
        ("k", "v"), 192, 512, (16, 128))
    with pytest.raises(ValueError, match="leave the loop at different"):
        runner.ouro_model_config(dict(CONF, early_exit_threshold=0.5))
    with pytest.raises(ValueError, match="full-attention layers"):
        runner.ouro_model_config(dict(CONF, use_sliding_window=True))
    toy = runner.ouro_model_config(runner.toy_config(
        dict(CONF, **harness.TOY_MODEL)))
    assert (toy.layers, toy.loop_steps, toy.full_layers) == (2, 2, 4)
    assert set(runner.KERNEL_PATHS) == {"prefill_attention",
                                        "decode_attention"}


def test_costs_by_hand():
    assert ouro_cost.passes(CONF) == 4 and ouro_cost.cache_layers(CONF) == 192
    assert ouro_cost.layer_params(CONF) == 51_388_416
    assert ouro_cost.stack_bytes(CONF) == 48 * 51_388_416 * 2  # 4.93 GB
    assert ouro_cost.head_bytes(CONF) == 2048 * 49152 * 2
    # a token keeps 192 x 2 x 2048 x 2 B = 1.5 MiB
    assert ouro_cost.cache_layers(CONF) * ouro_cost.row_bytes(CONF) \
        == 1_572_864
    # 8 sequences of 320 rows: every held row of 192 layers, K and V
    attention = ouro_cost.decode_attention_cost(CONF, 8 * 320)
    assert attention["bytes"] == 8 * 320 * 1_572_864
    assert attention["flops"] == 4 * 192 * 8 * 320 * 2048
    layers = ouro_cost.decode_layers_cost(CONF, 8 * 320, 8)
    assert layers["bytes"] == 4 * ouro_cost.stack_bytes(CONF) \
        + 8 * 320 * 1_572_864 + 8 * 1_572_864
    assert round(layers["bytes"] / 1e9, 2) == 23.77  # 24.3 ms at the peak
    assert layers["flops"] / 197e12 < layers["bytes"] / 819e9  # the memory's
    assert ouro_cost.decode_step_bytes(CONF, 8 * 320, 8) \
        == layers["bytes"] + 201_326_592
    # a prompt of 192 rows: 2 x 192 x 4 x 48 layers' weights, the attention's
    # half square, the head at one position
    prefill = ouro_cost.prefill_cost(CONF, 192)
    products = 2 * 192 * 4 * 48 * 51_388_416
    assert prefill["flops"] == products + 192 * 4 * 2048 * 192 * 193 / 2 \
        + 2 * 2048 * 49152
    assert round(prefill["flops"] / 1e12, 2) == 3.82
    assert prefill["flops"] / 197e12 < prefill["bytes"] / 819e9  # 192 rows:
    # the weights' four reads (24 ms) outlast the products (19 ms)


SCOPES = {"_decode_impl": {
    "attend_cached": ["decode_attention.6"], "mlp": ["fusion.7"],
    "loop.pass_end": ["fusion.8"], "lm_head": ["fusion.11"],
    "sample": ["fusion.12"]}}
OPS = {"_decode_impl/decode_attention.6": 0.060, "_decode_impl/fusion.7": 0.2,
       "_decode_impl/fusion.8": 0.001, "_decode_impl/fusion.11": 0.003,
       "_decode_impl/fusion.12": 0.0005, "_decode_impl/fusion.99": 0.05}


def _ctx(toy=False, spans=True, scopes=SCOPES, prefill=True, conf=CONF):
    dispatch = [["ray_tpu.engine.decode_dispatch", i * 1000, 10, 7,
                 {"active": 8, "rows": 8 * 320 + i}] for i in range(3)]
    trace = {"op_self_s": OPS, "programs": {
        "_decode_impl": {"count": 10, "total_s": 0.32, "p50_s": 0.032}}}
    if spans:
        trace["program_spans"] = {"spans": dispatch, "busy": {}, "window": {}}
    counters = {"reference_check": {"op_scopes": scopes}}
    if prefill:
        counters["loop_prefill"] = {"ms_per_req": 40.0, "bucket": 256}
    return {"cell": {"toy": toy, "config": conf, "traffic": TRAFFIC,
                     "name": CELL},
            "trace": trace, "counters": counters,
            "device": {"kind": "TPU v5 lite"}}


@pytest.mark.parametrize("metric, want", [
    ("loop_layers_ms_per_decode_step", 32.0 - 0.35),
    ("loop_attention_ms_per_decode_step", 6.0),
    ("whole_prefill_ms_per_req", 40.0),
    ("head_sample_ms_per_decode_step", 0.35),
    ("tput_decode_step_device_ms", 32.0),
])
def test_each_reader_on_a_recorded_run(metric, want):
    read = harness.load_reader(metric).read
    assert read(_ctx()) == pytest.approx(want)
    # the parent of the PR has no such scope, counter or trace: nothing is
    # read, nothing raises, the line leaves the metric out
    bare = {"cell": {"toy": False, "config": CONF, "traffic": TRAFFIC},
            "trace": {}, "counters": {}, "device": {"kind": "TPU v5 lite"}}
    assert read(bare) is None


def test_roofline_shares_stay_under_the_peaks():
    ctx = _ctx()
    rows = 8 * 320 + 1  # the spans' median
    layers = ouro_cost.decode_layers_cost(CONF, rows, 8)
    attention = ouro_cost.decode_attention_cost(CONF, rows)
    prefill = ouro_cost.prefill_cost(CONF, 192)
    got = {m: harness.load_reader(m).read(ctx) for m in NEW if "roof" in m}
    assert got["loop_layers_roofline"] == pytest.approx(
        100 * layers["bytes"] / 819e9 / 31.65e-3)
    assert got["loop_attention_roofline"] == pytest.approx(
        100 * attention["bytes"] / 819e9 / 6e-3)
    assert got["loop_prefill_roofline"] == pytest.approx(
        100 * prefill["bytes"] / 819e9 / 40e-3)
    assert all(0 < v < 100 for v in got.values()), got
    for m in got:  # a CPU has no published peak; another family's keys
        read = harness.load_reader(m).read
        assert read(_ctx(toy=True)) is None
        assert read(_ctx(conf={})) is None
    for m in ("loop_layers_roofline", "loop_attention_roofline"):
        read = harness.load_reader(m).read
        assert read(_ctx(spans=False)) is None
        assert read(_ctx(scopes={})) is None
    assert harness.load_reader("loop_prefill_roofline").read(
        _ctx(prefill=False)) is None


def test_the_new_entries_have_readers_units_and_the_cell():
    for metric in NEW:
        assert harness.load_reader(metric).__file__.endswith(
            os.path.join("layer_metrics", metric + ".py"))
    bench = harness.load_benchmark()
    mine = [m for m in bench["per_layer"] if CELL in m.get("workloads", [])]
    own = {m["name"]: m for m in mine if m["name"] in NEW}
    assert set(own) == set(NEW)
    assert {m["unit"] for n, m in own.items() if "roofline" in n} == {"%"}
    assert {m["unit"] for n, m in own.items() if "roofline" not in n} == {"ms"}
    assert all((m["source"], m["layer"], m["moves"]) == (
        "device_trace", "model", "out_tokens_per_s") for m in own.values())
    assert {m["name"] for m in mine} - set(own) >= set(JOINED)
    # (which entries are this cell's ALONE, and how many entries and cells
    # there are, is `test_per_layer_entries.py`'s and `test_contract.py`'s to
    # say: an entry is a question since PR 69, and the next cell's PR edits
    # no file the benchmark has, this one among them)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONF["name"], "problems-256-in-256-out", 1)
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert config["reduced"] == CONF["reduced"] == []
    assert config["source"] == CONF["source"]
    assert config["file"] == "benchmarks/configs/ouro-2.6b-serve-whole.json"
    for line in (config["why"], config["source"], cell["why"]):
        assert 1 <= len(line) <= 200 and line.isprintable() and line.isascii()
    tput = next(m for m in bench["end_to_end"]
                if m["name"] == "out_tokens_per_s")
    assert CELL in tput["workloads"] and tput["bound"] == 0.055
    for m in mine:  # every entry finds its reader, a prefixed one its words'
        harness.load_reader(m["name"])
