"""The arithmetic of solar_open2_cost.py, by hand; the configuration file
against the catalog's row and what ISSUE 67 states of it; the runner's model
configuration; and each new or joined reader on a recorded fixture."""

import json
import os

import pytest

from benchmarks import harness, kimi_linear_cost, solar_open2_cost

CONF = harness.load_json(os.path.join(
    harness.HERE, "configs", "solar-open2-250b-serve-ep16-d8.json"))
TRAFFIC = harness.load_json(harness.find_data_file(
    "traffic", "docqa-8k-in-512-out"))
CELL = "serve-kda-gqa-docqa-8k-in-512-out"
NEW = ("gqa_attention_roofline", "gqa_project_ms_per_decode_step")


def test_the_configuration_is_the_published_one_but_for_its_three_cuts():
    assert CONF["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    assert {k: CONF["published"][k] for k in CONF["reduced"]} == {
        "num_hidden_layers": 48, "n_routed_experts": 320,
        "vocab_size": 196608}
    assert (CONF["num_hidden_layers"], CONF["n_routed_experts"],
            CONF["vocab_size"]) == (8, 20, 24576)
    # every published width
    assert (CONF["hidden_size"], CONF["num_attention_heads"],
            CONF["num_key_value_heads"], CONF["head_dim"],
            CONF["moe_intermediate_size"], CONF["num_experts_per_tok"],
            CONF["n_shared_experts"], CONF["intermediate_size"]) == (
        4096, 64, 8, 128, 1280, 8, 1, 10240)
    lin = CONF["linear_attn_config"]
    assert (lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"],
            lin["num_kv_heads"]) == (64, 128, 4, None)
    for key in ("source", "assumed", "deployment", "runner", "serve"):
        assert CONF[key]
    assert "Sixteen chips share each layer" in CONF["deployment"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(json.loads(line) for line in open(catalog)
                   if '"Solar-Open2-250B"' in line)
        assert CONF["source"] == row["source_url"]
        for key, value in row["config"].items():
            # nested groups and lists whole (`gqa_layers` too: the entries
            # from the depth up name layers the cut leaves out)
            if key not in CONF["reduced"]:
                assert CONF[key] == value, key
    # the traffic is ISSUE 67's, letter for letter
    assert (TRAFFIC["loop"], TRAFFIC["clients"], TRAFFIC["max_requests_per_s"],
            TRAFFIC["new_tokens"], TRAFFIC["ramp_s"], TRAFFIC["repeat_every"],
            TRAFFIC["repeat_prompt_tokens"], TRAFFIC["warmup_prompt_tokens"],
            TRAFFIC["reference_prompt_tokens"],
            TRAFFIC["reference_new_tokens"], TRAFFIC["path"]) == (
        "closed", 16, 4.0, 512, 6.0, 8, 6000, [6000], 4800, 8,
        "/llm/generate_stream")
    assert TRAFFIC["prompt_tokens"] == {"dist": "uniform", "min": 4112,
                                        "max": 8000}
    assert CONF["serve"]["cache_slots"] == TRAFFIC["clients"] == 16
    assert CONF["serve"]["max_len"] == 8704 >= 8000 + 512


def test_the_runner_builds_the_pattern_from_the_file():
    runner = harness.load_module("runners", "serve_solar_open2")
    cfg = runner.solar_model_config(CONF)
    assert (cfg.lead_kind, cfg.layer_kinds, cfg.tail_kinds) == (
        "", ("gkv", "kda", "kda", "kda"), ())
    assert (cfg.layers, cfg.periods, cfg.layers_of("kda"),
            cfg.layers_of("gkv"), cfg.sparse_layers, cfg.full_layers) == (
        8, 2, 6, 2, 8, 2)
    assert [l for l, k in enumerate(cfg.kinds) if k == "gkv"] == [0, 4] == \
        [l for l in CONF["gqa_layers"] if l < 8]
    assert (cfg.heads, cfg.kv_heads, cfg.hd, cfg.kda_conv, cfg.gqa_gate,
            cfg.kda_neg_eigval, cfg.hidden, cfg.mlp_hidden,
            cfg.shared_expert_hidden, cfg.dense_mlp_hidden) == (
        64, 8, 128, 4, True, True, 4096, 1280, 1280, 0)
    assert (cfg.num_experts, cfg.experts_held, cfg.experts_per_token,
            cfg.routed_scale, cfg.router_score, cfg.norm_topk_prob) == (
        320, (0, 20), 8, 1.0, "sigmoid", True)
    assert cfg.keeps == ("k", "v", "mat", "conv") and cfg.stateful
    # ISSUE 67's arithmetic: 3.90B parameters held, 7.80 GB in bfloat16
    assert cfg.num_params() == 3_898_793_600
    rows, mat, conv = cfg.kept(8704)
    assert (rows.fields, rows.layers, rows.rows, rows.shape) == (
        ("k", "v"), 2, 8704, (8, 128))
    assert (mat.layers, mat.shape) == (6, (64, 128, 128))
    # --toy keeps every mechanism at debug widths
    tcfg = runner.solar_model_config(
        runner.toy_config(dict(CONF, **harness.TOY_MODEL)))
    assert (tcfg.layers, tcfg.periods, tcfg.heads, tcfg.kv_heads, tcfg.hd,
            tcfg.num_experts, tcfg.experts_held, tcfg.experts_per_token,
            tcfg.gqa_gate, tcfg.kda_neg_eigval) == (
        8, 2, 8, 2, 16, 16, (0, 4), 4, True, True)
    with pytest.raises(ValueError, match="every layer sparse"):
        runner.solar_model_config(dict(CONF, first_k_dense_replace=1))
    with pytest.raises(ValueError, match="unrotated"):
        runner.solar_model_config(dict(CONF, use_rope=True))
    with pytest.raises(ValueError, match="gqa_layers"):
        runner.solar_model_config(dict(CONF, gqa_layers=[0, 5]))


def test_costs_by_hand():
    # a position's K and V rows in one layer: 2 x 8 x 128 x 2 B = 4 KiB
    assert solar_open2_cost.position_bytes(CONF) == 4096
    assert solar_open2_cost.gqa_layers(CONF) == 2
    # 16 slots of 8,512 positions, 2 layers: 1.116 GB a step at the end
    cost = solar_open2_cost.gqa_attention_cost(CONF, 16 * 8512)
    assert cost["bytes"] == 2 * 16 * 8512 * 4096
    assert round(cost["bytes"] / 1e9, 3) == 1.116
    assert cost["flops"] == 2 * 16 * 8512 * 64 * 4 * 128
    assert cost["flops"] / 197e12 < cost["bytes"] / 819e9 / 20  # memory bound
    # the states' yardstick, `kimi_linear_cost.state_update_cost`, counts
    # `linear_attn_config.kda_layers`, which this configuration does not
    # publish (its file holds the published group whole):
    with pytest.raises(KeyError, match="kda_layers"):
        kimi_linear_cost.state_update_cost(CONF, 16)
    # `with_kda_layers` hands that arithmetic the layers `gqa_layers` does not
    # name (PR 69: the cell is on `state_update_roofline`'s list): 6 layers x
    # 16 sequences x 64 states of 128 x 128 float32, read and written
    seen = solar_open2_cost.with_kda_layers(CONF)
    assert seen["linear_attn_config"]["kda_layers"] == [1, 2, 3, 5, 6, 7]
    assert "kda_layers" not in CONF["linear_attn_config"]  # the file's, whole
    state = kimi_linear_cost.state_update_cost(seen, 16)
    assert state["bytes"] == 6 * 16 * 64 * 128 * 128 * 2 * 4
    assert round(state["bytes"] / 1e9, 3) == 0.805
    assert {k: v for k, v in seen.items() if k != "linear_attn_config"} == {
        k: v for k, v in CONF.items() if k != "linear_attn_config"}
    # one expert is 3 x 4096 x 1280 = 15.73M parameters = 31.46 MB
    assert round(kimi_linear_cost.held_experts_cost(CONF, 1)["bytes"] / 1e6,
                 2) == 31.46


SCOPES = {"_decode_impl": {
    "kda.project": ["fusion.1"], "kda.conv": ["fusion.2"],
    "kda.gate": ["fusion.3"], "kda.state": ["kda_state_update.1"],
    "kda.out": ["fusion.6"], "gqa.project": ["fusion.7", "fusion.13"],
    "gqa.attend": ["decode_attention.1", "fusion.14"],
    "gqa.gate": ["fusion.15"], "gqa.out": ["fusion.8"],
    "moe.shared": ["fusion.9"], "moe_router": ["fusion.10"],
    "moe_experts": ["ragged-dot-none.1"], "lm_head": ["fusion.11"],
    "sample": ["fusion.12"]}}
OPS = {"_decode_impl/fusion.1": 0.012, "_decode_impl/fusion.2": 0.002,
       "_decode_impl/fusion.3": 0.002,
       "_decode_impl/kda_state_update.1": 0.013,
       "_decode_impl/fusion.6": 0.003, "_decode_impl/fusion.7": 0.003,
       "_decode_impl/fusion.13": 0.001,
       "_decode_impl/decode_attention.1": 0.0145,
       "_decode_impl/fusion.14": 0.0005, "_decode_impl/fusion.15": 0.0002,
       "_decode_impl/fusion.8": 0.001, "_decode_impl/fusion.9": 0.004,
       "_decode_impl/fusion.10": 0.002,
       "_decode_impl/ragged-dot-none.1": 0.025,
       "_decode_impl/fusion.11": 0.003, "_decode_impl/fusion.12": 0.0005}


def _ctx(toy=False):
    dispatch = [["ray_tpu.engine.decode_dispatch", i * 1000, 10, 7,
                 {"active": 16, "rows": 16 * 6300 + i}] for i in range(3)]
    trace = {"op_self_s": OPS, "programs": {
        "_decode_impl": {"count": 10, "total_s": 0.1, "p50_s": 0.01}},
        "program_spans": {"spans": dispatch, "busy": {}, "window": {}}}
    counters = {"reference_check": {"op_scopes": SCOPES},
                "engine": {"steps": 100, "tokens_out": 1600, "admitted": 0},
                "moe": {"moe_assignments": 102400, "moe_rows": 1600,
                        "layers": 8, "moe_assignments_held": 6400,
                        "moe_experts_reached": 5300},
                "kda_prefill": {"ms_per_req": 280.0, "prefill_ms": 450.0}}
    return {"cell": {"toy": toy, "config": CONF, "name": CELL},
            "trace": trace, "counters": counters,
            "device": {"kind": "TPU v5 lite"}}


@pytest.mark.parametrize("metric, want", [
    ("gqa_project_ms_per_decode_step", 0.4),
    ("state_update_ms_per_decode_step", 1.3),
    ("state_project_ms_per_decode_step", 1.9),
    ("state_prefill_ms_per_req", 280.0),
    ("shared_expert_ms_per_decode_step", 0.4),
    ("moe_router_ms_per_decode_step", 0.2),
    ("moe_expert_ms_per_decode_step", 2.5),
    ("head_sample_ms_per_decode_step", 0.35),
    ("moe_assignments_per_token", 8.0),
    ("moe_held_share", 6400 / 102400),
    ("tput_decode_step_device_ms", 10.0),
])
def test_each_reader_on_a_recorded_run(metric, want):
    read = harness.load_reader(metric).read
    assert read(_ctx()) == pytest.approx(want)
    # the parent of the PR has no such scope, counter or trace: nothing is
    # read, nothing raises, the line leaves the metric out
    bare = {"cell": {"toy": False, "config": CONF}, "trace": {},
            "counters": {}, "device": {"kind": "TPU v5 lite"}}
    assert read(bare) is None


def test_roofline_shares_from_what_the_steps_hold_and_reach():
    ctx = _ctx()
    rows = solar_open2_cost.gqa_attention_cost(CONF, 16 * 6300 + 1)
    held = kimi_linear_cost.held_experts_cost(CONF, 53.0)
    got = {m: harness.load_reader(m).read(ctx) for m in (
        "gqa_attention_roofline", "held_experts_roofline",
        "state_update_roofline")}
    assert got["gqa_attention_roofline"] == pytest.approx(
        100 * rows["bytes"] / 819e9 / 1.5e-3)
    assert got["state_update_roofline"] == pytest.approx(
        100 * 6 * 16 * 64 * 128 * 128 * 8 / 819e9 / 1.3e-3)
    assert got["held_experts_roofline"] == pytest.approx(
        100 * held["bytes"] / 819e9 / 2.5e-3)
    assert all(0 < v < 100 for v in got.values())
    assert harness.load_reader("gqa_attention_roofline").read(
        _ctx(toy=True)) is None
    # another configuration's cell with the same spans reads nothing
    other = dict(ctx, cell=dict(ctx["cell"], config={"hidden_size": 1}))
    assert harness.load_reader("gqa_attention_roofline").read(other) is None


def test_the_share_cannot_pass_the_whole():
    """The count is of the rows HELD and no others: even if the kernel took
    no longer than the HBM needs for every row a slot CAN hold, the share of
    a full cache is 100% and of the traffic's fullest step 97.8%."""
    full = solar_open2_cost.gqa_attention_cost(CONF, 16 * 8704)
    least_ms = full["bytes"] / 819e9 * 1e3
    fullest = solar_open2_cost.gqa_attention_cost(CONF, 16 * 8512)
    assert fullest["bytes"] / 819e9 * 1e3 / least_ms == pytest.approx(
        8512 / 8704)
    assert fullest["bytes"] <= full["bytes"]


def test_the_entries_are_in_the_benchmark_with_their_cell():
    for metric in NEW:
        assert harness.load_reader(metric).__file__.endswith(
            os.path.join("layer_metrics", metric + ".py"))
    bench = harness.load_benchmark()
    mine = [m for m in bench["per_layer"] if CELL in m.get("workloads", [])]
    own = {m["name"]: m for m in mine if m["name"] in NEW}
    assert set(own) == set(NEW)
    assert own["gqa_attention_roofline"]["unit"] == "%"
    assert len(mine) > len(NEW)  # the shared readers' lists, joined
    # (which entries are this cell's ALONE, and how many entries and cells
    # there are, is `test_per_layer_entries.py`'s and `test_contract.py`'s to
    # say: an entry is a question since PR 69, and the next cell's PR edits
    # no file the benchmark has, this one among them)
    # joined at no cost in PR 69: `solar_open2_cost.state_roofline`
    assert "state_update_roofline" in {m["name"] for m in mine}
    assert all(m["moves"] in ("out_tokens_per_s", "setup_s") for m in mine)
    for m in mine:
        harness.load_reader(m["name"])
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "solar-open2-250b-serve-ep16-d8", "docqa-8k-in-512-out", 1)
    tput = next(m for m in bench["end_to_end"]
                if m["name"] == "out_tokens_per_s")
    assert CELL in tput["workloads"] and tput["bound"] == 0.055
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == CONF["reduced"]
    assert entry["source"] == CONF["source"]


def test_the_state_check_sees_a_bfloat16_state():
    """The delta rule alone, at a small size on the CPU: the programs' scan
    and step (float32 states) stand orders under the limits, the recurrence
    with a bfloat16 state, which the whole forward's limits do not see on the
    chip, over both."""
    import jax

    from ray_tpu.models import transformer as T

    runner = harness.load_module("runners", "serve_solar_open2")
    cfg = T.config("solar_open2_debug", head_dim=128, hidden=128)
    kda = T.init_params(cfg, jax.random.key(0))["blocks"]["kda"]
    out = runner.state_check(kda, 3000000001, 512, 8,
                             [("state_bf16", dict(state="bfloat16"))])
    assert out["ok"] and out["step_path"] == "plain"
    assert 0.4 < out["beta_over_one_share"] < 0.6  # negative eigenvalues
    low = out["second_readings"]["state_bf16"]
    for what, limit in runner.STATE_RMS_MAX.items():
        assert out[f"{what}_rms_err_over_std"] < limit / 20
        assert low[f"{what}_rms_err_over_std"] > limit
