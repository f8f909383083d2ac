"""The arithmetic of cca_cost.py, by hand, and how scope_ops.py gets from a
compiled program's text and a reduced trace to a scope's time."""

import os

from benchmarks import cca_cost, harness, scope_ops

CONF = harness.load_json(os.path.join(
    harness.HERE, "configs", "zaya1-8b-serve-d16.json"))


def test_the_configuration_is_the_published_one_but_for_its_depth():
    assert CONF["reduced"] == ["num_hidden_layers"]
    assert (CONF["num_hidden_layers"], CONF["published"]) == \
        (16, {"num_hidden_layers": 40})
    assert (CONF["hidden_size"], CONF["num_attention_heads"],
            CONF["num_key_value_heads"], CONF["head_dim"]) == (2048, 8, 2, 128)
    assert (CONF["num_experts"], CONF["num_experts_per_tok"],
            CONF["moe_intermediate_size"], CONF["router_hidden_size"]) == \
        (16, 1, 2048, 256)
    assert CONF["vocab_size"] == 262272 and CONF["tie_word_embeddings"]
    assert CONF["max_position_embeddings"] == 131072
    assert set(CONF["departures"]) == {"residual-scaled", "MoD"}


def test_decode_attention_cost_by_hand():
    # a token's k and v in the latent: 2 x 2 heads x 128 x 2 bytes = 1 KB a
    # layer (ISSUE 30), 16 KB over the 16 layers
    one = cca_cost.decode_attention_cost(CONF, 1)
    assert one["bytes"] == 16 * 1024
    # 32 slots full to 2048: the whole 1.07 GB cache
    full = cca_cost.decode_attention_cost(CONF, 32 * 2048)
    assert round(full["bytes"] / 1e9, 2) == 1.07
    # 8 query heads x (a dot product + a weighted sum) of 128 terms
    assert one["flops"] == 16 * 8 * 128 * 2 * 2
    # memory bound: 4 operations a byte against the chip's 240
    assert full["flops"] / 197e12 < full["bytes"] / 819e9 / 50


def _ctx(op_self_s, programs, scopes, toy=False):
    return {"cell": {"toy": toy, "config": CONF},
            "trace": {"op_self_s": op_self_s, "programs": programs},
            "counters": {"reference_check": {"op_scopes": scopes}},
            "device": {"kind": "TPU v5 lite"}}


HLO = """HloModule jit__decode_impl

%fused_computation.3 (p: bf16[32,128]) -> bf16[32,128] {
  %inside.1 = bf16[32,128] add(%p, %p), metadata={op_name="jit(_decode_impl)/jit(main)/while/body/cca.conv/add"}
}

%while_body (arg: (s32[])) -> (s32[]) {
  %fusion.7 = bf16[32,128] fusion(%x), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(_decode_impl)/jit(main)/while/body/cca.conv/add" source_file="zaya.py"}
  %fusion.8 = f32[32,8,2048] fusion(%q), kind=kOutput, calls=%fc, metadata={op_name="jit(_decode_impl)/jit(main)/while/body/cca.attend/attend_cached/dot_general"}
  ROOT %ragged-dot.2 = f32[32,2048] custom-call(%r), metadata={op_name="jit(_decode_impl)/jit(main)/while/body/moe_experts/ragged_dot"}
  %copy.4 = bf16[2] copy(%z), metadata={op_name="jit(_decode_impl)/jit(main)/while/body/mul"}
}

ENTRY %main.9 (Arg_0: bf16[2]) -> s32[32] {
  %sort.1 = f32[32,262272] sort(%l), metadata={op_name="jit(_decode_impl)/jit(main)/sample/sort"}
  %fusion.1 = bf16[32,262272] fusion(%h), kind=kOutput, calls=%fc2, metadata={op_name="jit(_decode_impl)/jit(main)/lm_head/dot_general"}
}
"""


def test_scopes_are_read_off_the_compiled_text():
    scopes = scope_ops.op_scopes(HLO)
    assert scopes == {"cca.conv": ["fusion.7"], "cca.attend": ["fusion.8"],
                      "moe_experts": ["ragged-dot.2"], "sample": ["sort.1"],
                      "lm_head": ["fusion.1"]}
    # the outermost of the known scopes names an operation
    assert scope_ops.scope_of("jit(f)/cca.attend/attend_cached/mul") == \
        "cca.attend"
    assert scope_ops.scope_of("jit(f)/while/body/mul") is None


def test_a_scopes_time_is_its_operations_self_time_in_that_program():
    scopes = {"_decode_impl": scope_ops.op_scopes(HLO)}
    ops = {"_decode_impl/fusion.7": 0.010, "_decode_impl/fusion.8": 0.040,
           "_decode_impl/sort.1": 0.080, "_decode_impl/fusion.1": 0.020,
           "_decode_impl/copy.4": 0.5, "_prefill_impl/fusion.8": 9.0,
           "fusion.8": 9.0}
    programs = {"_decode_impl": {"count": 10, "total_s": 2.0, "p50_s": 0.2}}
    ctx = _ctx(ops, programs, scopes)
    assert scope_ops.ms_per_run(ctx, "_decode_impl", ("cca.attend",)) == 4.0
    assert scope_ops.ms_per_run(
        ctx, "_decode_impl", ("cca.project", "cca.conv")) == 1.0
    assert scope_ops.ms_per_run(
        ctx, "_decode_impl", ("lm_head", "sample")) == 10.0
    # nothing to read: no map (another runner, the parent), no trace, no
    # operation of the scope in the window
    assert scope_ops.ms_per_run(_ctx(ops, programs, {}), "_decode_impl",
                                ("cca.attend",)) is None
    assert scope_ops.ms_per_run(
        {"cell": {}, "trace": {}, "counters": {}}, "_decode_impl",
        ("cca.attend",)) is None
    assert scope_ops.ms_per_run(ctx, "_decode_impl", ("zaya.router",)) is None


def test_roofline_share_from_the_rows_the_sequences_hold():
    scopes = {"_decode_impl": {"cca.attend": ["fusion.8"]}}
    programs = {"_decode_impl": {"count": 1, "total_s": 0.1, "p50_s": 0.1}}
    rows = 32 * 1400
    least = cca_cost.decode_attention_cost(CONF, rows)["bytes"] / 819e9
    ctx = _ctx({"_decode_impl/fusion.8": 4 * least}, programs, scopes)
    assert abs(cca_cost.attention_roofline(ctx, rows) - 25.0) < 1e-9
    assert cca_cost.attention_roofline(ctx, None) is None
    toy = _ctx({"_decode_impl/fusion.8": 1.0}, programs, scopes, toy=True)
    assert cca_cost.attention_roofline(toy, rows) is None  # no peak for a CPU
