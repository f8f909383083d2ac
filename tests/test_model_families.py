"""The table of model families (`models/families.py`): every entry's module
states what the family is under the same names, and the configuration, the
cache, the engine's row count and the refusals read that statement."""

import dataclasses
import importlib

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import families
from ray_tpu.models import transformer as T
from ray_tpu.models.continuous_batching import ContinuousBatcher
from ray_tpu.models.decoding import ROWS, STATES, KVCache, init_cache
from ray_tpu.ops.attention import decode_block

# family module -> its debug preset(s)
PRESETS = {
    "transformer": ("debug", "moe_debug", "olmoe_debug", "ouro_debug"),
    "zaya": ("zaya_debug",),
    "laguna": ("laguna_debug", "mimo_v2_debug"),
    "kimi_linear": ("kimi_linear_debug", "solar_open2_debug"),
    "longcat": ("longcat_debug",),
    "nemotron_h": ("nemotron_h_debug", "jamba_debug"),
}
MODULES = (families.ONE_BLOCK, *families.SUBLAYERS, *families.PATTERNS)
# the fields `families.of` chooses by: set on another family's configuration
# they make it a third family's, which its `check` then judges
SELECTS = {"layer_kinds", "lead_kind", "tail_kinds", "attention", "router"}
# a value away from its default for every field some family declares
AWAY = dict(
    tie_embeddings=True, lora_rank=4, qk_norm=True, partial_rotary=0.5,
    window=4, window_heads=2, rope_yarn=(4.0, 16.0, 32.0, 1.0, 1.1),
    head_gate=True, dense_mlp_hidden=64, shared_expert_hidden=32,
    experts_held=(0, 2), kda_conv=4, mla_latent=32, mla_rope_dim=8,
    mla_q_rank=8, mla_rotate=True, mla_scales=(2.0, 2.0),
    router_score="sigmoid", zero_experts=4, ssm_heads=4, ssm_head_dim=16,
    ssm_groups=4, ssm_state=8, ssm_conv=3, ssm_chunk=64, ssm_dt_rank=4,
    moe_latent=16, gqa_gate=True, kda_neg_eigval=True,
    expert_act="relu2", window_kv_heads=2, value_dim=8, window_sink=True,
    value_scale=0.5, window_partial_rotary=0.5, loop_steps=2, sandwich=True,
    exit_threshold=0.5)
BATCH, MAX_LEN = 3, 32


def module(name):
    return importlib.import_module(f"ray_tpu.models.{name}")


def test_the_table_and_the_presets_cover_each_other():
    assert set(PRESETS) == set(MODULES)
    declared = set().union(*(module(m).FIELDS for m in MODULES))
    every = {f.name for f in dataclasses.fields(T.TransformerConfig)}
    assert declared | T.COMMON == every and not declared & T.COMMON
    assert declared - SELECTS == set(AWAY)
    # every preset constructs, and is of a family of the table
    for name in T.PRESETS:
        assert families.of(T.config(name)).__name__.rsplit(".")[-1] in MODULES


def hand_rows(name: str, cfg, lens):
    """(held, read) of `_kv_rows` by each family's own arithmetic."""
    def blocks(rows, t, width):  # a latent layer's rows: whole blocks
        block = decode_block(t, width * jnp.dtype(cfg.dtype).itemsize)
        return int((-(-rows // block) * block).sum())

    def granules(rows, t):  # K and V rows: whole 16s (8s in a ring of 8)
        granule = 16 if t % 16 == 0 else 8
        return int((-(-rows // granule) * granule).sum())

    if name in ("transformer", "zaya"):  # a looped model: a layer a pass
        layers = cfg.loop_steps * cfg.layers
        return layers * int(lens.sum()), layers * granules(lens, MAX_LEN)
    if name == "laguna":
        full, window = cfg.kinds.count("full"), cfg.kinds.count("window")
        ring = np.minimum(lens, cfg.window)
        return (full * int(lens.sum()) + window * int(ring.sum()),
                full * granules(lens, MAX_LEN)
                + window * granules(ring, cfg.window))
    if name == "nemotron_h":  # the attention layers' rows; a mixer keeps none
        gqa = cfg.kinds.count("gqa")
        return gqa * int(lens.sum()), gqa * granules(lens, MAX_LEN)
    if name == "kimi_linear" and cfg.kinds.count("gkv"):
        # delta-rule layers beside K/V rows: the "gkv" layers' rows alone
        gkv = cfg.kinds.count("gkv")
        return gkv * int(lens.sum()), gkv * granules(lens, MAX_LEN)
    latent = {"kimi_linear": cfg.kinds.count("mla"),
              "longcat": 2 * cfg.layers}[name]
    width = -(-(cfg.mla_latent + cfg.mla_rope_dim) // 128) * 128
    return latent * int(lens.sum()), latent * blocks(lens, MAX_LEN, width)


@pytest.mark.parametrize("name, preset", [
    (m, p) for m in MODULES for p in PRESETS[m]])
def test_a_family_states_what_it_is_and_the_rest_reads_it(name, preset):
    cfg = T.config(preset)
    family = families.of(cfg)
    assert family is module(name)
    assert family.FIELDS <= {
        f.name for f in dataclasses.fields(cfg)} - T.COMMON
    family.check(cfg)
    stated = cfg.kept(MAX_LEN)
    assert stated == tuple(k for k in family.kept(cfg, MAX_LEN) if k.layers)
    # rows and states are told apart as `KVCache` tells them
    for kept in stated:
        assert set(kept.fields) <= set(STATES if kept.rows is None else ROWS)
    names = tuple(n for kept in stated for n in kept.fields)
    assert cfg.keeps == names == tuple(
        n for n in KVCache._fields if n in names)  # the cache's own order
    assert cfg.stateful == bool(set(names) & set(STATES))
    beside = [n for n in names if n not in ("k", "v")]
    if beside:  # by what is kept, whichever family keeps it
        with pytest.raises(ValueError, match=", ".join(beside)):
            families.only_kv_rows(cfg, "a test holds none")
        families.only_kv_rows(cfg, "a test holds them", also=tuple(beside))
    else:
        families.only_kv_rows(cfg, "a test holds none")
    # the cache holds exactly the stated fields, at the stated shapes
    cache = init_cache(cfg, BATCH, MAX_LEN)
    for kept in stated:
        rows = () if kept.rows is None else (kept.rows,)
        for field in kept.fields:
            array = getattr(cache, field)
            assert array.shape == (kept.layers, BATCH, *rows,
                                   *kept.shape_of(field))
            assert array.dtype == (kept.dtype or cfg.dtype)
    for field in set(KVCache._fields) - set(names) - {"lengths"}:
        array = getattr(cache, field)  # K/V without a layer, the rest absent
        assert array.shape[0] == 0 if field in ("k", "v") else array is None
    # the engine's count of rows is the family's own arithmetic
    batcher = ContinuousBatcher.__new__(ContinuousBatcher)
    batcher.cfg, batcher.max_len = cfg, MAX_LEN
    lens = np.array([1, 9, 17, 32])
    assert batcher._kv_rows(lens) == hand_rows(name, cfg, lens)


WINDOWS, PERIOD = ("window",) * 3, ("kda", "kda", "mla", "kda")
# preset, overrides -> `pattern.cut`: [(unit, repeats, scanned)] as each
# family's own loop cut its layers before `pattern.forward_cached` took the
# loops over; None: the family keeps a `forward_cached` of its own
CUTS = {
    # the period form: the lead, ONE scan of the periods (of one period
    # too), the tail as one unit in a row
    ("laguna_debug", ()): [
        (("full",), 1, False), ((*WINDOWS, "full"), 2, True)],
    ("laguna_debug", (("layers", 5),)): [
        (("full",), 1, False), ((*WINDOWS, "full"), 1, True)],
    ("kimi_linear_debug", ()): [
        (("kda",), 1, False), (PERIOD, 2, True), (("kda", "mla"), 1, False)],
    ("kimi_linear_debug", (("layers", 9), ("tail_kinds", ()))): [
        (("kda",), 1, False), (PERIOD, 2, True)],
    # the family's form without a lead: whole periods, read off `cfg.kinds`
    # by `runs` as a list is; ONE period has nothing to repeat but its kda
    ("solar_open2_debug", ()): [(("gkv", "kda", "kda", "kda"), 2, True)],
    ("solar_open2_debug", (("layers", 4),)): [
        (("gkv",), 1, False), (("kda",), 3, True)],
    # the list form: `runs` behind the first layer where it has the dense MLP
    ("mimo_v2_debug", ()): [
        (("full",), 1, False), (("window",), 2, True), (("full",), 1, False),
        (("window",), 3, True)],
    ("nemotron_h_debug", ()): [
        (("ssm", "lmoe"), 2, True), (("ssm",), 1, False),
        (("gqa",), 1, False), (("lmoe", "ssm"), 2, True),
        (("gqa",), 1, False)],
    ("jamba_debug", ()): [
        (("ssm1", "mlp"), 2, True), (("gqa",), 1, False),
        (("mlp", "ssm1"), 3, True), (("mlp",), 1, False),
        (("gqa",), 1, False), (("mlp",), 1, False), (("ssm1",), 1, False),
        (("mlp",), 1, False)],
    ("longcat_debug", ()): None,
}


@pytest.mark.parametrize("preset, overrides", sorted(CUTS))
def test_a_pattern_family_states_one_layer_and_the_loop_is_patterns(
        preset, overrides):
    from ray_tpu.models import pattern

    cfg = T.config(preset, **dict(overrides))
    family, want = families.of(cfg), CUTS[preset, overrides]
    name = family.__name__.rsplit(".")[-1]
    assert name in families.PATTERNS
    if want is None:  # the one family named as keeping its own loop
        assert name == "longcat" and callable(family.forward_cached)
        assert not hasattr(family, "layer")
        return
    assert not hasattr(family, "forward_cached"), (
        f"{name} has a layer loop of its own beside pattern.forward_cached")
    assert callable(getattr(family, "layer", None)), (
        f"{name} does not state its one layer (`layer`)")
    # what it carries is what its layers keep, in the cache's own order
    assert set(cfg.keeps) <= set(family.CARRIED) <= set(KVCache._fields)
    assert family.CARRIED == tuple(
        n for n in KVCache._fields if n in family.CARRIED)
    got = pattern.cut(cfg)
    assert got == want
    assert sum((unit * n for unit, n, _ in got), ()) == cfg.kinds
    # a lead is alone and never scanned; the list form scans what repeats
    assert all(scanned == (n > 1) for _, n, scanned in got) or cfg.lead_kind


@pytest.mark.parametrize("name, field", [
    (m, f) for m in MODULES
    for f in sorted(set(AWAY) - module(m).FIELDS)])
def test_a_family_refuses_every_other_familys_field_by_name(name, field):
    """No family lists another's fields: what is neither common nor its own
    is refused where it is set, whichever family it belongs to."""
    cfg = T.config(PRESETS[name][0])
    assert getattr(cfg, field) != AWAY[field]
    with pytest.raises(ValueError, match=rf"\b{field}\b"):
        dataclasses.replace(cfg, **{field: AWAY[field]})


# a value away from its default for the common fields that are no width
# (each family's check does its own arithmetic on those); None: the other
# truth value
COMMON_AWAY = {
    "remat": None, "norm_topk_prob": None, "lora_alpha": 8.0,
    "capacity_factor": 2.0, "router_hidden": 16, "window_rope_theta": 5e4,
    "routed_scale": 1.5, "rope_theta": 2e4, "norm_eps": 1e-4, "max_seq": 256,
    "vocab_size": 1024}


@pytest.mark.parametrize("field", sorted(COMMON_AWAY))
def test_a_common_field_is_refused_by_no_family(field):
    """What no check has ever policed stays settable on every family."""
    assert field in T.COMMON
    for presets in PRESETS.values():
        cfg = T.config(presets[0])
        value = COMMON_AWAY[field]
        if value is None:
            value = not getattr(cfg, field)
        assert getattr(dataclasses.replace(cfg, **{field: value}),
                       field) == value


@pytest.mark.parametrize("preset, kinds, module_name", [
    ("solar_open2_debug", {"gkv", "kda"}, "kimi_linear"),
    ("kimi_linear_debug", {"kda", "mla"}, "kimi_linear"),
    ("nemotron_h_debug", {"ssm", "gqa", "lmoe"}, "nemotron_h"),
    ("jamba_debug", {"ssm1", "gqa", "mlp"}, "nemotron_h"),
])
def test_grouped_attention_has_one_name_a_family(preset, kinds, module_name):
    """K/V rows of grouped heads are "gqa" as one of `nemotron_h`'s
    one-sublayer layers and "gkv" as the attention half of a `kimi_linear`
    layer: `PATTERNS` takes the first family whose kinds hold a pattern's, so
    a kind in two families would send one of them astray."""
    cfg = T.config(preset)
    assert set(cfg.kinds) == kinds
    assert families.of(cfg) is module(module_name)
    owners = [n for n, runs in families.PATTERNS.items()
              if {"gqa", "gkv"} & runs]
    assert owners == ["kimi_linear", "nemotron_h"]
    assert not families.PATTERNS["kimi_linear"] & families.PATTERNS[
        "nemotron_h"]


def test_a_pattern_of_two_families_kinds_is_refused_by_name():
    with pytest.raises(ValueError, match="unknown layer kinds"):
        T.config("solar_open2_debug", layer_kinds=("gqa", "kda", "kda", "kda"))
    with pytest.raises(ValueError, match="unknown layer kinds"):
        T.config("nemotron_h_debug", layer_kinds=("ssm", "gkv"), layers=2)
