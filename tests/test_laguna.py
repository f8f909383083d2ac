"""A layer pattern through the serving engine (models/laguna.py): window layers
with a ring beside full layers with slots, two head counts, a gate a head, a
held share of the experts and a shared expert, against the plain reference
(`benchmarks/reference_laguna.py`) at toy widths on the CPU: window 8, two
periods = 9 layers, 16 experts in two shares of 8, top-4."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_laguna as R
from ray_tpu.models import decoding, laguna, pattern
from ray_tpu.models import transformer as T
from ray_tpu.models.continuous_batching import ContinuousBatcher
from ray_tpu.models.decoding import SamplingParams

CFG = T.config("laguna_debug")
KINDS = {"full": "full_attention", "window": "sliding_attention"}


def published(cfg) -> dict:
    """The keys `reference_laguna` reads, as a `config.json` spells them."""
    kinds = ["full"] + list(cfg.layer_kinds) * cfg.periods
    factor, original, fast, slow, attention_factor = cfg.rope_yarn
    return {
        "num_hidden_layers": cfg.layers, "head_dim": cfg.hd,
        "num_key_value_heads": cfg.kv_heads, "rms_norm_eps": cfg.norm_eps,
        "sliding_window": cfg.window,
        "layer_types": [KINDS[k] for k in kinds],
        "num_attention_heads_per_layer": [
            cfg.heads if k == "full" else cfg.window_heads for k in kinds],
        "mlp_layer_types": ["dense"] + ["sparse"] * (cfg.layers - 1),
        "num_experts_per_tok": cfg.experts_per_token,
        "norm_topk_prob": cfg.norm_topk_prob,
        "moe_routed_scaling_factor": cfg.routed_scale,
        "experts_held_first": cfg.experts_held[0] if cfg.experts_held else 0,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": cfg.rope_theta, "rope_type": "yarn",
                "factor": factor, "original_max_position_embeddings": original,
                "beta_fast": fast, "beta_slow": slow,
                "attention_factor": attention_factor,
                "partial_rotary_factor": cfg.partial_rotary},
            "sliding_attention": {
                "rope_type": "default", "rope_theta": cfg.window_rope_theta,
                "partial_rotary_factor": 1}},
    }


@pytest.fixture(scope="module")
def params():
    return T.init_params(CFG, jax.random.key(5))


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n).tolist()


def test_the_pattern_and_its_counts():
    assert (CFG.periods, CFG.full_layers, CFG.window_layers,
            CFG.sparse_layers) == (2, 3, 6, 8)
    assert not CFG.stateful  # a ring is K/V rows, not a state
    shapes = {p: s for p, (s, _, _) in laguna.leaves(CFG).items()}
    assert shapes[("blocks", "full", "wq")] == (3, 128, 4, 16)
    assert shapes[("blocks", "window", "wq")] == (6, 128, 6, 16)
    assert shapes[("blocks", "sparse", "wi_gate")] == (8, 8, 128, 64)  # HELD
    assert shapes[("blocks", "sparse", "router")] == (8, 128, 16)  # all
    whole = dataclasses.replace(CFG, experts_held=None)
    one_layer_of_absent = 8 * 3 * 128 * 64
    assert whole.num_params() - CFG.num_params() == 8 * one_layer_of_absent
    axes = T.param_axes(CFG)
    made = jax.eval_shape(lambda: T.init_params(CFG, jax.random.key(0)))
    for path, leaf in jax.tree_util.tree_leaves_with_path(made):
        names = [k.key for k in path]
        node = axes
        for n in names:
            node = node[n]
        assert len(node) == leaf.ndim, names


def test_a_large_leaf_is_drawn_in_pieces_and_never_in_float32(monkeypatch):
    """Above `WHOLE_DRAW_MAX` a leaf comes out of a `lax.map` over its leading
    axes, cast inside: the program holds no float32 array of the leaf's
    size."""
    shape = (4, 8, 32, 16)
    monkeypatch.setattr(pattern, "WHOLE_DRAW_MAX", 32 * 16)
    pattern._draw.clear_cache()
    text = pattern._draw.lower(jax.random.key(0), shape=shape, fan_in=32,
                              dtype=jnp.bfloat16).as_text()
    assert "while" in text and "f32[4,8,32,16]" not in text.replace(" ", "")
    leaf = pattern._draw(jax.random.key(0), shape=shape, fan_in=32,
                        dtype=jnp.bfloat16)
    assert leaf.shape == shape and leaf.dtype == jnp.bfloat16
    spread = float(jnp.std(leaf.astype(jnp.float32)))
    assert abs(spread - 32 ** -0.5) < 0.01
    pattern._draw.clear_cache()


@pytest.mark.parametrize("s", [5, 8, 16, 21, 40])
def test_band_attention_is_the_masked_attention(s):
    """Blocks of `window` queries against their own keys and the block
    before give what the [S, S] mask gives, at lengths below, at, a multiple
    of and not a multiple of the window."""
    w, rng = 8, np.random.default_rng(s)
    q = jnp.asarray(rng.normal(size=(2, s, 6, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, s, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, s, 2, 16)), jnp.float32)
    got = laguna._attend_band(q, k, v, w)
    i = np.arange(s)
    seen = (i[:, None] >= i[None, :]) & (i[:, None] - i[None, :] < w)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", q.reshape(2, s, 2, 3, 16), k) / 4
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    want = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v).reshape(2, s, 6, 16)
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_ring_rows_hold_the_last_window_positions():
    held = np.asarray(laguna._ring_positions(jnp.asarray([-1, 2, 7, 8, 21]), 8))
    assert (held[0] < 0).all()  # an empty sequence holds nothing
    assert held[1].tolist()[:3] == [0, 1, 2] and (held[1][3:] < 0).all()
    assert held[2].tolist() == list(range(8))
    assert held[3].tolist() == [8, 1, 2, 3, 4, 5, 6, 7]
    assert sorted(held[4].tolist()) == list(range(14, 22))
    assert all(p % 8 == r for r, p in enumerate(held[4].tolist()))


def test_rope_tables_agree_with_the_reference():
    conf = published(CFG)
    rot, inv, scale = laguna.full_rope_table(CFG)
    r_rot, r_inv, r_scale = R.inverse_frequencies(
        conf["rope_parameters"]["full_attention"], CFG.hd)
    assert rot == r_rot == 8 and scale == r_scale == CFG.rope_yarn[4]
    np.testing.assert_allclose(inv, r_inv, rtol=1e-6)
    # the published model's: 0.1 ln(128) + 1, and frequencies between the
    # plain ones and the plain ones over the factor
    wide = dataclasses.replace(
        CFG, head_dim=128, hidden=128,
        rope_yarn=(128.0, 8192.0, 32.0, 1.0, 1.4852030263919618))
    rot, inv, scale = laguna.full_rope_table(wide)
    plain = 5e5 ** (-np.arange(0, 64, 2) / 64)
    assert rot == 64 and abs(scale - (0.1 * np.log(128) + 1)) < 1e-12
    assert (inv <= plain * 1.0001).all() and (inv >= plain / 128 * 0.9999).all()
    assert inv[0] == 1.0 and abs(inv[-1] - plain[-1] / 128) < 1e-12


@jax.jit
def _step(params, tok, cache, mask):
    positions = cache.lengths[:, None]
    kv_mask = jnp.arange(cache.k.shape[2])[None, :] <= cache.lengths[:, None]
    logits, cache, aux = decoding.forward_cached(
        CFG, params, tok[:, None], positions, cache, kv_mask, mask[:, None])
    return logits[:, 0], aux, cache._replace(
        lengths=jnp.where(mask, cache.lengths + 1, cache.lengths))


def _step_logits(cb, tok, active):
    """One decode step's logits for every slot, through `forward_cached` on
    the batcher's own cache (the decode program returns tokens alone)."""
    logits, aux, cb.cache = _step(cb.params, jnp.asarray(tok), cb.cache,
                                  jnp.asarray(active))
    return np.asarray(logits), aux


def test_prefill_then_decode_past_two_wraps_against_the_reference(params):
    """(a) A prompt of 21 tokens (two and a half windows, shorter than its
    bucket of 32) through the batcher's prefill program and install, then 22
    decode steps beside two busy neighbour slots: positions reach 42, five
    times round the ring of 8. Every position's logits against ONE full
    forward of the reference."""
    cb = ContinuousBatcher(CFG, params, max_len=64, slots=3)
    cb.shutdown()
    prompts = [_prompt(1, 13), _prompt(2, 21), _prompt(3, 30)]
    firsts = []
    for slot, prompt in enumerate(prompts):
        last, row_k, row_v, ring_k, ring_v, load, choice, reached = \
            cb._prefill(prompt)
        assert choice.shape == (8, cb._bucket(len(prompt)), 4)
        assert ring_k.shape == (6, 8, 2, 16)
        assert row_k.shape[:2] == (3, cb._bucket(len(prompt)))
        assert int(load.sum()) == len(prompt) * 4 * 8  # pad rows not counted
        cb.cache = cb._install_jit(cb.cache, *cb._pad_row(row_k, row_v), slot,
                                   len(prompt), None, ring_k, ring_v)
        firsts.append(np.asarray(last))
    seqs = [list(p) for p in prompts]
    system = [[f] for f in firsts]
    tok = np.array([int(f.argmax()) for f in firsts], np.int32)
    for _ in range(22):
        for s, t in zip(seqs, tok):
            s.append(int(t))
        logits, aux = _step_logits(cb, tok, [True, True, True])
        assert int(aux["expert_load"].sum()) == 3 * 4 * 8
        for slot in range(3):
            system[slot].append(logits[slot])
        tok = logits.argmax(-1).astype(np.int32)
    conf = published(CFG)
    for slot, prompt in enumerate(prompts):
        n = len(system[slot])
        ref, _ = R.logits(params, np.asarray(seqs[slot])[None], conf, last=n)
        out = R.compare_logits(np.stack(system[slot]), np.asarray(ref[0]))
        assert out["rms_err_over_std"] < 2e-4, (slot, out)
        assert out["argmax_agree"] == 1.0


def test_the_scheduler_serves_it_beside_busy_slots(params):
    """(a) through `submit`: admit, pump, lookahead and retire; greedy tokens
    the reference ranks first at every position, the expert counters whole."""
    cb = ContinuousBatcher(CFG, params, max_len=64, slots=3)
    try:
        prompts = [_prompt(4, 19), _prompt(5, 9), _prompt(6, 33), _prompt(7, 12)]
        futs = [cb.submit(p, SamplingParams(max_tokens=20)) for p in prompts]
        outs = [f.result(300) for f in futs]
    finally:
        cb.shutdown()
    conf = published(CFG)
    for prompt, out in zip(prompts, outs):
        assert len(out) == 20
        ref, _ = R.logits(params, np.asarray(prompt + out[:-1])[None], conf,
                          last=20)
        got = R.compare_tokens(out, np.asarray(ref[0]))
        assert got["argmax_agree"] == 1.0, got
    st = cb.stats
    # (d) every routed assignment of a real row is counted, 4 a row a layer,
    # and each is held here or absent
    assert st["moe_assignments"] == 4 * st["moe_rows"] * CFG.sparse_layers
    absent = sum(st["moe_expert_load"][8:])
    assert st["moe_assignments_held"] == sum(st["moe_expert_load"][:8])
    assert st["moe_assignments_held"] + absent == st["moe_assignments"]
    assert 0.3 < st["moe_assignments_held"] / st["moe_assignments"] < 0.7
    assert 0 < st["moe_experts_reached"] <= 8 * CFG.sparse_layers * st["steps"]


def test_a_reused_slot_shows_nothing_of_its_last_occupant(params):
    """(b) One slot: a long prompt that decodes round the ring, then a prompt
    shorter than the window: its answer is the one a fresh engine gives, and
    after its install the ring's rows beyond its length are zero."""
    long_one, short = _prompt(8, 40), _prompt(9, 5)
    cb = ContinuousBatcher(CFG, params, max_len=64, slots=1)
    try:
        cb.submit(long_one, SamplingParams(max_tokens=20)).result(300)
        assert float(jnp.abs(cb.cache.ring_k).min(axis=(0, 3, 4)).min()) > 0
        reused = cb.submit(short, SamplingParams(max_tokens=12)).result(300)
    finally:
        cb.shutdown()
    fresh_cb = ContinuousBatcher(CFG, params, max_len=64, slots=1)
    try:
        fresh = fresh_cb.submit(short, SamplingParams(max_tokens=12)).result(300)
    finally:
        fresh_cb.shutdown()
    assert reused == fresh
    ref, _ = R.logits(params, np.asarray(short + reused[:-1])[None],
                      published(CFG), last=12)
    assert R.compare_tokens(reused, np.asarray(ref[0]))["argmax_agree"] == 1.0
    cb.cache = cb._empty_cache()._replace(
        ring_k=jnp.ones_like(cb.cache.ring_k),
        ring_v=jnp.ones_like(cb.cache.ring_v))
    _, row_k, row_v, ring_k, ring_v, *_ = cb._prefill(short)
    cache = cb._install_jit(cb.cache, *cb._pad_row(row_k, row_v), 0, 5, None,
                            ring_k, ring_v)
    assert float(jnp.abs(cache.ring_k[:, 0, 5:]).max()) == 0.0
    assert float(jnp.abs(cache.ring_k[:, 0, :5]).min()) > 0.0


def test_the_shares_add_up_to_the_uncut_layer(params):
    """(c) The routed parts the two shares give (experts 0-7 here, 8-15 on
    the other chip) plus the shared expert, which both compute alike, counted
    ONCE, are the uncut reference's whole layer."""
    whole = dataclasses.replace(CFG, experts_held=None)
    wparams = T.init_params(whole, jax.random.key(6))
    sparse = wparams["blocks"]["sparse"]
    rng = np.random.default_rng(0)
    y = jnp.asarray(rng.normal(size=(2, 7, CFG.hidden)), jnp.float32)
    layer = 3
    small = {n: a[layer] for n, a in sparse.items()
             if n not in laguna.EXPERT_LEAVES}
    parts = []
    for first in (0, 8):
        share = dataclasses.replace(CFG, experts_held=(first, 8))
        stacks = {n: sparse[n][:, first:first + 8]
                  for n in laguna.EXPERT_LEAVES}
        out, load = T.moe_dropless(share, y, dict(small, **stacks), None,
                                   layer)
        assert int(load.sum()) == 14 * 4  # the load is over all 16
        parts.append(out)
    shared = laguna._swiglu(y, small["shared_gate"], small["shared_up"],
                            small["shared_down"])
    # the uncut reference: every expert held, the shared expert inside
    stacks = {n: sparse[n].reshape(-1, *sparse[n].shape[2:])
              for n in laguna.EXPERT_LEAVES}
    with jax.default_matmul_precision("highest"):
        want, _, _ = R.sparse_mlp(
            y.reshape(14, -1), small, stacks, layer * 16, count=16, top_k=4,
            norm_topk_prob=True, scale=2.5, first=0)
    got = (parts[0] + parts[1] + shared).reshape(14, -1)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # and neither share alone is the layer
    assert float(jnp.abs(parts[0].reshape(14, -1) + shared.reshape(14, -1)
                         - want).max()) > 1e-2


def test_absent_assignments_contribute_exactly_zero(params):
    """(d) A token whose every expert is on the other chip gets exactly 0.0
    from the routed part; one whose experts are all here gets what the uncut
    layer gives it."""
    sparse = params["blocks"]["sparse"]
    p = {n: (a if n in laguna.EXPERT_LEAVES else a[0])
         for n, a in sparse.items()}
    y = jnp.asarray(np.random.default_rng(1).normal(size=(1, 3, CFG.hidden)),
                    jnp.float32)
    experts = jnp.asarray([[8, 9, 14, 15], [0, 3, 5, 7], [2, 12, 6, 9]])
    weights = jnp.full((3, 4), 0.625, jnp.float32)
    out, load = T.moe_dropless(CFG, y, p, None, 0, (weights, experts))
    assert float(jnp.abs(out[0, 0]).max()) == 0.0
    assert float(jnp.abs(out[0, 1]).max()) > 0.0
    assert load.tolist() == [1, 0, 1, 1, 0, 1, 1, 1, 1, 2, 0, 0, 1, 0, 1, 1]
    held_only, _ = T.moe_dropless(
        CFG, y, p, None, 0, (weights.at[2, 1].set(0.0).at[2, 3].set(0.0),
                             experts.at[2, 1].set(2).at[2, 3].set(6)))
    np.testing.assert_allclose(out[0, 2], held_only[0, 2], atol=1e-6)


def test_the_reference_follows_a_tie_and_refuses_another_set():
    """`router_weights(follow=...)`: the system's set is taken where its
    lowest expert has, by the reference's own probabilities, at least (1 -
    margin) of the k-th; the weights are then the reference's own over that
    set. A set further off is refused and the reference keeps its own."""
    y = jnp.eye(4, dtype=jnp.float32)
    logits = jnp.log(jnp.asarray([
        [0.40, 0.30, 0.151, 0.149],    # 3rd and 4th: a tie
        [0.40, 0.30, 0.20, 0.10],      # the 4th is half the 3rd: no tie
        [0.40, 0.30, 0.20, 0.10],      # the system agrees
        [0.25, 0.25, 0.25, 0.25]]))
    follow = jnp.asarray([[0, 1, 3], [0, 1, 3], [2, 1, 0], [3, 2, 1]])
    w, chosen, gap = R.router_weights(
        y, logits, top_k=3, norm_topk_prob=True, scale=2.0, follow=follow)
    assert sorted(chosen[0].tolist()) == [0, 1, 3] and 0 < float(gap[0]) < 0.02
    assert sorted(chosen[1].tolist()) == [0, 1, 2] and float(gap[1]) == -1.0
    assert float(gap[2]) == 0.0 and float(gap[3]) == 0.0
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.0, rtol=1e-6)
    np.testing.assert_allclose(
        w[0], 2.0 * np.array([0.40, 0.30, 0.0, 0.149]) / 0.849, rtol=1e-5)
    routes = R._routes(chosen[None], np.asarray(gap)[None])
    assert (routes["followed"], routes["refused"], routes["pairs"]) == (1, 1, 4)


@pytest.mark.parametrize("part", ["gate", "shared", "window", "rope", "scale"])
def test_the_reference_without_a_part_is_another_model(params, part):
    """What the chip check's second readings rest on: leaving out the gate,
    the shared expert, the window, the kinds' RoPE or the factor 2.5 moves
    the logits by far more than the system's rounding."""
    seq = np.asarray(_prompt(10, 30))[None]
    conf = published(CFG)
    ref, _ = R.logits(params, seq, conf, last=8)
    other, _ = R.logits(params, seq, conf, last=8, drop=(part,))
    assert R.compare_logits(np.asarray(other[0]),
                            np.asarray(ref[0]))["rms_err_over_std"] > 0.05


def test_each_refusal_names_the_pattern(params):
    """(e)"""
    from ray_tpu.models.disagg_prefill import DisaggPrefillEngine
    from ray_tpu.models.paged_kv import PagedBatcher

    with pytest.raises(ValueError, match="ring_k, ring_v.*pages hold no ring"):
        PagedBatcher(CFG, params, max_len=64, slots=2, page_size=16)
    with pytest.raises(ValueError, match="ring_k, ring_v.*KV channel"):
        DisaggPrefillEngine(CFG, params, max_len=64)
    with pytest.raises(ValueError, match="layer pattern.*cached forward"):
        T.forward(CFG, params, jnp.zeros((1, 8), jnp.int32))
    cache = decoding.init_cache(CFG, 1, 16)
    with pytest.raises(ValueError, match="layer pattern.*no other cache"):
        decoding.forward_cached(
            CFG, params, jnp.zeros((1, 1), jnp.int32),
            jnp.zeros((1, 1), jnp.int32), cache, jnp.ones((1, 16), bool),
            jnp.ones((1, 1), bool), access=lambda layer: None)


@pytest.mark.parametrize("change, says", [
    (dict(layer_kinds=("window", "ring")), "unknown layer kinds"),
    (dict(layers=8), "whole periods"),
    (dict(window=0), "needs window"),
    (dict(window_heads=5), "whole groups of kv_heads"),
    (dict(experts_held=(12, 8)), "no share of num_experts"),
    (dict(qk_norm=True), "qk_norm: no field of .*laguna"),
    (dict(rope_yarn=(4.0, 16.0)), "rope_yarn is"),
    (dict(layer_kinds=(), partial_rotary=1.0),
     "window, window_heads.*no field of .*transformer"),
])
def test_the_configuration_is_validated(change, says):
    with pytest.raises(ValueError, match=says):
        dataclasses.replace(CFG, **change)
