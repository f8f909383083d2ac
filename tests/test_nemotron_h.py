"""Layers of ONE sublayer each through the serving engine
(models/nemotron_h.py): Mamba-2 mixers that keep a float32 state and a
convolution window, attentions of 2 KV heads without rotation, ReLU^2 experts
in a latent behind a sigmoid router with a selection bias, a held share of
them, against the plain reference (`benchmarks/reference_nemotron_h.py`) at
toy widths on the CPU: the 11 layers M E M E M * E M E M *, 8 heads of 8 in 2
groups and a state of 16, 4 heads on 2, 16 experts in two shares of 8, top-4
in a latent of 32."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_nemotron_h as R
from ray_tpu.models import decoding, nemotron_h as N
from ray_tpu.models import pattern
from ray_tpu.models import transformer as T
from ray_tpu.models.continuous_batching import ContinuousBatcher
from ray_tpu.models.decoding import SamplingParams
from ray_tpu.ops import ssd

CFG = T.config("nemotron_h_debug")
LETTER = {"ssm": "M", "gqa": "*", "lmoe": "E"}


def published(cfg) -> dict:
    """The keys `reference_nemotron_h` reads, as a `config.json` spells
    them."""
    return {
        "num_hidden_layers": cfg.layers,
        "hybrid_override_pattern": "".join(LETTER[k] for k in cfg.kinds),
        "layer_norm_epsilon": cfg.norm_eps, "mamba_num_heads": cfg.ssm_heads,
        "n_groups": cfg.ssm_groups, "num_key_value_heads": cfg.kv_heads,
        "head_dim": cfg.hd, "num_experts_per_tok": cfg.experts_per_token,
        "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scale,
        "experts_held_first": cfg.experts_held[0] if cfg.experts_held else 0,
    }


@pytest.fixture(scope="module")
def params():
    """Seeded weights; the convolution's bias, zero as initialised, drawn
    here so that leaving it out shows."""
    out = T.init_params(CFG, jax.random.key(5))
    bias = out["blocks"]["ssm"]["conv_b"]
    out["blocks"]["ssm"]["conv_b"] = 0.5 * jax.random.normal(
        jax.random.key(6), bias.shape, bias.dtype)
    return out


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n).tolist()


@jax.jit
def _step(params, tok, cache, active):
    positions = cache.lengths[:, None]
    kv_mask = jnp.arange(cache.k.shape[2])[None, :] <= positions
    rows = jnp.where(active, cache.lengths + 1, 0)
    logits, cache, aux = decoding.forward_cached(
        CFG, params, tok[:, None], positions, cache, kv_mask,
        active[:, None], rows=rows)
    return logits[:, 0], cache._replace(
        lengths=jnp.where(active, cache.lengths + 1, cache.lengths)), aux


def _step_logits(cb, tok, active):
    """One decode step of the batcher's own program body, its logits kept."""
    logits, cb.cache, aux = _step(cb.params, jnp.asarray(tok), cb.cache,
                                  jnp.asarray(active))
    return np.asarray(logits), aux


def _install(cb, slot, prompt):
    last, row_k, row_v, mat, conv, load, choice, reached = cb._prefill(prompt)
    cb.cache = cb._install_jit(cb.cache, *cb._pad_row(row_k, row_v), slot,
                               len(prompt), None, None, None, mat, conv)
    return last, row_k, mat, conv, load, choice


def test_the_loop_is_read_off_the_string():
    """A run of kinds that repeats is one scan, what is left is unrolled;
    the published prefix cuts into three scans and four single layers."""
    letters = tuple("MEMEMEM*EMEMEMEM*EMEME")
    cut = N.runs(letters)
    assert cut == [(("M", "E"), 3), (("M",), 1), (("*",), 1),
                   (("E", "M"), 4), (("*",), 1), (("E", "M"), 2),
                   (("E",), 1)]
    assert sum(len(u) * r for u, r in cut) == 22
    assert N.runs(CFG.layer_kinds) == [
        (("ssm", "lmoe"), 2), (("ssm",), 1), (("gqa",), 1),
        (("lmoe", "ssm"), 2), (("gqa",), 1)]
    assert N.runs(("a", "b", "c")) == [(("a",), 1), (("b",), 1), (("c",), 1)]
    assert N.runs(("a",) * 5) == [(("a",), 5)]
    assert CFG.sparse_layers == 4 and CFG.keeps == ("k", "v", "mat", "conv")


def test_prefill_then_decode_is_the_reference(params):
    """Prompts of 21 and 70 tokens (the second through the 128 bucket: nine
    chunks of the scan, the last not whole, and shorter than its bucket)
    prefilled by the batcher's own program, installed, then 14 decode steps
    beside each other: every position's logits against ONE full forward of
    the reference."""
    cb = ContinuousBatcher(CFG, params, max_len=128, slots=2)
    cb.shutdown()
    prompts = [_prompt(2, 21), _prompt(3, 70)]
    firsts = []
    for slot, prompt in enumerate(prompts):
        last, row_k, mat, conv, load, choice = _install(cb, slot, prompt)
        bucket = cb._bucket(len(prompt))
        assert row_k.shape == (2, bucket, 2, 16)
        assert mat.shape == (5, 16, 64) and mat.dtype == jnp.float32
        assert conv.shape == (5, 3 * (64 + 2 * 2 * 16))
        assert choice.shape == (4, bucket, 4)
        assert int(load.sum()) == len(prompt) * 4 * 4  # pad rows not counted
        firsts.append(np.asarray(last))
    seqs = [list(p) for p in prompts]
    system = [[f] for f in firsts]
    tok = np.array([int(f.argmax()) for f in firsts], np.int32)
    for _ in range(14):
        for s, t in zip(seqs, tok):
            s.append(int(t))
        logits, aux = _step_logits(cb, tok, [True, True])
        assert int(aux["expert_load"].sum()) == 2 * 4 * 4
        assert aux["expert_choice"].shape == (4, 2, 4)
        for slot in range(2):
            system[slot].append(logits[slot])
        tok = logits.argmax(-1).astype(np.int32)
    conf = published(CFG)
    for slot in range(2):
        n = len(system[slot])
        ref, routes = R.logits(params, np.asarray(seqs[slot])[None], conf,
                               last=n)
        out = R.compare_logits(np.stack(system[slot]), np.asarray(ref[0]))
        assert out["rms_err_over_std"] < 2e-5, (slot, out)
        assert out["argmax_agree"] == 1.0
        assert routes["chosen"].shape == (4, len(seqs[slot]), 4)


def _as_heads(state, heads):
    """The program's [B, state, heads * head_dim] as the reference's [B,
    heads, head_dim, state]."""
    b, n, lanes = state.shape
    return jnp.moveaxis(state.reshape(b, n, heads, lanes // heads), 1, 3)


@pytest.mark.parametrize("chunk", [4, 32, 128])
def test_the_chunked_scan_is_the_token_scan(chunk):
    """State and outputs of `ssm_chunks` against `ssm_step` a position at a
    time, from a state that is not zero, over 70 positions (a last chunk
    that is not whole; one chunk longer than the sequence), one head
    decaying by e^-30 a position, one not at all, and a run of pad positions
    (dt 0: decay 1, nothing added) that must leave the state alone: the
    state a prompt shorter than its bucket leaves is that of its TRUE last
    position."""
    b, s, h, p, g, n = 2, 70, 4, 8, 2, 16
    ks = jax.random.split(jax.random.key(chunk), 6)
    x = jax.random.normal(ks[0], (b, s, h, p))
    bm, cm = (jax.random.normal(key, (b, s, g, n)) for key in ks[1:3])
    dt = jax.nn.softplus(jax.random.normal(ks[3], (b, s, h)))
    rate = jnp.exp(jax.random.normal(ks[4], (h,)))
    dt = dt.at[1, 60:].set(0.0)
    log_a = -rate * dt
    log_a = log_a.at[..., 0].set(jnp.where(dt[..., 0] > 0, -30.0, 0.0))
    log_a = log_a.at[..., 1].set(0.0)
    state = jax.random.normal(ks[5], (b, n, h * p))
    want, outs = state, []
    for t in range(s):
        want, o = ssd.ssm_step(
            want, jnp.repeat(jnp.exp(log_a[:, t]), p, axis=-1),
            jnp.repeat(dt[:, t], p, axis=-1) * x[:, t].reshape(b, -1),
            bm[:, t], cm[:, t])
        outs.append(o.reshape(b, h, p))
        if t == 59:
            at_60 = want
    got, o = jax.jit(functools.partial(ssd.ssm_chunks, chunk=chunk))(
        state, x, dt, log_a, bm, cm)
    np.testing.assert_allclose(o, jnp.stack(outs, 1), atol=5e-5)
    np.testing.assert_allclose(got, want, atol=5e-5)
    np.testing.assert_array_equal(want[1], at_60[1])  # pads changed nothing
    assert np.isfinite(np.asarray(got)).all()
    # the same recurrence as the reference spells it, a head's [P, N] state
    per = h // g
    ref = _as_heads(state, h)
    for t in range(s):
        a = jnp.exp(log_a[:, t])[..., None, None]
        ref = a * ref + (dt[:, t, :, None] * x[:, t])[..., None] \
            * jnp.repeat(bm[:, t], per, axis=1)[:, :, None, :]
    np.testing.assert_allclose(_as_heads(got, h), ref, atol=5e-5)


def test_a_short_prompt_leaves_its_true_last_window_and_state(params):
    """The prefill program of a prompt shorter than its bucket (21 of 32, and
    no multiple of the chunk of 8) leaves the state and the convolution
    window that the same 21 tokens leave a position at a time."""
    cb = ContinuousBatcher(CFG, params, max_len=64, slots=1)
    cb.shutdown()
    prompt = _prompt(12, 21)
    _, _, mat, conv, _, _ = _install(cb, 0, prompt)
    want = decoding.init_cache(CFG, 1, 64)
    for t, token in enumerate(prompt):
        _, want, _ = decoding.forward_cached(
            CFG, params, jnp.asarray([[token]]), jnp.asarray([[t]]), want,
            jnp.arange(64)[None] <= t, jnp.ones((1, 1), bool),
            rows=jnp.asarray([t + 1]))
    np.testing.assert_allclose(mat, want.mat[:, 0], atol=2e-5)
    np.testing.assert_allclose(conv, want.conv[:, 0], atol=2e-5)
    np.testing.assert_allclose(cb.cache.k[:, 0, :21], want.k[:, 0, :21],
                               atol=2e-5)
    assert np.abs(np.asarray(mat)).max() > 0.01


def test_the_state_kernel_is_the_step(monkeypatch):
    """`ops.ssd.ssm_state_update` through the interpreter against `ssm_step`
    on the same stack: a layer that is not the first, the other layers
    untouched, a sequence that takes no part (decay 1, dt 0) kept bit for
    bit."""
    import jax.experimental.pallas as pl

    from ray_tpu.ops import attention as A

    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    layers, b, n, heads, p, groups, layer = 3, 4, 16, 8, 64, 2, 1
    lanes = heads * p
    ks = jax.random.split(jax.random.key(3), 5)
    mat = jax.random.normal(ks[0], (layers, b, n, lanes))
    a = jnp.repeat(jnp.exp(-jnp.exp(jax.random.normal(ks[1], (b, heads)))),
                   p, axis=-1).at[2].set(1.0)
    dtx = jax.random.normal(ks[2], (b, lanes)).at[2].set(0.0)
    bm, cm = (jax.random.normal(key, (b, groups, n)) for key in ks[3:])
    assert ssd.ssm_state_update_takes(mat)
    assert not ssd.ssm_state_update_takes(mat.astype(jnp.bfloat16))
    want, o_want = ssd.ssm_step(mat[layer], a, dtx, bm, cm)
    got, o = jax.jit(ssd.ssm_state_update)(mat, layer, a, dtx, bm, cm)
    np.testing.assert_allclose(got[layer], want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(o, o_want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got[layer, 2], mat[layer, 2])
    np.testing.assert_array_equal(got[0], mat[0])
    np.testing.assert_array_equal(got[2], mat[2])


def test_the_scheduler_serves_it_beside_busy_slots(params):
    """Through `submit`: admit, pump, lookahead and retire; greedy tokens the
    reference ranks first at every position; every state installed is given
    back; the expert counters whole, with the k of this model; the bytes of
    state the steps rewrote."""
    cb = ContinuousBatcher(CFG, params, max_len=64, slots=3)
    try:
        prompts = [_prompt(4, 19), _prompt(5, 9), _prompt(6, 33),
                   _prompt(7, 12)]
        futs = [cb.submit(p, SamplingParams(max_tokens=16)) for p in prompts]
        outs = [f.result(300) for f in futs]
    finally:
        cb.shutdown()
    conf = published(CFG)
    for prompt, out in zip(prompts, outs):
        assert len(out) == 16
        ref, _ = R.logits(params, np.asarray(prompt + out[:-1])[None], conf,
                          last=16)
        got = R.compare_tokens(out, np.asarray(ref[0]))
        assert got["argmax_agree"] == 1.0, got
    st = cb.stats
    assert st["state_installs"] == st["state_resets"] == 4
    assert st["moe_assignments"] == 4 * st["moe_rows"] * CFG.sparse_layers
    assert st["moe_assignments_held"] == sum(st["moe_expert_load"][:8])
    assert 0.3 < st["moe_assignments_held"] / st["moe_assignments"] < 0.7
    assert 0 < st["moe_experts_reached"] <= 8 * CFG.sparse_layers * st["steps"]
    # the attention layers' rows are read as held, a mixer keeps none
    assert st["kv_rows_held"] % CFG.layers_of("gqa") == 0
    # a step's every active sequence: 5 layers' states and windows, each way
    a_sequence = 2 * 5 * (16 * 64 * 4 + 3 * 128 * 4)
    assert st["state_bytes_rewritten"] % a_sequence == 0
    assert st["steps"] <= st["state_bytes_rewritten"] // a_sequence \
        <= 3 * st["steps"]
    # every slot was given back: no state, no window is anyone's
    assert not np.asarray(cb.cache.mat).any()
    assert not np.asarray(cb.cache.conv).any()


def test_a_reused_slot_shows_nothing_of_its_last_occupant(params):
    """One slot: a long prompt that decodes on, then a short one: its answer
    is the one a fresh engine gives (state and window reset); equal prompts
    in different slots, beside each other, give equal answers; an install
    overwrites the slot's whole state, window and rows, and a step leaves a
    free slot's state as it is."""
    long_one, short = _prompt(8, 40), _prompt(9, 5)
    cb = ContinuousBatcher(CFG, params, max_len=64, slots=1)
    try:
        cb.submit(long_one, SamplingParams(max_tokens=20)).result(300)
        reused = cb.submit(short, SamplingParams(max_tokens=12)).result(300)
    finally:
        cb.shutdown()
    fresh_cb = ContinuousBatcher(CFG, params, max_len=64, slots=3)
    try:
        futs = [fresh_cb.submit(p, SamplingParams(max_tokens=12))
                for p in (short, long_one, short)]
        fresh, _, twin = (f.result(300) for f in futs)
    finally:
        fresh_cb.shutdown()
    assert reused == fresh == twin
    cb = ContinuousBatcher(CFG, params, max_len=64, slots=2)
    cb.shutdown()
    ones = {n: jnp.ones_like(getattr(cb.cache, n))
            for n in ("k", "v", "mat", "conv")}
    cb.cache = cb._empty_cache()._replace(**ones)
    _, row_k, mat, conv, _, _ = _install(cb, 0, short)
    cache = cb.cache
    # its own rows of bucket length (pad positions masked by the length),
    # then zeros: nothing of the ones that were there
    assert not np.asarray(cache.k[:, 0, 16:]).any()
    assert (np.asarray(cache.k[:, 0, :5]) != 1).all()
    np.testing.assert_array_equal(cache.mat[:, 0], mat)
    np.testing.assert_array_equal(cache.conv[:, 0], conv)
    assert (np.asarray(cache.mat[:, 1]) == 1).all()
    mat = np.asarray(mat)
    # a step for slot 0 alone: slot 1's states stay; then slot 0 is released
    _step_logits(cb, np.zeros(2, np.int32), [True, False])
    assert (np.asarray(cb.cache.mat[:, 1]) == 1).all()
    assert (np.asarray(cb.cache.conv[:, 1]) == 1).all()
    assert np.abs(np.asarray(cb.cache.mat[:, 0]) - mat).max() > 0
    cleared = cb._reset_state_jit(cb.cache, 0)
    assert not np.asarray(cleared.mat[:, 0]).any()
    assert not np.asarray(cleared.conv[:, 0]).any()
    assert (np.asarray(cleared.mat[:, 1]) == 1).all()


def test_the_shares_add_up_to_the_uncut_layer(params):
    """The routed parts the two shares give IN the latent (experts 0-7 here,
    8-15 on the other chip), through the latent's way out, which both hold
    whole, ONCE, plus the shared expert, which both compute alike, counted
    ONCE, are the uncut reference's whole layer; and the program's layer for
    a share is that share's part through `latent_up` plus the shared
    expert."""
    whole = dataclasses.replace(CFG, experts_held=None)
    sparse = T.init_params(whole, jax.random.key(6))["blocks"]["sparse"]
    assert sparse["wi_up"].shape == (4, 16, 32, 48) and "wi_gate" not in sparse
    assert pattern.expert_names(CFG) == R.EXPERT_LEAVES
    rng = np.random.default_rng(0)
    y = jnp.asarray(rng.normal(size=(14, CFG.hidden)), jnp.float32)
    layer = 3
    small = {n: a[layer] for n, a in sparse.items()
             if n not in R.EXPERT_LEAVES}
    stacks = {n: sparse[n].reshape(-1, *sparse[n].shape[2:])
              for n in R.EXPERT_LEAVES}
    kw = dict(top_k=4, renormalize=True, scale=CFG.routed_scale)
    with jax.default_matmul_precision("highest"):
        w, chosen, _ = R.router_weights(y, small, **kw)
        latent = y @ small["latent_down"]
        parts = [R.routed_part(latent, w, stacks, layer * 16 + first, first, 8)
                 for first in (0, 8)]
        shared = R.relu2(y, small["shared_up"], small["shared_down"])
        uncut = R.routed_part(latent, w, stacks, layer * 16, 0, 16) \
            @ small["latent_up"] + shared
        summed = (parts[0] + parts[1]) @ small["latent_up"] + shared
    np.testing.assert_allclose(summed, uncut, atol=1e-5)
    assert np.abs(np.asarray(parts[0])).max() > 0.01
    for share, first in enumerate((0, 8)):
        cfg = dataclasses.replace(CFG, experts_held=(first, 8))
        p = dict(small, **{n: sparse[n][:, first:first + 8]
                           for n in R.EXPERT_LEAVES})
        x, load, picked, reached = N.sparse_mlp(
            cfg, y[None] * 0, dict(p, ln_mlp=p["ln_mlp"] * 0), None, layer,
            N.router)
        assert not np.asarray(x).any()  # zeros in, zeros out: no bias
        # the program's whole sublayer on rows whose norm is y (unit RMS)
        rows = y / jnp.sqrt(jnp.mean(y * y, -1, keepdims=True) + CFG.norm_eps)
        with jax.default_matmul_precision("highest"):
            w_n, chosen_n, _ = R.router_weights(rows, small, **kw)
            want = R.routed_part(
                rows @ small["latent_down"], w_n, stacks,
                layer * 16 + first, first, 8) @ small["latent_up"] \
                + R.relu2(rows, small["shared_up"], small["shared_down"])
            x, load, picked, reached = N.sparse_mlp(
                cfg, y[None], p, None, layer, N.router)
        np.testing.assert_allclose(x[0] - y, want, atol=2e-5)
        np.testing.assert_array_equal(np.sort(picked), np.sort(chosen_n))
        assert int(load.sum()) == 14 * 4 and 0 < int(reached) <= 8


@pytest.mark.parametrize("change", [
    dict(state="bfloat16"), dict(drop=("relu",)), dict(drop=("d",)),
    dict(drop=("conv_bias",)), dict(drop=("scale",)), dict(drop=("decay",)),
    dict(drop=("gate",)), dict(drop=("bias",)), dict(drop=("shared",))])
def test_the_reference_without_a_part_is_another_model(params, change):
    """Each part the reference can leave out moves its logits far beyond
    what the system differs by (under 2e-5 above, 7e-7 as measured; a
    bfloat16 state reads 4.7e-4 over these 24 positions); the selection bias
    moves the sets that are chosen."""
    tokens = np.asarray(_prompt(11, 24))[None]
    conf = published(CFG)
    whole, routes = R.logits(params, tokens, conf)
    other, other_routes = R.logits(params, tokens, conf, **change)
    err = R.compare_logits(np.asarray(other[0]), np.asarray(whole[0]))
    floor = 2e-4 if change.get("state") else 2e-2
    assert err["rms_err_over_std"] > floor, err
    if change.get("drop") == ("bias",):
        assert (np.sort(routes["chosen"][0])
                != np.sort(other_routes["chosen"][0])).any()


def test_the_reference_follows_a_tie_and_refuses_the_rest(params):
    """`follow`: the reference's own sets are followed with no gap; sets of
    experts it scores far below its k-th are refused and it keeps its own."""
    tokens = np.asarray(_prompt(13, 12))[None]
    conf = published(CFG)
    whole, routes = R.logits(params, tokens, conf)
    same, told = R.logits(params, tokens, conf, follow=routes["chosen"])
    assert told["followed"] == told["refused"] == 0
    np.testing.assert_array_equal(same, whole)
    # every token told the four experts the reference ranks LAST
    worst = np.tile(np.arange(4), (4, 12, 1))
    _, told = R.logits(params, tokens, conf, follow=jnp.asarray(worst))
    assert told["refused"] + told["followed"] > 0
    assert told["pairs"] == 4 * 12 and told["margin"] == R.ROUTE_TIE_MARGIN


@pytest.mark.parametrize("change, says", [
    (dict(layer_kinds=("ssm", "kda")), "unknown layer kinds"),
    (dict(layers=10), "names every one of the 10 layers"),
    (dict(lead_kind="ssm"), "no leading layer"),
    (dict(ssm_conv=0), "needs ssm_heads"),
    (dict(ssm_groups=3), "needs ssm_heads in whole ssm_groups"),
    (dict(kv_heads=3), "whole groups of kv_heads"),
    (dict(num_experts=0, experts_held=None), "needs num_experts"),
    (dict(window=8), "window: no field of .*nemotron_h"),
    (dict(kda_conv=4), "kda_conv: no field of .*nemotron_h"),
    (dict(qk_norm=True), "qk_norm: no field of"),  # (tied: the family's)
    (dict(expert_act="gelu"), "unknown expert_act"),
    (dict(experts_held=(12, 8)), "no share of num_experts"),
])
def test_the_configuration_is_validated(change, says):
    with pytest.raises(ValueError, match=says):
        dataclasses.replace(CFG, **change)


def test_each_refusal_names_what_it_refuses(params):
    from ray_tpu.models.disagg_prefill import DisaggPrefillEngine
    from ray_tpu.models.paged_kv import PagedBatcher

    assert CFG.stateful and CFG.keeps == ("k", "v", "mat", "conv")
    with pytest.raises(ValueError, match="mat, conv.*pages hold no"):
        PagedBatcher(CFG, params, max_len=64, slots=2, page_size=16)
    with pytest.raises(ValueError, match="mat, conv.*KV channel"):
        DisaggPrefillEngine(CFG, params, max_len=64)
    with pytest.raises(ValueError, match="layer pattern.*cached forward"):
        T.forward(CFG, params, jnp.zeros((1, 8), jnp.int32))
    cache = decoding.init_cache(CFG, 1, 16)
    with pytest.raises(ValueError, match="layer pattern.*no other cache"):
        decoding.forward_cached(
            CFG, params, jnp.zeros((1, 1), jnp.int32),
            jnp.zeros((1, 1), jnp.int32), cache, jnp.ones((1, 16), bool),
            jnp.ones((1, 1), bool), access=lambda layer: None)
    with pytest.raises(ValueError, match="moe_latent, expert_act: no field "
                                         "of .*kimi_linear"):
        dataclasses.replace(T.config("kimi_linear_debug"), moe_latent=32,
                            expert_act="relu2")
