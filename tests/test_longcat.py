"""Double layers with the expert layer on a shortcut through the serving
engine (models/longcat.py): two latent attentions a layer with a low-rank
query and a rotated shared key, one cache row a position and SUBLAYER, a
softmax router over routed and zero-compute outputs, a held share of the
routed experts, against the plain reference (`benchmarks/reference_longcat.
py`) at toy widths on the CPU: 3 double layers, 4 heads of 16 + 8 rotated, a
latent of 32, a query through 24, top-4 of 16 routed (4 held) + 8 zero-compute
outputs, times 6."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_longcat as R
from ray_tpu.models import decoding, kimi_linear as K, longcat as L
from ray_tpu.models import transformer as T
from ray_tpu.models.continuous_batching import ContinuousBatcher
from ray_tpu.models.decoding import SamplingParams

CFG = T.config("longcat_debug")
# Program and reference are both float32 here and differ by the order of
# their sums (a cache and absorbed steps against one expanded forward; sorted
# rows against one expert at a time): logits agree to 1e-5 of their spread.
# The limit stands a decade above that and two below what ONE bfloat16
# rounding of a float32 part moves them by (`test_lower_precision_...`).
LOGITS_RMS_MAX = 2e-4


def published(cfg, **changed) -> dict:
    """The keys `reference_longcat` reads, as the model's `config.json`
    spells them."""
    return dict({
        "num_layers": cfg.layers, "rms_norm_eps": cfg.norm_eps,
        "hidden_size": cfg.hidden, "ffn_hidden_size": cfg.dense_mlp_hidden,
        "qk_nope_head_dim": cfg.hd, "qk_rope_head_dim": cfg.mla_rope_dim,
        "kv_lora_rank": cfg.mla_latent, "q_lora_rank": cfg.mla_q_rank,
        "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
        "rope_theta": cfg.rope_theta, "moe_topk": cfg.experts_per_token,
        "routed_scaling_factor": cfg.routed_scale,
        "published": {"n_routed_experts": cfg.num_experts},
        "experts_held_first": cfg.experts_held[0] if cfg.experts_held else 0,
    }, **changed)


@pytest.fixture(scope="module")
def params():
    return T.init_params(CFG, jax.random.key(5))


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n).tolist()


@jax.jit
def _step(params, tok, cache, active):
    positions = cache.lengths[:, None]
    kv_mask = jnp.arange(cache.latent.shape[2])[None, :] <= positions
    rows = jnp.where(active, cache.lengths + 1, 0)
    logits, cache, aux = decoding.forward_cached(
        CFG, params, tok[:, None], positions, cache, kv_mask,
        active[:, None], rows=rows)
    return logits[:, 0], cache._replace(
        lengths=jnp.where(active, cache.lengths + 1, cache.lengths)), aux


def _through_the_cache(params, prompts, steps):
    """Each prompt prefilled by the batcher's own program and installed in
    its slot, then `steps` greedy decode steps of the batcher's program body
    beside each other. Returns (the sequences as they grew, every position's
    logits from the last prompt position on)."""
    cb = ContinuousBatcher(CFG, params, max_len=128, slots=len(prompts))
    cb.shutdown()
    system = []
    for slot, prompt in enumerate(prompts):
        last, row_k, row_v, latent, load, choice, reached, most, gathered = \
            cb._prefill(prompt)
        bucket = cb._bucket(len(prompt))
        assert row_k.shape[0] == 0
        assert latent.shape == (2 * CFG.layers, bucket, 128)  # by SUBLAYER
        assert not np.asarray(latent[..., 40:]).any()
        assert choice.shape == (CFG.layers, bucket, 4)
        assert load.shape == (24,)  # the zero-compute outputs counted apart
        assert int(load.sum()) == len(prompt) * 4 * CFG.layers
        assert 0 <= int(most) <= 4
        assert int(gathered) == bucket * 4 * CFG.layers  # 4 of 24 held: all
        cb.cache = cb._install_jit(cb.cache, *cb._pad_row(row_k, row_v), slot,
                                   len(prompt), None, None, None, None, None,
                                   latent)
        system.append([np.asarray(last)])
    seqs = [list(p) for p in prompts]
    tok = np.array([int(s[0].argmax()) for s in system], np.int32)
    active = jnp.ones(len(prompts), bool)
    for _ in range(steps):
        for s, t in zip(seqs, tok):
            s.append(int(t))
        logits, cb.cache, aux = _step(cb.params, jnp.asarray(tok), cb.cache,
                                      active)
        assert int(aux["expert_load"].sum()) == len(prompts) * 4 * CFG.layers
        for slot in range(len(prompts)):
            system[slot].append(np.asarray(logits[slot]))
        tok = np.asarray(logits).argmax(-1).astype(np.int32)
    return seqs, [np.stack(s) for s in system]


@pytest.fixture(scope="module")
def served(params):
    """Prompts of 21 and 70 tokens (the second through the 128 bucket and
    shorter than it) and 10 decoded positions, two slots of unequal
    length."""
    return _through_the_cache(params, [_prompt(2, 21), _prompt(3, 70)], 10)


@pytest.mark.parametrize("slot", [0, 1])
def test_prefill_then_decode_is_the_reference(params, served, slot):
    """Every position's LOGITS, the prompt's last and ten decoded through
    the cache, against ONE full forward of the reference."""
    seqs, system = served
    n = len(system[slot])
    ref, _ = R.logits(params, np.asarray(seqs[slot])[None], published(CFG),
                      last=n)
    out = R.compare_logits(system[slot], np.asarray(ref[0]))
    assert out["rms_err_over_std"] < LOGITS_RMS_MAX, out
    assert out["argmax_agree"] == 1.0


@pytest.mark.parametrize("part", ["router", "softmax", "weighted_sum"])
def test_lower_precision_where_float32_is_stated_is_refused(
        params, served, part, monkeypatch):
    """ONE float32 part of the program rounded to bfloat16 (the router's
    scores, the attention's softmax, the experts' weighted sum) moves the
    logits past the limit the program is held to."""
    bf16 = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    if part == "router":
        scores = jax.nn.softmax
        monkeypatch.setattr(K.jax.nn, "softmax", lambda x, axis=-1: (
            bf16(scores(x, axis=axis)) if x.shape[-1] == CFG.router_outputs
            else scores(x, axis=axis)))
    elif part == "softmax":
        scores = jax.nn.softmax
        monkeypatch.setattr(K.jax.nn, "softmax", lambda x, axis=-1: (
            scores(x, axis=axis) if x.shape[-1] == CFG.router_outputs
            else bf16(scores(x, axis=axis))))
    else:
        router = K.router
        monkeypatch.setattr(L, "router", lambda *a: (
            lambda w, e: (bf16(w), e))(*router(*a)))
    jax.clear_caches()
    try:
        seqs, system = _through_the_cache(
            params, [_prompt(2, 21), _prompt(3, 70)], 2)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    worst = 0.0
    for slot in range(2):
        ref, _ = R.logits(params, np.asarray(seqs[slot])[None],
                          published(CFG), last=3)
        worst = max(worst, R.compare_logits(
            system[slot], np.asarray(ref[0]))["rms_err_over_std"])
    assert worst > LOGITS_RMS_MAX, worst


def test_the_absorbed_step_is_the_expanded_form_with_the_rotation(params):
    """One sublayer: positions 0..19 prefilled (expanded), then position 20
    by a decode step (absorbed against the cached `[c ; rot(k_r)]`, the
    step's own query rotated at ITS position), against positions 0..20
    prefilled at once; the rows the step left are the prefill's; and the
    rotation is in it (the same step at a shifted position differs)."""
    p = jax.tree.map(lambda a: a[1, 0], params["blocks"]["mla"])
    x = jax.random.normal(jax.random.key(0), (2, 21, CFG.hidden))
    pos = jnp.broadcast_to(jnp.arange(21), (2, 21))
    stack = jnp.zeros((6, 2, 32, CFG.latent_row))
    whole, full = K.mla_attention(CFG, x, p, pos, stack, None,
                                  jnp.ones((2, 21), bool), 2)
    _, stack = K.mla_attention(CFG, x[:, :20], p, pos[:, :20], stack, None,
                               jnp.ones((2, 20), bool), 2)
    args = (jnp.arange(32)[None] <= pos[:, 20:], jnp.ones((2, 1), bool), 2)
    step, after = K.mla_attention(CFG, x[:, 20:], p, pos[:, 20:], stack,
                                  *args, rows=jnp.array([21, 21]))
    np.testing.assert_allclose(step[:, 0], whole[:, 20], atol=1e-5)
    np.testing.assert_allclose(after, full, atol=1e-6)
    assert not np.asarray(after[1]).any() and not np.asarray(after[3]).any()
    flat = dataclasses.replace(CFG, mla_rotate=False)
    other, _ = K.mla_attention(flat, x[:, 20:], p, pos[:, 20:], stack, *args,
                               rows=jnp.array([21, 21]))
    assert np.abs(np.asarray(other - step)).max() > 1e-3


@pytest.mark.parametrize("block", [4, 16, 30])
def test_the_blocked_prefill_is_the_plain_one(block):
    """Queries a block at a time against the keys up to the block's end (a
    last block that is not whole; pad positions behind a sequence's true
    length) give every real row what the whole [H, S, S] array gives."""
    b, s, h, d, r = 2, 37, 3, 8, 4
    ks = jax.random.split(jax.random.key(block), 5)
    q_n, k_n, v = (jax.random.normal(k, (b, s, h, d)) for k in ks[:3])
    q_r = jax.random.normal(ks[3], (b, s, h, r))
    k_r = jax.random.normal(ks[4], (b, s, r))
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    real = jnp.arange(s)[None] < jnp.array([37, 25])[:, None]
    args = (q_n, q_r, k_n, k_r, v, pos, real, 0.3)
    plain = K._attend_expanded(*args)
    blocked = K._attend_expanded(*args, block)
    np.testing.assert_allclose(blocked, plain, atol=1e-6)
    # and the rule that chooses it follows from the shapes alone
    assert CFG.heads * 128 * 128 * 4 <= K.PREFILL_LOGITS_MAX < 64 * 4096**2 * 4


def test_the_engine_prefills_with_the_flash_kernel_where_it_takes_the_shape(
        monkeypatch):
    """The batcher's own prefill program at heads of 128 + 64 rotated beside
    values of 128 (the published widths), a prompt of 200 tokens in the 256
    bucket with pad rows behind it, the flash forward through the Pallas
    interpreter (as `tests/test_mimo.py::kernels_through_the_interpreter`):
    the engine books "flash" for the bucket, the last position's logits are
    the reference's, and logits, latent rows, load and choices are those of
    the expanded form, which the same engine runs off the chip and books as
    "dense"."""
    import functools

    import jax.experimental.pallas as pl

    from ray_tpu.ops import attention as A

    cfg = T.config("longcat_debug", head_dim=128, mla_rope_dim=64, layers=2,
                   max_seq=256)
    wide = T.init_params(cfg, jax.random.key(8))
    prompt = _prompt(9, 200)

    def prefill():
        cb = ContinuousBatcher(cfg, wide, max_len=256, slots=1)
        cb.shutdown()
        return cb._prefill(prompt), cb.prefill_attention_path

    want, path = prefill()
    assert path == {"prefill_256": "dense"}
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    monkeypatch.setattr(A, "DENSE_SCORES_BYTES", 0)
    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    got, path = prefill()
    assert path == {"prefill_256": "flash"}
    ref, _ = R.logits(wide, np.asarray(prompt)[None], published(cfg), last=1)
    out = R.compare_logits(np.asarray(got[0])[None], np.asarray(ref[0]))
    assert out["rms_err_over_std"] < LOGITS_RMS_MAX, out
    assert out["argmax_agree"] == 1.0
    for a, b in zip(got, want):
        # a pad row sees other keys than under the mask, and is nobody's:
        # the rows [sublayers, S, 640] and choices [layers, S, k] by position
        a, b = (np.asarray(x)[:, :200] if x.ndim == 3 else np.asarray(x)
                for x in (a, b))
        if np.issubdtype(a.dtype, np.integer):
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(a, b, atol=2e-5)


def test_a_long_prompts_expert_rows_go_a_part_at_a_time(params, monkeypatch):
    """A prompt of 70 tokens through the 128 bucket with the expert layer
    called 32 rows at a time gives the logits, the rows, the load and the
    choices of the one call over all 128 rows."""
    cb = ContinuousBatcher(CFG, params, max_len=128, slots=1)
    cb.shutdown()
    whole = cb._prefill(_prompt(3, 70))
    monkeypatch.setattr(L, "EXPERT_ROWS", 32)
    cb._prefill_jits.clear()
    parts = cb._prefill(_prompt(3, 70))
    for a, b in zip(whole, parts):
        if jnp.issubdtype(a.dtype, jnp.integer):
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(a, b, atol=1e-5)
    assert L.EXPERT_ROWS == 32 and int(parts[4].sum()) == 70 * 4 * CFG.layers


def _expert_parameters(cfg, key=6):
    sparse = T.init_params(dataclasses.replace(cfg, experts_held=None),
                           jax.random.key(key))["blocks"]["sparse"]
    return sparse, {n: a for n, a in sparse.items()
                    if n not in R.EXPERT_LEAVES}


@pytest.mark.parametrize("zero_choices, says", [
    (4, "all"), (0, "none"), (2, "mixed")])
def test_a_token_with_zero_compute_choices(zero_choices, says):
    """A token whose choices are all zero-compute outputs gets the sum of
    their weights times its input and nothing else; one with none gets its
    held experts' part alone; a mixed one both; and an absent expert adds
    exactly nothing to any of them."""
    cfg = dataclasses.replace(CFG, experts_held=(4, 4))
    sparse, _ = _expert_parameters(cfg)
    layer = 1
    p = {n: sparse[n][:, 4:8] for n in R.EXPERT_LEAVES}
    y = jax.random.normal(jax.random.key(1), (1, 5, cfg.hidden))
    # held 4..7, absent 0..3 and 8..15, zero-compute 16..23
    chosen = [16, 23, 19, 20][:zero_choices] + [5, 6, 4, 7][zero_choices:]
    experts = jnp.tile(jnp.asarray(chosen), (5, 1)).at[3, -1].set(9)
    weights = jnp.asarray(np.random.default_rng(0).uniform(
        0.1, 0.9, (5, 4)), jnp.float32)
    out, load = T.moe_dropless(cfg, y, p, None, layer, (weights, experts))
    w = jnp.sum(jax.nn.one_hot(experts, 24) * weights[..., None], axis=1)
    stacks = {n: sparse[n].reshape(-1, *sparse[n].shape[2:])
              for n in R.EXPERT_LEAVES}
    with jax.default_matmul_precision("highest"):
        want = R.expert_layer(y[0], w, stacks, layer * 16 + 4, 4, 4, 16)
    np.testing.assert_allclose(out[0], want, atol=1e-5)
    if says == "all":  # row 3's last choice is absent: it adds nothing
        rows = np.array(weights.sum(-1))
        rows[3] -= float(weights[3, -1])
        np.testing.assert_allclose(out[0], rows[:, None] * np.asarray(y[0]),
                                   rtol=1e-6, atol=1e-7)
    assert load.shape == (24,)
    assert int(load[16:].sum()) == 5 * zero_choices - (says == "all")
    assert int(load[4:8].sum()) == 5 * (4 - zero_choices) - (says != "all")
    assert int(load[9]) == 1


@pytest.mark.parametrize("crowded, says", [(False, "the first rows"),
                                           (True, "the whole layout")])
def test_a_thin_share_gathers_its_first_rows_alone(crowded, says):
    """4 of 128 outputs held (under a thirty-second): 32 rows' 128
    assignments sort the held ones first and the first 64 alone are
    gathered, multiplied and added to their tokens; where more than 64 are
    held (every row crowds onto the held four) the whole layout runs. Either
    way the result is the reference's, nothing dropped."""
    cfg = dataclasses.replace(CFG, num_experts=120, experts_held=(8, 4))
    assert T.held_rows_cap(cfg, 32 * 4) == 64
    assert T.held_rows_cap(cfg, 16 * 4) is None  # would not halve the rows
    assert T.held_rows_cap(CFG, 32 * 4) is None  # 4 of 24: no thin share
    sparse, _ = _expert_parameters(cfg)
    p = {n: sparse[n][:, 8:12] for n in R.EXPERT_LEAVES}
    y = jax.random.normal(jax.random.key(3), (1, 32, cfg.hidden))
    rng = np.random.default_rng(int(crowded))
    if crowded:  # three of each row's four on the held experts: 96 > 64
        experts = np.stack([np.concatenate([
            rng.permutation(np.arange(8, 12))[:3], [120 + t % 8]])
            for t in range(32)])
    else:  # chosen evenly over the 128 outputs: about 4 held
        experts = np.stack([rng.permutation(128)[:4] for _ in range(32)])
    experts = jnp.asarray(experts, jnp.int32)
    weights = jnp.asarray(rng.uniform(0.1, 0.9, (32, 4)), jnp.float32)
    held = int(((experts >= 8) & (experts < 12)).sum())
    assert (held > 64) == crowded and held > 0
    assert int(T.rows_gathered(cfg, experts)) == (128 if crowded else 64)
    out, load = jax.jit(lambda y, w, e: T.moe_dropless(
        cfg, y, p, None, 2, (w, e)))(y, weights, experts)
    w = jnp.sum(jax.nn.one_hot(experts, 128) * weights[..., None], axis=1)
    stacks = {n: sparse[n].reshape(-1, *sparse[n].shape[2:])
              for n in R.EXPERT_LEAVES}
    with jax.default_matmul_precision("highest"):
        want = R.expert_layer(y[0], w, stacks, 2 * 120 + 8, 8, 4, 120)
    np.testing.assert_allclose(out[0], want, atol=1e-5)
    assert int(load[8:12].sum()) == held and int(load.sum()) == 128


def test_the_shares_add_up_to_the_uncut_double_layer():
    """32 shares of 2 experts each (64 routed + 8 zero-compute outputs): the
    program's expert layer for a share is that share's held part plus the
    zero-compute part; and over all 32 shares the held parts, with the
    zero-compute part and the dense path (both attentions, both dense MLPs)
    counted ONCE, add up to the uncut reference's double layer."""
    shares, each = 32, 2
    cfg = dataclasses.replace(CFG, num_experts=shares * each,
                              experts_held=(0, each), layers=2)
    whole = T.init_params(dataclasses.replace(cfg, experts_held=None),
                          jax.random.key(6))["blocks"]
    conf = published(cfg)
    i = 1
    at = lambda tree: jax.tree.map(lambda a: a[i], tree)  # noqa: E731
    small = {n: a for n, a in whole["sparse"].items()
             if n not in R.EXPERT_LEAVES}
    experts = {n: whole["sparse"][n] for n in R.EXPERT_LEAVES}
    x = jax.random.normal(jax.random.key(2), (1, 14, cfg.hidden))
    uncut, chosen, _ = R.double_layer(x, at(whole["mla"]), at(whole["dense"]),
                                      at(small), experts, i, None, conf)
    # count=0: no held expert, so the dense path and the zero-compute part
    once, _, _ = R.double_layer(x, at(whole["mla"]), at(whole["dense"]),
                                at(small), experts, i, None, conf, count=0)
    pos = jnp.broadcast_to(jnp.arange(14), (1, 14))
    real = jnp.ones((1, 14), bool)
    total, reached = 0.0, 0
    for share in range(shares):
        first = share * each
        held = dataclasses.replace(cfg, experts_held=(first, each))
        mine = {n: a[:, first:first + each] for n, a in experts.items()}
        out, _, (load, picked, r, most, _) = L.double_layer(
            held, x, L.sublayers(whole["mla"], i),
            L.sublayers(whole["dense"], i),
            dict(at(small), **mine), pos,
            jnp.zeros((4, 1, 14, cfg.latent_row)), None, real, i)
        np.testing.assert_array_equal(np.sort(picked), np.sort(chosen))
        # the program given a share is the reference given the same share
        part, _, _ = R.double_layer(
            x, at(whole["mla"]), at(whole["dense"]), at(small), mine, i, None,
            dict(conf, experts_held_first=first))
        np.testing.assert_allclose(out, part, atol=2e-5)
        # and the shares' parts are summed free of the dense path, which the
        # reference computes alike in every call: its 32 copies cancel exactly
        total, reached = total + (part - once), reached + int(r)
    np.testing.assert_allclose(total + once, uncut, atol=2e-5)
    # every routed choice is somebody's: the shares' reached experts are the
    # experts the tokens chose
    assert reached == len(np.unique(chosen[chosen < shares * each]))
    assert np.abs(np.asarray(uncut - once)).max() > 0.01
    assert int((np.asarray(chosen) >= shares * each).sum()) > 0


@pytest.mark.parametrize("drop", ["rotate", "scale_q", "scale_kv", "zero",
                                  "bias", "scale", "shortcut"])
def test_the_reference_without_a_part_is_another_model(params, drop):
    """Each part the reference can leave out or misplace moves its logits far
    beyond what the system differs by; the selection bias moves the sets."""
    tokens = np.asarray(_prompt(11, 24))[None]
    conf = published(CFG)
    whole, routes = R.logits(params, tokens, conf)
    other, other_routes = R.logits(params, tokens, conf, drop=(drop,))
    err = R.compare_logits(np.asarray(other[0]), np.asarray(whole[0]))
    if drop == "bias":  # it moves the sets alone: a tenth of a score's mean
        assert (np.sort(routes["chosen"]) != np.sort(other_routes["chosen"])
                ).any()
    else:
        assert err["rms_err_over_std"] > 100 * LOGITS_RMS_MAX, err


def test_the_reference_follows_a_tie_and_refuses_another_set(params):
    tokens = np.asarray(_prompt(12, 16))[None]
    conf = published(CFG)
    _, own = R.logits(params, tokens, conf)
    _, same = R.logits(params, tokens, conf, follow=own["chosen"])
    assert same["followed"] == same["refused"] == 0
    far = (own["chosen"] + 7) % CFG.router_outputs
    _, other = R.logits(params, tokens, conf, follow=far)
    assert other["refused"] > 0


def test_the_scheduler_serves_it_beside_busy_slots(params):
    """Through `submit`: admit, pump, lookahead and retire; greedy tokens the
    reference ranks first at every position; the expert counters whole, the
    three classes apart."""
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
    assert "state_installs" not in st  # rows alone: nothing to reset
    assert len(st["moe_expert_load"]) == 24
    assert st["moe_assignments"] == 4 * st["moe_rows"] * CFG.layers
    assert st["moe_assignments_held"] == sum(st["moe_expert_load"][:4])
    assert st["moe_assignments_zero"] == sum(st["moe_expert_load"][16:])
    assert st["moe_assignments"] == st["moe_assignments_held"] \
        + st["moe_assignments_zero"] + st["moe_assignments_absent"]
    assert 0.2 < st["moe_assignments_zero"] / st["moe_assignments"] < 0.5
    # the static layout: every program gathers k rows a row it computes, a
    # pad row's and a free slot's too
    assert st["moe_rows_gathered"] >= st["moe_assignments"]  # pad rows too
    assert st["moe_calls_whole_layout"] == 0  # 4 of 24 held: no cap stands
    programs = st["steps"] + st["admitted"]
    assert programs <= st["moe_routed_most"] <= 4 * programs
    # two cache layers a double layer
    assert st["kv_rows_held"] % (2 * CFG.layers) == 0
    assert cb.cache.latent.shape[0] == 2 * CFG.layers


@pytest.mark.parametrize("change, says", [
    (dict(lead_kind="full"), "unknown layer kinds"),
    (dict(layer_kinds=("scmoe", "mla")), "unknown layer kinds"),
    (dict(tail_kinds=("scmoe",)), "tail_kinds: no field of"),
    (dict(mla_latent=0), "needs mla_latent"),
    (dict(mla_rope_dim=7), "even mla_rope_dim"),
    (dict(dense_mlp_hidden=0), "needs mla_latent"),
    (dict(router_score="sigmoid"), "router_score: no field of"),
    (dict(shared_expert_hidden=64), "shared_expert_hidden: no field of"),
    (dict(kda_conv=4), "kda_conv: no field of"),
    (dict(window=8), "window: no field of .*longcat"),
    (dict(experts_held=(14, 4)), "no share of num_experts"),
    (dict(layer_kinds=(), lead_kind="full"),
     "mla_latent.*no field of .*transformer"),
])
def test_the_configuration_is_validated(change, says):
    with pytest.raises(ValueError, match=says):
        dataclasses.replace(CFG, **change)


@pytest.mark.parametrize("preset, change, says", [
    ("kimi_linear_debug", dict(zero_experts=4),
     "zero_experts: no field of .*kimi_linear"),
    ("laguna_debug", dict(mla_rotate=True), "mla_rotate: no field of"),
    ("debug", dict(mla_q_rank=8), "mla_q_rank: no field of"),
])
def test_the_other_blocks_refuse_its_fields(preset, change, says):
    with pytest.raises(ValueError, match=says):
        dataclasses.replace(T.config(preset), **change)


def test_each_refusal_names_what_it_refuses(params):
    from ray_tpu.models.disagg_prefill import DisaggPrefillEngine
    from ray_tpu.models.paged_kv import PagedBatcher

    assert not CFG.stateful and CFG.keeps == ("latent",)
    assert CFG.kinds == ("scmoe",) * 3 and CFG.sparse_layers == 3
    assert CFG.layers_of("mla") == 0 and CFG.latent_layers == 6
    with pytest.raises(ValueError, match="latent.*pages hold no"):
        PagedBatcher(CFG, params, max_len=64, slots=2, page_size=16)
    with pytest.raises(ValueError, match="latent.*KV channel"):
        DisaggPrefillEngine(CFG, params, max_len=64)
    with pytest.raises(ValueError, match="layer pattern.*cached forward"):
        T.forward(CFG, params, jnp.zeros((1, 8), jnp.int32))
    cache = decoding.init_cache(CFG, 1, 16)
    with pytest.raises(ValueError, match="layer pattern.*no other cache"):
        decoding.forward_cached(
            CFG, params, jnp.zeros((1, 1), jnp.int32),
            jnp.zeros((1, 1), jnp.int32), cache, jnp.ones((1, 16), bool),
            jnp.ones((1, 1), bool), access=lambda layer: None)


def test_a_kimi_latent_layer_takes_the_fields_too():
    """The fields are a latent-attention sublayer's, whichever pattern holds
    it: Kimi-Linear's pattern with a low-rank query, the rotation and the
    factors has the leaves for them and runs."""
    cfg = dataclasses.replace(
        T.config("kimi_linear_debug"), mla_q_rank=24, mla_rotate=True,
        mla_scales=(1.5, 2.0))
    params = T.init_params(cfg, jax.random.key(0))
    assert params["blocks"]["mla"]["wq_a"].shape == (3, 128, 24)
    assert params["blocks"]["mla"]["wq"].shape == (3, 24, 4 * 24)
    cb = ContinuousBatcher(cfg, params, max_len=32, slots=1)
    try:
        out = cb.submit(_prompt(1, 9), SamplingParams(max_tokens=3)).result(300)
    finally:
        cb.shutdown()
    assert len(out) == 3
