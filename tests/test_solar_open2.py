"""Delta-rule linear-attention layers whose steps reach 2 beside gated,
unrotated grouped attention over K/V rows, every layer sparse, through the
serving engine (models/kimi_linear.py's form without a lead,
upstage/Solar-Open2-250B) against the plain reference
(`benchmarks/reference_solar_open2.py`) at toy widths on the CPU: 8 layers
(two periods of gkv kda kda kda), 8 query heads of 16 on 2 K/V heads, 16
experts in four shares of 4, top-4, one shared."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_solar_open2 as R
from ray_tpu.models import decoding, kimi_linear as K
from ray_tpu.models import transformer as T
from ray_tpu.models.continuous_batching import ContinuousBatcher
from ray_tpu.models.decoding import SamplingParams

CFG = T.config("solar_open2_debug")


def published(cfg) -> dict:
    """The keys `reference_solar_open2` reads, as a `config.json` spells
    them (layers numbered from 0)."""
    return {
        "num_hidden_layers": cfg.layers, "rms_norm_eps": cfg.norm_eps,
        "num_key_value_heads": cfg.kv_heads, "gqa_interval": 3,
        "gqa_layers": [l for l, k in enumerate(cfg.kinds) if k == "gkv"],
        "num_experts_per_tok": cfg.experts_per_token,
        "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scale,
        "experts_held_first": cfg.experts_held[0] if cfg.experts_held else 0,
    }


CONF = published(CFG)


@pytest.fixture(scope="module")
def params():
    return T.init_params(CFG, jax.random.key(5))


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


def test_prefill_then_decode_is_the_reference(params):
    """Prompts of 21 and 70 tokens (the second through the 128 bucket: four
    chunks of the scan, and shorter than its bucket) prefilled by the
    batcher's own program, installed, then 12 decode steps beside each other:
    every position's logits against ONE full forward of the reference. The
    steps' beta passes 1 (the factor 2 is there)."""
    cb = ContinuousBatcher(CFG, params, max_len=128, slots=2)
    cb.shutdown()
    assert CFG.keeps == ("k", "v", "mat", "conv") and CFG.full_layers == 2
    prompts = [_prompt(2, 21), _prompt(3, 70)]
    firsts = []
    for slot, prompt in enumerate(prompts):
        last, row_k, row_v, mat, conv, load, choice, reached = \
            cb._prefill(prompt)
        bucket = cb._bucket(len(prompt))
        assert row_k.shape == row_v.shape == (2, bucket, 2, 16)
        assert mat.shape == (6, 8, 16, 16) and mat.dtype == jnp.float32
        assert conv.shape == (6, 3 * 3 * 8 * 16)
        assert choice.shape == (8, bucket, 4)  # EVERY layer routes
        assert int(load.sum()) == len(prompt) * 4 * 8  # pad rows not counted
        cb.cache = cb._install_jit(cb.cache, *cb._pad_row(row_k, row_v), slot,
                                   len(prompt), None, None, None, mat, conv)
        firsts.append(np.asarray(last))
    seqs = [list(p) for p in prompts]
    system = [[f] for f in firsts]
    tok = np.array([int(f.argmax()) for f in firsts], np.int32)
    for _ in range(12):
        for s, t in zip(seqs, tok):
            s.append(int(t))
        logits, cb.cache, aux = _step(cb.params, jnp.asarray(tok), cb.cache,
                                      jnp.asarray([True, True]))
        assert int(aux["expert_load"].sum()) == 2 * 4 * 8
        for slot in range(2):
            system[slot].append(np.asarray(logits[slot]))
        tok = np.asarray(logits).argmax(-1).astype(np.int32)
    for slot in range(2):
        n = len(system[slot])
        ref, _ = R.logits(params, np.asarray(seqs[slot])[None], CONF, last=n)
        out = R.compare_logits(np.stack(system[slot]), np.asarray(ref[0]))
        assert out["rms_err_over_std"] < 2e-4, (slot, out)
        assert out["argmax_agree"] == 1.0


def test_the_scheduler_serves_it_with_slots_taken_again(params):
    """Through `submit`: five requests through two slots, so that a slot is
    taken again with a state and rows in it; how far below the reference's
    first choice each greedy token lies in the reference's LOGITS (one full
    forward a request); every state installed is given back; the counters by
    kind."""
    cb = ContinuousBatcher(CFG, params, max_len=64, slots=2)
    try:
        prompts = [_prompt(4, 19), _prompt(5, 9), _prompt(6, 33),
                   _prompt(7, 12), _prompt(8, 40)]
        futs = [cb.submit(p, SamplingParams(max_tokens=12)) for p in prompts]
        outs = [f.result(300) for f in futs]
    finally:
        cb.shutdown()
    for prompt, out in zip(prompts, outs):
        ref, _ = R.logits(params, np.asarray(prompt + out[:-1])[None], CONF,
                          last=12)
        got = R.compare_tokens(out, np.asarray(ref[0]))
        assert got["max_shortfall_over_std"] < 1e-3, got
    st = cb.stats
    # off the chip both delta-rule choices say so, by program (what
    # `engine_stats()["kda_path"]` carries)
    assert cb.kda_path["decode"] == "state:plain"
    assert {cb.kda_path[f"prefill_{b}"] for b in (16, 32, 64)} == {
        "scan:plain"}
    assert st["state_installs"] == st["state_resets"] == 5
    assert st["moe_assignments"] == 4 * st["moe_rows"] * 8
    assert st["moe_assignments_held"] == sum(st["moe_expert_load"][:4])
    assert 0.1 < st["moe_assignments_held"] / st["moe_assignments"] < 0.45
    # the rows read are the two gkv layers' alone; a kda layer keeps none
    assert st["kv_rows_held"] % CFG.layers_of("gkv") == 0
    assert st["kv_rows_held"] > 0
    assert not np.asarray(cb.cache.mat).any()
    assert not np.asarray(cb.cache.conv).any()


@pytest.mark.parametrize("chunk", [4, 32])
def test_the_chunked_scan_is_the_token_scan_with_steps_up_to_two(chunk):
    """`kda_chunks` against `kda_step` a position at a time with beta in (0,
    2): the unit-triangular system `I + beta tril(A_kk)` with entries twice
    as large, one key repeated thirty times at beta 1.98 (the state along it
    flips its sign every position: eigenvalue -0.98), from a state that is
    not zero, pads that must leave the state alone."""
    b, s, h, d = 2, 70, 3, 8
    ks = jax.random.split(jax.random.key(chunk), 6)
    q, k, v = (jax.random.normal(key, (b, s, h, d)) for key in ks[:3])
    k = k.at[0, 10:40].set(k[0, 10])
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    log_a = -jnp.exp(jax.random.normal(ks[3], (b, s, h, d)) - 2.0)
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (b, s, h)))
    beta = beta.at[0, 10:40].set(1.98)
    log_a = log_a.at[0, 10:40].set(0.0)  # nothing decays: the flip alone
    log_a = log_a.at[1, 60:].set(0.0)
    beta = beta.at[1, 60:].set(0.0)
    assert float(beta.max()) > 1.9 and float((1 - beta).min()) < -0.9
    state = jax.random.normal(ks[5], (b, h, d, d))
    want, outs, along = state, [], []
    for t in range(s):
        want, o = K.kda_step(want, q[:, t], k[:, t], v[:, t] * 0 if 10 <= t
                             < 40 else v[:, t], log_a[:, t], beta[:, t])
        outs.append(o)
        along.append(jnp.einsum("hk,hkv->hv", k[0, 10], want[0]))
        if t == 59:
            at_60 = want
    v = v.at[:, 10:40].set(0.0)
    # with v = 0 the state along the repeated key is multiplied by 1 - beta
    # each position: it changes its sign 30 times and shrinks by 0.98^30
    np.testing.assert_allclose(along[20], -0.98 * along[19], rtol=1e-4,
                               atol=1e-6)
    got, o = jax.jit(functools.partial(K.kda_chunks, chunk=chunk))(
        state, q, k, v, log_a, beta)
    np.testing.assert_allclose(o, jnp.stack(outs, 1), atol=5e-5)
    np.testing.assert_allclose(got, want, atol=5e-5)
    np.testing.assert_array_equal(want[1], at_60[1])  # pads changed nothing
    assert np.isfinite(np.asarray(got)).all()


@pytest.fixture
def kernels_through_the_interpreter(monkeypatch):
    """`tests/test_llm.py`'s: the chip's path on the CPU, blocks of 16."""
    import jax.experimental.pallas as pl

    from ray_tpu.ops import attention as A

    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    monkeypatch.setattr(A, "DECODE_BLOCK_ROWS", 16)
    monkeypatch.setattr(A, "DECODE_THIN_BLOCK_ROWS", 16)
    monkeypatch.setattr(A, "DENSE_SCORES_BYTES", 0)
    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    return A


def test_the_state_kernel_takes_steps_up_to_two(
        kernels_through_the_interpreter):
    """`ops.delta_rule.state_update` through the interpreter at the
    configuration's shape cut small (6 heads, not 32: the kernel's loop is
    over whatever it is given) with beta in (0, 2): float32 states whose
    eigenvalue along k is negative, against `kda_step`; the same update
    twice along one key flips the state's sign twice."""
    from ray_tpu.ops import delta_rule

    n, b, h, d, layer = 2, 3, 6, 128, 1
    ks = jax.random.split(jax.random.key(3), 6)
    mat = jax.random.normal(ks[0], (n, b, h, d, d))
    q, k, v = (jax.random.normal(key, (b, h, d)) for key in ks[1:4])
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    log_a = -jnp.exp(jax.random.normal(ks[4], (b, h, d)) - 2).at[2].set(0.0)
    beta = (2 * jax.nn.sigmoid(2 + jax.random.normal(ks[5], (b, h)))
            ).at[2].set(0.0).at[0].set(1.9)
    assert delta_rule.state_update_takes(mat) and float(beta.max()) > 1.7
    want, o_want = K.kda_step(mat[layer], q, k, v, log_a, beta)
    update = jax.jit(delta_rule.state_update)
    got, o = update(mat, layer, q, k, v, log_a, beta)
    np.testing.assert_allclose(got[layer], want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(o, o_want, rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(got[layer, 2], mat[layer, 2])
    np.testing.assert_array_equal(got[0], mat[0])
    # sequence 0, no decay, v = 0: S^T k is multiplied by 1 - 1.9 a step
    still = jnp.zeros_like(log_a)
    once, _ = update(mat, layer, q, k, v * 0, still, beta)
    before = jnp.einsum("hk,hkv->hv", k[0], mat[layer, 0])
    after = jnp.einsum("hk,hkv->hv", k[0], once[layer, 0])
    np.testing.assert_allclose(after, -0.9 * before, rtol=1e-4, atol=1e-5)


def test_a_gated_step_reads_groups_of_eight_with_the_decode_kernel(
        kernels_through_the_interpreter):
    """One "gkv" layer's decode step through `decode_attention` (booked
    "kernel") at the configuration's grouping cut small: 16 query heads of
    128 on 2 K/V heads, groups of EIGHT that adjoin, the gate on; against
    the same layer off the chip (the XLA spelling)."""
    from ray_tpu.ops import traced

    cfg = T.config("solar_open2_debug", heads=16, kv_heads=2, head_dim=128,
                   layers=4)
    p = {n: a[0] for n, a in T.init_params(
        cfg, jax.random.key(1))["blocks"]["gkv"].items()}
    b, t = 3, 48
    ks = jax.random.split(jax.random.key(2), 3)
    k_cache, v_cache = (jax.random.normal(key, (1, b, t, 2, 128))
                        for key in ks[:2])
    x = jax.random.normal(ks[2], (b, 1, cfg.hidden))
    lengths = jnp.array([40, 0, 17])
    rows = jnp.array([41, 0, 18])
    mask = jnp.arange(t)[None] <= lengths[:, None]

    def run(rows):
        with traced.booked() as seen:
            out = K.gkv_attention(cfg, x, p, lengths[:, None], k_cache,
                                  v_cache, mask, 0, rows)
        return out, dict(seen)

    (got, gk, gv), seen = run(rows)
    assert seen["held_rows"] == {"kernel"}
    kernels_through_the_interpreter._on_tpu = lambda: False
    (want, wk, wv), seen = run(rows)
    assert seen["held_rows"] == {"dense"}
    np.testing.assert_array_equal(gk, wk)
    got, want = (np.asarray(a, np.float32)[[0, 2]] for a in (got, want))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_the_shares_add_up_to_the_uncut_layer(params):
    """The routed parts that ALL the shares give (four of 4 experts here,
    sixteen of 20 in the deployment) plus the shared expert, which every
    chip computes alike, counted ONCE, are the uncut reference's whole
    layer; and the program's layer for a share is that share's part."""
    whole = dataclasses.replace(CFG, experts_held=None)
    sparse = T.init_params(whole, jax.random.key(6))["blocks"]["sparse"]
    rng = np.random.default_rng(0)
    y = jnp.asarray(rng.normal(size=(14, CFG.hidden)), jnp.float32)
    layer = 3
    small = {n: a[layer] for n, a in sparse.items()
             if n not in R.EXPERT_LEAVES}
    stacks = {n: sparse[n].reshape(-1, *sparse[n].shape[2:])
              for n in R.EXPERT_LEAVES}
    firsts = (0, 4, 8, 12)
    with jax.default_matmul_precision("highest"):
        w, chosen, _ = R.router_weights(y, small, top_k=4, renormalize=True,
                                        scale=CFG.routed_scale)
        parts = [R.routed_part(y, w, stacks, layer * 16 + first, first, 4)
                 for first in firsts]
        shared = R.swiglu(y, small["shared_gate"], small["shared_up"],
                          small["shared_down"])
        uncut = R.routed_part(y, w, stacks, layer * 16, 0, 16) + shared
    np.testing.assert_allclose(sum(parts) + shared, uncut, atol=1e-5)
    assert all(np.abs(np.asarray(p)).max() > 0.01 for p in parts)
    for part, first in zip(parts, firsts):
        cfg = dataclasses.replace(CFG, experts_held=(first, 4))
        p = dict(small, **{n: sparse[n][:, first:first + 4]
                           for n in R.EXPERT_LEAVES})
        routed, _ = T.moe_dropless(cfg, y[None], p, None, layer,
                                   K.router(cfg, y, p))
        np.testing.assert_allclose(routed[0], part, atol=1e-5)
        np.testing.assert_array_equal(np.sort(K.router(cfg, y, p)[1]),
                                      np.sort(chosen))


@pytest.mark.parametrize("change", [
    dict(drop=("gate",)), dict(drop=("beta2",)), dict(drop=("groups",)),
    dict(drop=("conv",)), dict(drop=("shared",)),
    dict(state="bfloat16"), dict(precision="bfloat16")])
def test_the_reference_with_a_fault_is_another_model(params, change):
    """Each of the seven faults the chip's check must refuse moves the
    reference's own logits, the large ones by a large share of their spread
    (the two precisions by little at toy widths and in 8 layers: the chip's
    limits are set at the published ones)."""
    toks = np.asarray(_prompt(11, 43))[None]  # no whole eights of keys
    ref, _ = R.logits(params, toks, CONF, last=8)
    other, _ = R.logits(params, toks, CONF, last=8, **change)
    off = R.compare_logits(np.asarray(other[0]), np.asarray(ref[0]))
    floor = 0.05 if "drop" in change else 1e-4
    assert off["rms_err_over_std"] > floor, (change, off)


def test_the_stated_precision_is_no_fault(params):
    """`precision="stated"` (bfloat16 operands and stream, float32 sums) is
    a reading, not a fault: it stands nearer the float32 reference than any
    dropped part, and further than the float32 state's rounding."""
    toks = np.asarray(_prompt(11, 43))[None]  # no whole eights of keys
    ref, _ = R.logits(params, toks, CONF, last=8)
    stated, _ = R.logits(params, toks, CONF, last=8, precision="stated")
    off = R.compare_logits(np.asarray(stated[0]), np.asarray(ref[0]))
    assert 1e-3 < off["rms_err_over_std"] < 0.3, off


@pytest.mark.parametrize("change, says", [
    (dict(layers=9), "whole periods of layer_kinds"),
    (dict(tail_kinds=("kda",)), "nothing behind them"),
    (dict(dense_mlp_hidden=64), "lead_kind '' states no leading"),
    (dict(kv_heads=3), "whole groups of kv_heads 3"),
    (dict(kda_conv=1), "kda_conv 1 is its taps"),
    (dict(num_experts=0, experts_held=None), "num_experts"),
    (dict(layer_kinds=("gkv", "mla"), layers=4), "mla_latent"),
    (dict(layer_kinds=("gkv",), layers=2), "no layer of this pattern is"),
    (dict(layer_kinds=("gkv", "gqa"), layers=4), "unknown layer kinds"),
    (dict(window=8), "window: no field of ray_tpu.models.kimi_linear"),
    (dict(lead_kind="kda", layers=9), "dense_mlp_hidden is its width"),
])
def test_the_configuration_is_validated(change, says):
    with pytest.raises(ValueError, match=says):
        T.config("solar_open2_debug", **change)


@pytest.mark.parametrize("preset, field", [
    ("kimi_linear_debug", dict(gqa_gate=True)),
    ("kimi_linear_debug", dict(kv_heads=2)),
    ("nemotron_h_debug", dict(gqa_gate=True)),
    ("nemotron_h_debug", dict(kda_neg_eigval=True)),
    ("laguna_debug", dict(kda_neg_eigval=True)),
    ("debug", dict(gqa_gate=True)),
])
def test_the_new_fields_are_refused_by_name_elsewhere(preset, field):
    name = next(iter(field))
    with pytest.raises(ValueError, match=name):
        T.config(preset, **field)
