"""OLMoE-1B-7B through the serving forward, at debug widths on the CPU: the
dropless expert layer, the router's two normalisation rules and the QK-norm
against ``benchmarks/reference_olmoe.py`` (plain float32 ``jax.numpy``, no
sort, no grouped matmul, nothing from ``ray_tpu.models``), and the sparse
model through every engine that shares ``_block_cached``.

Tolerances. In float32 both sides compute the same sums in another order (a
grouped matmul over sorted rows against one expert at a time over all rows):
differences are a few float32 roundings, 1e-6 relative, so 1e-5 passes and a
dropped assignment (1/k of a token's output), a renormalised router (the
weights scaled by 1/sum, tens of percent) or a bfloat16-accumulated matmul
(1e-2) fail by orders of magnitude. In bfloat16 the bound is
``reference.py``'s: RMS error over the reference's standard deviation under
5%, for the reasons written there.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness, reference_olmoe as R
from ray_tpu.models import transformer as T
from ray_tpu.models.continuous_batching import ContinuousBatcher
from ray_tpu.models.decoding import (
    Generator, SamplingParams, forward_cached, init_cache)

REL = 1e-5  # float32 against float32: see the module docstring
CONF = dict(hidden_size=128, intermediate_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=4, head_dim=32,
            vocab_size=512, rope_theta=1e4, rms_norm_eps=1e-5,
            max_position_embeddings=128, num_experts=16,
            num_experts_per_tok=4, norm_topk_prob=False)


def _cfg(**overrides):
    return T.config("olmoe_debug", **overrides)


def _layer(cfg, seed=0, layer=0):
    params = T.init_params(cfg, jax.random.key(seed))
    return jax.tree.map(lambda a: a[layer], params["blocks"])


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    params = T.init_params(cfg, jax.random.key(1))
    # the norms start as ones: give them something to do
    for name in ("ln_q", "ln_k"):
        params["blocks"][name] = 0.5 + jax.random.uniform(
            jax.random.key(len(name)), params["blocks"][name].shape)
    return cfg, params


def test_preset_and_parameter_count():
    """The published widths, and `num_params` counts every leaf (the two
    QK-norm vectors too)."""
    big = T.config("olmoe_1b_7b")
    per_layer_experts = 3 * 64 * 2048 * 1024
    assert per_layer_experts == 402_653_184  # 94% of a layer
    assert big.num_params() == sum(
        np.prod(s.shape) for s in jax.tree.leaves(jax.eval_shape(
            lambda: T.init_params(big, jax.random.key(0)))))
    assert 6.9e9 < big.num_params() < 7.0e9
    cfg = _cfg()
    params = T.init_params(cfg, jax.random.key(0))
    assert cfg.num_params() == sum(a.size for a in jax.tree.leaves(params))
    assert params["blocks"]["ln_q"].shape == (2, 4 * 32)
    assert jax.tree.structure(T.param_axes(cfg), is_leaf=lambda a: isinstance(
        a, tuple)) == jax.tree.structure(params)


@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_dropless_matches_the_reference_values_and_gradients(norm_topk_prob):
    """Cases 1 and 4: outputs and the gradients of `wi_gate` and `router`,
    under either normalisation rule; the two rules differ from each other."""
    cfg = _cfg(norm_topk_prob=norm_topk_prob)
    p = _layer(cfg)
    y = jax.random.normal(jax.random.key(2), (2, 24, cfg.hidden))
    cot = jax.random.normal(jax.random.key(3), y.shape)

    def system(p):
        return jnp.sum(T.moe_dropless(cfg, y, p)[0] * cot)

    def reference(p):
        with jax.default_matmul_precision("highest"):
            out, _ = R.experts(y.reshape(-1, cfg.hidden), p, top_k=4,
                               norm_topk_prob=norm_topk_prob)
        return jnp.sum(out.reshape(y.shape) * cot)

    out, load = T.moe_dropless(cfg, y, p)
    with jax.default_matmul_precision("highest"):
        want, _ = R.experts(y.reshape(-1, cfg.hidden), p, top_k=4,
                            norm_topk_prob=norm_topk_prob)
    assert _rel(out.reshape(want.shape), want) < REL
    assert int(load.sum()) == 2 * 24 * 4  # every assignment ran
    got, ref = jax.grad(system)(p), jax.grad(reference)(p)
    for name in ("wi_gate", "wi_up", "wo_mlp", "router"):
        assert _rel(got[name], ref[name]) < REL, name
    # the other rule is another function: far outside the tolerance
    other, _ = T.moe_dropless(_cfg(norm_topk_prob=not norm_topk_prob), y, p)
    assert _rel(other, out) > 0.05


def test_dropless_equals_the_capacity_layer_when_nothing_is_dropped():
    """Case 2: ties `moe_dropless` to `_moe_mlp` (Mixtral's rule, a capacity
    of every token) until the follow-up removes the dispatch tensors."""
    cfg = _cfg(norm_topk_prob=True, capacity_factor=16 / 4)
    p = _layer(cfg)
    y = jax.random.normal(jax.random.key(4), (2, 16, cfg.hidden))
    assert _rel(T.moe_dropless(cfg, y, p)[0], T._moe_mlp(cfg, y, p)) < REL


def test_dropless_under_skew():
    """Case 3: every token picks the same 4 experts. Nothing is dropped,
    where the capacity layer at its shipped factor drops most of them."""
    cfg = _cfg()
    p = dict(_layer(cfg))
    u = jnp.ones((cfg.hidden,)) / cfg.hidden ** 0.5
    p["router"] = p["router"].at[:, :4].add(8.0 * u[:, None])
    y = jax.random.normal(jax.random.key(5), (1, 32, cfg.hidden)) + 4.0 * u
    out, load = T.moe_dropless(cfg, y, p)
    assert load.tolist() == [32] * 4 + [0] * 12
    with jax.default_matmul_precision("highest"):
        want, _ = R.experts(y[0], p, top_k=4, norm_topk_prob=False)
    assert _rel(out[0], want) < REL
    dropped = T._moe_mlp(_cfg(norm_topk_prob=False), y, p)
    assert _rel(dropped, out) > 0.1  # capacity 1.25 x 32 x 4 / 16 = 10 of 32
    # one dropped assignment is outside the tolerance: the reference with
    # one (token, expert) weight zeroed
    w, _ = R.router_weights(y[0], p["router"], top_k=4, norm_topk_prob=False)
    with jax.default_matmul_precision("highest"):
        one_less, _ = R.experts(y[0], p, top_k=4, norm_topk_prob=False,
                                use=w.at[0, 0].set(0.0))
    assert _rel(one_less, want) > 100 * REL


def test_pad_rows_are_computed_not_counted_and_change_nothing():
    cfg = _cfg()
    p = _layer(cfg)
    y = jax.random.normal(jax.random.key(6), (1, 16, cfg.hidden))
    mask = (jnp.arange(16) < 11)[None]
    out, load = T.moe_dropless(cfg, y, p, mask)
    assert int(load.sum()) == 11 * 4
    other = y.at[:, 11:].set(7.0)  # other pad rows, the same real rows
    out2, load2 = T.moe_dropless(cfg, other, p, mask)
    np.testing.assert_array_equal(np.asarray(out[:, :11]),
                                  np.asarray(out2[:, :11]))
    np.testing.assert_array_equal(np.asarray(load), np.asarray(load2))


def test_qk_norm_in_the_training_forward(model):
    """Case 5: `forward()` (with a capacity that drops nothing) and the
    reference agree with non-trivial `ln_q`, `ln_k`; a reference without
    the norms does not."""
    cfg, params = model
    cfg = T.config(cfg, capacity_factor=16 / 4)
    tokens = np.random.default_rng(0).integers(0, 512, (2, 40)).astype(np.int32)
    got = np.asarray(T.forward(cfg, params, jnp.asarray(tokens)))
    want, _ = R.logits(params, tokens, CONF)
    assert R.compare_logits(got, want)["rms_err_over_std"] < 1e-4
    bare, _ = R.logits(params, tokens, CONF, qk_norm=False)
    assert not R.compare_logits(got, bare)["ok"]


def _prefill_then_decode_logits(cfg, params, prompt, steps, max_len=64):
    """Logits of `Generator`'s path: its prefill program, then `steps`
    single-token steps through its cache, feeding the greedy token back."""
    g = Generator(cfg, params, max_len=max_len)
    lens = jnp.asarray([len(prompt)], jnp.int32)
    last, cache = g._prefill(params, jnp.asarray([prompt], jnp.int32), lens,
                             init_cache(cfg, 1, max_len))
    rows, chosen = [np.asarray(last[0], np.float32)], []

    @jax.jit
    def step(tok, cache):
        kv_mask = jnp.arange(max_len)[None, :] <= cache.lengths[:, None]
        lg, new, aux = forward_cached(
            cfg, params, tok[:, None], cache.lengths[:, None], cache, kv_mask,
            jnp.ones((1, 1), bool))
        return lg[:, 0], new._replace(lengths=cache.lengths + 1), aux

    for _ in range(steps):
        chosen.append(int(rows[-1].argmax()))
        lg, cache, aux = step(jnp.asarray(chosen[-1:], jnp.int32), cache)
        assert int(aux["expert_load"].sum()) == \
            cfg.experts_per_token * cfg.layers
        rows.append(np.asarray(lg[0], np.float32))
    chosen.append(int(rows[-1].argmax()))
    return np.stack(rows), chosen


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_against_one_full_forward(model, dtype):
    """Case 6: prefill and 8 decode steps through the cache against ONE full
    forward of the reference. float32: the sums' order only. bfloat16: the
    RMS-over-std bound of `reference.py` (stored activations round by 2^-9,
    independently, through 2 layers: well under 5%; a wrong mask, a stale
    cache row or a missing norm is tens of percent).

    Router near-ties: in bfloat16 the system's k-th choice can swap with the
    reference's (k+1)-th. The reference routes by itself all the same; the
    bound still holds because the swapped pair's weights differ by less than
    the rounding that swapped them and both are the smallest of the chosen
    weights, so a swap moves one token's layer output by about one rounding
    step of one expert's contribution. The swaps are counted and printed."""
    cfg, params = model
    if dtype == "bfloat16":
        cfg = T.config(cfg, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
        params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    prompt = np.random.default_rng(7).integers(0, 512, 21).tolist()
    got, chosen = _prefill_then_decode_logits(cfg, params, prompt, steps=8)
    seq = np.asarray(prompt + chosen[:-1], np.int32)
    want, ref_routing = R.logits(params, seq[None], CONF, last=9)
    check = R.compare_logits(got, np.asarray(want[0]))
    tokens = R.compare_tokens(chosen, np.asarray(want[0]))
    runner = harness.load_module("runners", "serve_olmoe")
    swaps = R.count_routing_differences(
        runner.system_routing(cfg, params, seq[None]), ref_routing)
    print(dtype, check, tokens, swaps)
    assert tokens["ok"]
    if dtype == "float32":
        assert check["rms_err_over_std"] < 1e-4
        assert swaps["topk_sets_differ"] == 0
    else:
        assert check["ok"] and check["rms_err_over_std"] > 1e-4
        assert swaps["topk_sets_differ"] < swaps["of"] // 4
    # and Generator's own loop emits those tokens
    g = Generator(cfg, params, max_len=64)
    assert g.generate([prompt], SamplingParams(max_tokens=9))[0] == chosen


def test_continuous_batcher_serves_the_sparse_model(model):
    """Case 7: mixed prompt lengths over two prefill buckets, other slots
    busy: greedy tokens equal `Generator`'s, and the counters hold every
    assignment of every real row and no pad row."""
    cfg, params = model
    rng = np.random.default_rng(11)
    lengths = [9, 14, 16, 21, 27, 30]  # buckets 16 and 32
    prompts = [rng.integers(0, 512, n).tolist() for n in lengths]
    n_new = [5, 7, 6, 5, 8, 6]
    want = [Generator(cfg, params, max_len=64).generate(
        [p], SamplingParams(max_tokens=n))[0] for p, n in zip(prompts, n_new)]
    batcher = ContinuousBatcher(cfg, params, max_len=64, slots=4)
    try:
        futures = [batcher.submit(p, SamplingParams(max_tokens=n))
                   for p, n in zip(prompts, n_new)]
        got = [f.result(timeout=120) for f in futures]
        stats = dict(batcher.stats)
    finally:
        batcher.shutdown()
    assert got == want
    assert sorted(batcher._prefill_jits) == [16, 32]
    # a request's first token comes from its prefill, the others from one
    # decode row each
    rows = sum(lengths) + sum(n - 1 for n in n_new)
    assert stats["moe_rows"] == rows
    assert stats["moe_assignments"] == \
        rows * cfg.experts_per_token * cfg.layers
    assert sum(stats["moe_expert_load"]) == stats["moe_assignments"]
    assert stats["max_active"] == 4


def test_a_dense_model_pays_nothing():
    """Case 8: a dense configuration's cached forward has no new output
    (its lowered program is the parent's, compared by hand in PR 25), its
    `aux` is empty and its batcher has no expert counters."""
    cfg = T.config("debug", dtype=jnp.float32)
    params = T.init_params(cfg, jax.random.key(0))
    cache = init_cache(cfg, 2, 32)
    args = (jnp.zeros((2, 1), jnp.int32), jnp.zeros((2, 1), jnp.int32), cache,
            jnp.ones((2, 32), bool), jnp.ones((2, 1), bool))
    logits, new, aux = forward_cached(cfg, params, *args)
    assert aux == {}
    jaxpr = jax.make_jaxpr(lambda p, *a: forward_cached(cfg, p, *a))(
        params, *args)
    assert len(jaxpr.out_avals) == 4  # logits, k, v, lengths
    batcher = ContinuousBatcher(cfg, params, max_len=32, slots=2)
    try:
        assert batcher.submit([1, 2, 3], SamplingParams(max_tokens=3)
                              ).result(timeout=120)
        assert not [k for k in batcher.stats if k.startswith("moe_")]
    finally:
        batcher.shutdown()


def test_paged_batcher_serves_the_sparse_model(model):
    """Case 9a: the scheduler over `PagedBatcher`'s pages: `Generator`'s
    tokens, and the counters of case 7 (the paged step's `aux` is counted)."""
    from ray_tpu.models.paged_kv import PagedBatcher

    cfg, params = model
    prompts = [[5, 17, 3], [100, 2, 3, 4, 5, 6, 88], [9], list(range(40, 60))]
    sp = SamplingParams(max_tokens=6)
    want = [Generator(cfg, params, max_len=64).generate([p], sp)[0]
            for p in prompts]
    paged = PagedBatcher(cfg, params, max_len=64, slots=4, page_size=16)
    try:
        got = [f.result(timeout=120)
               for f in [paged.submit(p, sp) for p in prompts]]
        stats = dict(paged.stats)
    finally:
        paged.shutdown()
    assert got == want
    # no prompt shares a full page with another: each is prefilled whole
    assert stats["prefill_tokens"] == sum(map(len, prompts))
    rows = stats["prefill_tokens"] + len(prompts) * (sp.max_tokens - 1)
    assert stats["moe_rows"] == rows
    assert stats["moe_assignments"] == \
        rows * cfg.experts_per_token * cfg.layers
    assert sum(stats["moe_expert_load"]) == stats["moe_assignments"]


def test_disaggregated_prefill_serves_the_sparse_model(ray_start_regular,
                                                       model):
    """Case 9b: the prefill replica's `forward_cached` and the decode
    replica's paged step, in two processes."""
    import ray_tpu
    from ray_tpu.models.disagg_prefill import DisaggPrefillEngine

    cfg, params = model
    prompts = [[5, 17, 3], [9, 9, 2, 1], [42]]
    sp = SamplingParams(max_tokens=5)
    want = [Generator(cfg, params, max_len=64).generate([p], sp)[0]
            for p in prompts]
    engine = DisaggPrefillEngine(cfg, params, max_len=64, slots=4,
                                 page_size=16)
    try:
        got = [ray_tpu.get(r, timeout=300)
               for r in [engine.generate(p, sp) for p in prompts]]
    finally:
        engine.shutdown()
    assert got == want
