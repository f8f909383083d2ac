"""ZAYA1-8B through the serving forward at debug widths on the CPU: the
compressed convolutional attention with its one-position state, the router
that carries its representation from layer to layer, and top-1 of the experts,
against ``benchmarks/reference_zaya.py`` (plain float32 ``jax.numpy``, whole
sequences, no state, nothing from ``ray_tpu.models``), and the state through
every cache that has to move it.

Tolerances. In float32 both sides compute the same sums in another order (a
state against a shifted sequence, a grouped matmul against one expert at a
time): a few float32 roundings, so 1e-4 of the logits' standard deviation
passes (1e-6 measured) and a dropped convolution, a dropped value shift, a
state taken at the bucket's end or a stale state fail by tens of percent. In
bfloat16 the bound is ``reference.py``'s: RMS error under 5% of the standard
deviation, with the reference following the system's route where its own
probabilities hold the two experts within ``ROUTE_TIE_MARGIN``.
"""

import functools
from concurrent.futures import Future

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_zaya as R
from ray_tpu.models import transformer as T
from ray_tpu.models import zaya
from ray_tpu.models.continuous_batching import ContinuousBatcher, _Request
from ray_tpu.models.decoding import (
    Generator, SamplingParams, forward_cached, init_cache)
from ray_tpu.models.paged_kv import PagedBatcher

CONF = dict(num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
            cca_time0=2, cca_time1=2, partial_rotary_factor=0.5,
            rope_parameters={"hybrid": {"rope_theta": 10000.0}},
            rms_norm_eps=1e-5)
MAX_LEN = 64


@pytest.fixture(scope="module")
def model():
    """Seeded weights; `init_params` already draws the taps, tau, gamma and
    the balancing bias away from the identity."""
    cfg = T.config("zaya_debug")
    params = T.init_params(cfg, jax.random.key(1))
    blocks = params["blocks"]
    assert float(jnp.abs(blocks["router_gamma"]).min()) > 0.2
    assert float(jnp.abs(blocks["tau"] - 1).max()) > 0.1
    assert float(jnp.abs(blocks["router_bias"]).max()) > 0
    assert float(jnp.abs(blocks["conv0"][:, 1]).mean()) > 0.1
    return cfg, params


@functools.cache
def _forward(cfg):
    """Jitted, as every engine runs it (the CPU runs a bfloat16 product with
    a float32 sum only inside a compiled program)."""
    return jax.jit(functools.partial(forward_cached, cfg))


def _prefill(cfg, params, prompt, bucket):
    """`prompt` right-padded to `bucket` through a one-row cache of MAX_LEN:
    (logits at its last token [V], cache, aux)."""
    n = len(prompt)
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :n] = prompt
    kv_mask = jnp.arange(MAX_LEN)[None, :] < n
    logits, cache, aux = _forward(cfg)(
        params, jnp.asarray(toks), jnp.arange(bucket)[None, :],
        init_cache(cfg, 1, MAX_LEN), kv_mask, kv_mask[:, :bucket])
    return (np.asarray(logits[0, n - 1], np.float32),
            cache._replace(lengths=jnp.asarray([n], jnp.int32)), aux)


def _decode(cfg, params, tok, cache, active=True):
    kv_mask = jnp.arange(MAX_LEN)[None, :] <= cache.lengths[:, None]
    logits, cache, aux = _forward(cfg)(
        params, jnp.asarray([[tok]], jnp.int32), cache.lengths[:, None],
        cache, kv_mask, jnp.full((1, 1), active))
    return (np.asarray(logits[0, 0], np.float32),
            cache._replace(lengths=cache.lengths + int(active)), aux)


def _prefill_then_decode(cfg, params, prompt, steps, bucket=32):
    """(logits [steps + 1, V], greedy tokens [steps + 1], route [L, S])."""
    row, cache, aux = _prefill(cfg, params, prompt, bucket)
    rows, chosen = [row], []
    route = [np.asarray(aux["expert_choice"])[:, :len(prompt)]]
    for _ in range(steps):
        chosen.append(int(rows[-1].argmax()))
        row, cache, aux = _decode(cfg, params, chosen[-1], cache)
        assert int(aux["expert_load"].sum()) == cfg.layers  # one a layer
        rows.append(row)
        route.append(np.asarray(aux["expert_choice"]))
    chosen.append(int(rows[-1].argmax()))
    return np.stack(rows), chosen, np.concatenate(route, axis=1)


def test_preset_and_parameter_count():
    """The published model: 8.30B in its 40 layers beside a 537M tied
    embedding, 0.76B of it active a token; `num_params` counts the new
    sublayers and equals the tree `init_params` builds."""
    cfg = T.config("zaya1_8b")
    layer = (cfg.num_params() - cfg.vocab_size * cfg.hidden - cfg.hidden) \
        / cfg.layers
    assert round(layer / 1e6, 1) == 207.6
    assert round(cfg.num_params() / 1e9, 2) == 8.84
    assert zaya.state_heads(cfg) * cfg.hd == 2688
    small = T.config("zaya_debug")
    params = T.init_params(small, jax.random.key(0))
    assert small.num_params() == sum(a.size for a in jax.tree.leaves(params))
    assert "unembed" not in params and "router" not in params["blocks"]
    axes = T.param_axes(small)["blocks"]
    assert {n: len(a) for n, a in axes.items()} == \
        {n: a.ndim for n, a in params["blocks"].items()}


def test_unknown_sublayers_and_the_training_forward_are_refused(model):
    cfg, params = model
    with pytest.raises(ValueError, match="unknown attention"):
        T.config(cfg, attention="mla")
    with pytest.raises(ValueError, match="unknown router"):
        T.config(cfg, router="hash")
    with pytest.raises(ValueError, match="partial_rotary"):
        T.config("debug", partial_rotary=0.5)
    with pytest.raises(ValueError, match="cached forward alone"):
        T.forward(cfg, params, jnp.zeros((1, 8), jnp.int32))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_against_one_full_forward(model, dtype):
    """Prefill (a 21-token prompt in its 32 bucket) and 8 decode steps through
    the cache and the state against ONE full forward of the reference over the
    whole sequence. float32: the sums' order only, and no route differs.
    bfloat16: `reference.py`'s RMS bound, the reference following the
    system's route where it is a tie within the margin; no route is refused."""
    cfg, params = model
    if dtype == "bfloat16":
        cfg = T.config(cfg, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
        params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    prompt = np.random.default_rng(7).integers(0, 512, 21).tolist()
    got, chosen, route = _prefill_then_decode(cfg, params, prompt, steps=8)
    seq = np.asarray(prompt + chosen[:-1], np.int32)
    want, routes = R.logits(params, seq[None], CONF, last=9, follow=route)
    check = R.compare_logits(got, np.asarray(want[0]))
    tokens = R.compare_tokens(chosen, np.asarray(want[0]))
    print(dtype, check, tokens,
          {k: v for k, v in routes.items() if k != "chosen"})
    assert tokens["ok"] and routes["refused"] == 0
    assert routes["pairs"] == cfg.layers * len(seq)
    if dtype == "float32":
        assert check["rms_err_over_std"] < 1e-4
        assert routes["followed"] == 0
        # by itself the reference takes the same route
        assert (R.logits(params, seq[None], CONF, last=1)[1]["chosen"]
                == route).all()
    else:
        assert check["ok"] and check["rms_err_over_std"] > 1e-4
        assert routes["followed"] < routes["pairs"] // 10
    # and Generator's own loop, which carries the state too, emits them
    g = Generator(cfg, params, max_len=MAX_LEN)
    assert g.generate([prompt], SamplingParams(max_tokens=9))[0] == chosen


@pytest.mark.parametrize("drop", ["conv0", "conv1", "shift", "mean"])
def test_the_tolerance_bites_on_every_part_of_the_attention(model, drop):
    """A reference without one convolution, without the value shift or
    without the q-k mean is tens of percent from the system: the bound that
    bfloat16 meets would catch a system that left the part out."""
    cfg, params = model
    prompt = np.random.default_rng(7).integers(0, 512, 21).tolist()
    got, chosen, _ = _prefill_then_decode(cfg, params, prompt, steps=4)
    seq = np.asarray(prompt + chosen[:-1], np.int32)
    want, _ = R.logits(params, seq[None], CONF, last=5, drop=(drop,))
    check = R.compare_logits(got, np.asarray(want[0]))
    assert not check["ok"] and check["rms_err_over_std"] > 0.2


def test_a_route_beyond_the_margin_is_refused(model):
    """The reference follows a near-tie and nothing else: told a route of
    random experts it keeps its own, counts the pairs it refused, and its
    logits are those of its own route."""
    cfg, params = model
    seq = np.random.default_rng(3).integers(0, 512, (1, 24))
    own, routes = R.logits(params, seq, CONF)
    wrong = (routes["chosen"] + 1) % cfg.num_experts
    told, refused = R.logits(params, seq, CONF, follow=wrong)
    assert refused["refused"] > refused["pairs"] * 0.8
    assert refused["max_followed_gap"] <= R.ROUTE_TIE_MARGIN
    followed = refused["chosen"] == wrong
    assert followed.sum() == refused["followed"]
    if not followed.any():
        np.testing.assert_allclose(np.asarray(told), np.asarray(own),
                                   atol=1e-5)


def test_a_padded_prompt_leaves_the_state_of_its_true_last_token(model):
    """A 21-token prompt in a 32 bucket against the same prompt in a bucket of
    its own length: the same logits and the same state. The state at the
    bucket's end (position 31, pad tokens) is another one."""
    cfg, params = model
    prompt = np.random.default_rng(5).integers(0, 512, 21).tolist()
    padded, cache_p, _ = _prefill(cfg, params, prompt, 32)
    exact, cache_e, _ = _prefill(cfg, params, prompt, 21)
    np.testing.assert_allclose(padded, exact, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cache_p.state),
                               np.asarray(cache_e.state), atol=1e-5)
    assert float(jnp.abs(cache_p.state).max()) > 0
    # what a gather at the bucket's end would have kept
    at_end = _prefill(cfg, params, prompt + [0] * 11, 32)[1].state
    assert float(jnp.abs(at_end - cache_p.state).max()) > 1e-2
    # one more token from each: the same logits
    a, _, _ = _decode(cfg, params, 9, cache_p)
    b, _, _ = _decode(cfg, params, 9, cache_e)
    np.testing.assert_allclose(a, b, atol=1e-5)


def test_an_inactive_slot_keeps_its_state_and_a_new_sequence_starts_at_zero(
        model):
    cfg, params = model
    fresh = init_cache(cfg, 2, MAX_LEN)
    assert fresh.state.shape == (cfg.layers, 2, zaya.state_heads(cfg), cfg.hd)
    assert not np.asarray(fresh.state).any()
    prompt = np.random.default_rng(5).integers(0, 512, 10).tolist()
    _, cache, _ = _prefill(cfg, params, prompt, 16)
    before = np.asarray(cache.state)
    _, same, aux = _decode(cfg, params, 4, cache, active=False)
    np.testing.assert_array_equal(np.asarray(same.state), before)
    assert int(aux["expert_load"].sum()) == 0  # computed, not counted
    _, moved, _ = _decode(cfg, params, 4, cache, active=True)
    assert float(np.abs(np.asarray(moved.state) - before).max()) > 1e-3


def test_the_router_carries_its_representation_from_layer_to_layer(model):
    """With gamma zeroed every layer routes on its own projection: the route
    of layer 0, which is given zeros either way, stays, and later layers'
    routes and the logits move."""
    cfg, params = model
    tokens = jnp.asarray(
        np.random.default_rng(2).integers(0, 512, (1, 48)), jnp.int32)
    mask = jnp.ones((1, 48), bool)

    def run(p):
        logits, _, aux = forward_cached(
            cfg, p, tokens, jnp.arange(48)[None, :], init_cache(cfg, 1, 48),
            mask, mask)
        return np.asarray(logits), np.asarray(aux["expert_choice"])

    logits, route = run(params)
    alone = dict(params, blocks=dict(
        params["blocks"],
        router_gamma=jnp.zeros_like(params["blocks"]["router_gamma"])))
    logits0, route0 = run(alone)
    assert (route[0] == route0[0]).all()
    assert (route[1:] != route0[1:]).any()
    assert np.abs(logits - logits0).max() > 1e-3
    # the function itself: zeros in, and r out is what the next layer adds
    p0 = jax.tree.map(lambda a: a[0], params["blocks"])
    y = jax.random.normal(jax.random.key(0), (5, cfg.hidden))
    zeros = jnp.zeros((5, cfg.router_hidden))
    (w, e), r = zaya.router(cfg, y, p0, zeros)
    assert w.shape == (5, 1) and e.shape == (5, 1) and e.dtype == jnp.int32
    np.testing.assert_allclose(
        np.asarray(r), np.asarray(y @ p0["router_down"]), atol=1e-5)
    (_, _), r1 = zaya.router(cfg, y, p0, r)
    np.testing.assert_allclose(
        np.asarray(r1), np.asarray(r * (1 + p0["router_gamma"])), atol=1e-5)


def _generator_tokens(cfg, params, prompts, n_new):
    g = Generator(cfg, params, max_len=MAX_LEN)
    return [g.generate([p], SamplingParams(max_tokens=n))[0]
            for p, n in zip(prompts, n_new)]


def test_generator_carries_the_state_in_a_batch_of_uneven_prompts(model):
    """`Generator` pads a batch to its longest prompt: each sequence's state
    is its own last token's, so the batch answers as each prompt alone."""
    cfg, params = model
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, 512, n).tolist() for n in (5, 17, 11)]
    alone = _generator_tokens(cfg, params, prompts, [6, 6, 6])
    together = Generator(cfg, params, max_len=MAX_LEN).generate(
        prompts, SamplingParams(max_tokens=6))
    assert together == alone


def test_continuous_batcher_moves_the_state_with_the_rows(model):
    """Interleaved sequences of different lengths over two prefill buckets
    and two slots, so that every slot is reused, a short prompt after a long
    one among them: greedy tokens equal `Generator`'s, one expert a row and
    layer is counted, every prefill's state was installed and every slot
    that was left was cleared."""
    cfg, params = model
    rng = np.random.default_rng(11)
    lengths = [30, 9, 27, 14, 3, 21]  # buckets 16 and 32
    prompts = [rng.integers(0, 512, n).tolist() for n in lengths]
    n_new = [9, 5, 4, 8, 7, 6]
    want = _generator_tokens(cfg, params, prompts, n_new)
    batcher = ContinuousBatcher(cfg, params, max_len=MAX_LEN, slots=2)
    batcher.route_log = []
    try:
        futures = [batcher.submit(p, SamplingParams(max_tokens=n))
                   for p, n in zip(prompts, n_new)]
        got = [f.result(timeout=120) for f in futures]
        stats = dict(batcher.stats)
        cache = batcher.cache
    finally:
        batcher.shutdown()
    assert got == want
    assert stats["max_active"] == 2
    rows = sum(lengths) + sum(n - 1 for n in n_new)
    assert stats["moe_rows"] == rows
    assert stats["moe_assignments"] / (stats["moe_rows"] * cfg.layers) == 1.0
    assert sum(stats["moe_expert_load"]) == stats["moe_assignments"]
    assert stats["state_installs"] == len(prompts)
    assert stats["state_resets"] == len(prompts)
    assert not np.asarray(cache.state).any()  # every slot was left
    # the routes that were logged: a prefill's [L, bucket], a step's [L, 2]
    shapes = {choice.shape for _, choice in batcher.route_log}
    assert shapes == {(cfg.layers, 16), (cfg.layers, 32), (cfg.layers, 2)}


def test_a_slot_reused_after_a_longer_sequence(model):
    """One slot: a long sequence, then a short one in the slot it left. The
    short one answers as in a new cache: it sees neither the rows nor the
    state of the slot's last occupant."""
    cfg, params = model
    rng = np.random.default_rng(17)
    long, short = rng.integers(0, 512, 40).tolist(), [7, 8, 9]
    want = _generator_tokens(cfg, params, [short], [8])[0]
    batcher = ContinuousBatcher(cfg, params, max_len=MAX_LEN, slots=1)
    try:
        batcher.submit(long, SamplingParams(max_tokens=12)).result(120)
        got = batcher.submit(short, SamplingParams(max_tokens=8)).result(120)
    finally:
        batcher.shutdown()
    assert got == want


def test_paged_batcher_keeps_the_state_outside_its_pages(model):
    """The scheduler over `PagedBatcher`'s pages: `Generator`'s tokens, no
    prefix is reused (no page keeps the state at its boundary: the second
    request's prompt repeats the first's full page and is prefilled whole),
    and a premade row, which brings no state, is refused."""
    cfg, params = model
    shared = list(range(40, 56))  # one full page of 16
    prompts = [shared + [1, 2, 3], [9], shared + [4, 5]]
    sp = SamplingParams(max_tokens=6)
    want = _generator_tokens(cfg, params, prompts, [6] * 3)
    paged = PagedBatcher(cfg, params, max_len=MAX_LEN, slots=2, page_size=16)
    try:
        got = [paged.submit(p, sp).result(timeout=120) for p in prompts]
        stats = dict(paged.stats)
        with pytest.raises(ValueError, match="premade row brings none"):
            paged.submit_prefilled([1, 2], None, None, None)
    finally:
        paged.shutdown()
    assert got == want
    assert stats["prefix_hit_tokens"] == 0
    assert stats["prefill_tokens"] == sum(map(len, prompts))
    assert stats["state_installs"] == stats["state_resets"] == 3
    assert stats["moe_assignments"] == stats["moe_rows"] * cfg.layers


def test_a_preempted_paged_sequence_gets_its_state_back(model):
    """Preempted twice, a request re-prefills over its prompt and what it has
    emitted; the state it resumes from is that prefill's, so it ends with
    the tokens of an undisturbed run. The slot it was taken from is cleared
    each time."""
    cfg, params = model
    prompt, sp = [5, 17, 3], SamplingParams(max_tokens=12)
    pb = PagedBatcher(cfg, params, max_len=MAX_LEN, slots=2, page_size=16)
    want = pb.submit(prompt, sp).result(timeout=120)
    pb.shutdown()  # the pump is gone: the steps below are the test's
    req = _Request(list(prompt), sp, Future(), None)
    pb._waiting.put(req)
    for at in (6, 11):
        while req.slot < 0 or pb._host_len[req.slot] < at:
            pb._step()
        assert pb._drain() and pb._host_len[req.slot] == at
        slot = req.slot
        assert np.asarray(pb.cache.state[:, slot]).any()
        pb._preempt(slot)
        assert not np.asarray(pb.cache.state[:, slot]).any()
        assert req.tokens == prompt + req.out
    while not req.future.done():
        pb._step()
    assert pb.stats["preempted"] == 2 and pb.stats["failed"] == 0
    assert pb.stats["state_installs"] == 4  # the first run's, and three here
    assert req.future.result() == want
    assert want == _generator_tokens(cfg, params, [prompt], [12])[0]


def test_disaggregated_prefill_refuses_a_stateful_attention(model):
    """The KV channel carries K and V rows alone: the engine refuses the
    model at construction, before an actor or a channel exists."""
    from ray_tpu.models.disagg_prefill import DisaggPrefillEngine

    cfg, params = model
    with pytest.raises(ValueError, match="KV channel does not carry"):
        DisaggPrefillEngine(cfg, params, max_len=MAX_LEN)
