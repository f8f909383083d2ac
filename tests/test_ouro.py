"""A LOOPED model of the one block through the serving engine
(`TransformerConfig.loop_steps` / `sandwich`; ByteDance/Ouro-2.6B), against
the plain reference (`benchmarks/reference_ouro.py`) at toy widths on the CPU:
3 layers run twice a token = 6 cache layers a sequence, 4 query heads = 4 KV
heads, a norm behind each sublayer, the final norm at the end of every pass,
the exit gate."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from benchmarks import reference_ouro as R
from ray_tpu.models import decoding
from ray_tpu.models import transformer as T
from ray_tpu.models.continuous_batching import ContinuousBatcher
from ray_tpu.models.decoding import SamplingParams

CFG = T.config("ouro_debug")
PASSES, LAYERS = CFG.loop_steps, CFG.layers
# what `reference_ouro` reads, as a `config.json` spells it
CONF = {"model_type": "ouro", "hidden_act": "silu",
        "tie_word_embeddings": False, "num_hidden_layers": LAYERS,
        "total_ut_steps": PASSES, "early_exit_threshold": 1.0,
        "rope_theta": CFG.rope_theta, "rms_norm_eps": CFG.norm_eps}
# what the system differs from the reference by at these widths, in float32
TOL = 2e-5


@pytest.fixture(scope="module")
def params():
    """Seeded weights; the norms' weights (ones as initialised) and the
    gate's bias (zero) drawn here, so that a weight left out, misplaced or
    read for another shows."""
    out = T.init_params(CFG, jax.random.key(7))
    names = ("ln_attn", "ln_attn_post", "ln_mlp", "ln_mlp_post")
    for i, name in enumerate(names):
        out["blocks"][name] = 1 + 0.3 * jax.random.normal(
            jax.random.key(30 + i), out["blocks"][name].shape)
    out["ln_f"] = 1 + 0.3 * jax.random.normal(jax.random.key(40), (CFG.hidden,))
    out["exit_b"] = jnp.asarray([0.4])
    return out


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n).tolist()


@functools.partial(jax.jit, static_argnames="cfg")
def _step(params, tok, cache, active, cfg=CFG):
    positions = cache.lengths[:, None]
    kv_mask = jnp.arange(cache.k.shape[2])[None, :] <= positions
    rows = jnp.where(active, cache.lengths + 1, 0)
    logits, cache, aux = decoding.forward_cached(
        cfg, params, tok[:, None], positions, cache, kv_mask,
        active[:, None], rows=rows)
    return logits[:, 0], aux["exit_pdf"][:, :, 0], cache._replace(
        lengths=jnp.where(active, cache.lengths + 1, cache.lengths))


@functools.partial(jax.jit, static_argnames="cfg")
def _prefill(params, tokens, lengths, cache, cfg=CFG):
    """Prompts [B, S] into a cache of longer slots: the rows scattered, the
    indexed layer attended (`StackLayer.view`)."""
    b, s = tokens.shape
    positions = jnp.arange(s)[None, :].repeat(b, 0)
    kv_mask = jnp.arange(cache.k.shape[2])[None, :] < lengths[:, None]
    logits, cache, aux = decoding.forward_cached(
        cfg, params, tokens, positions, cache, kv_mask, kv_mask[:, :s])
    return logits, aux["exit_pdf"], cache._replace(lengths=lengths)


def _decode_on(params, cache, tok, seqs, system, pdfs, steps):
    active = jnp.ones(len(seqs), bool)
    for _ in range(steps):
        for seq, t in zip(seqs, tok):
            seq.append(int(t))
        logits, pdf, cache = _step(params, jnp.asarray(tok), cache, active)
        for slot in range(len(seqs)):
            system[slot].append(np.asarray(logits[slot]))
            pdfs[slot].append(np.asarray(pdf[:, slot]))
        tok = np.asarray(logits).argmax(-1).astype(np.int32)
    return cache


def _holds_to_the_reference(params, seqs, system, pdfs):
    for seq, logits, pdf in zip(seqs, system, pdfs):
        n = len(logits)
        ref, ref_pdf = R.logits(params, np.asarray(seq)[None], CONF, last=n)
        out = R.compare_logits(np.stack(logits), np.asarray(ref[0]))
        assert out["rms_err_over_std"] < TOL, out
        assert out["argmax_agree"] == 1.0
        np.testing.assert_allclose(
            np.stack(pdf, axis=1), np.asarray(ref_pdf[:, 0, -n:]), atol=1e-5)


def test_the_one_block_states_the_loop_and_refuses_by_name():
    """`kept` and `init_cache`: passes x layers K/V layers, nothing else; the
    parameters: two more norms a layer and the gate's two leaves; what does
    not run is refused by its name."""
    (kept,) = CFG.kept(64)
    assert kept.fields == ("k", "v") and kept.layers == PASSES * LAYERS == 6
    assert CFG.full_layers == 6 and CFG.keeps == ("k", "v")
    cache = decoding.init_cache(CFG, 3, 64)
    assert cache.k.shape == cache.v.shape == (6, 3, 64, 4, 32)
    assert cache.state is None and cache.ring_k is None and cache.mat is None
    params = T.init_params(CFG, jax.random.key(0))
    assert params["blocks"]["ln_attn_post"].shape == (LAYERS, CFG.hidden)
    assert params["exit_w"].shape == (CFG.hidden,)
    assert params["exit_b"].shape == (1,) and not params["exit_b"].any()
    assert sum(a.size for a in jax.tree.leaves(params)) == CFG.num_params()
    axes = T.param_axes(CFG)
    assert jax.tree.structure(axes, is_leaf=lambda a: isinstance(a, tuple)) \
        == jax.tree.structure(params)
    # the published widths: ISSUE 64's 2,667,974,657
    whole = T.config("llama2_7b", vocab_size=49152, hidden=2048,
                     mlp_hidden=5632, layers=48, heads=16, kv_heads=16,
                     head_dim=128, loop_steps=4, sandwich=True)
    assert whole.num_params() == 2_667_974_657
    assert whole.full_layers == 192
    with pytest.raises(ValueError, match="leave the loop at different passes"):
        T.config("ouro_debug", exit_threshold=0.9)
    with pytest.raises(ValueError, match="loss over its exit distribution"):
        T.loss_fn(CFG, params, {"tokens": jnp.zeros((1, 8), jnp.int32)})
    with pytest.raises(ValueError, match="the looped block is the dense one"):
        T.config("moe_debug", loop_steps=2)
    with pytest.raises(ValueError, match="loop_steps.*no field of"):
        T.config("zaya_debug", loop_steps=2)
    with pytest.raises(ValueError, match="once a token at least"):
        T.config("debug", loop_steps=0)


def test_one_pass_without_sandwich_is_the_parents_block_to_the_bit():
    """`loop_steps` 1 and `sandwich` off: the parameters and the program are
    the one block's as the parent had them: the parent's loop, written out
    here, gives the same bits, and `aux` is empty."""
    cfg = T.config("debug", dtype=jnp.float32)
    assert cfg == T.config("debug", dtype=jnp.float32, loop_steps=1,
                           sandwich=False, exit_threshold=1.0)
    params = T.init_params(cfg, jax.random.key(3))
    assert set(params) == {"embed", "blocks", "ln_f", "unembed"}
    assert not {"ln_attn_post", "ln_mlp_post"} & set(params["blocks"])
    tokens = jnp.asarray([_prompt(1, 12), _prompt(2, 12)])
    positions = jnp.arange(12)[None, :].repeat(2, 0)
    mask = jnp.ones((2, 12), bool)

    def parents(params, cache):
        x = params["embed"].astype(cfg.dtype)[tokens]
        tree, whole = decoding.layers_to_scan(cfg, params)

        def body(carry, layer):
            x, k, v, state, route = carry
            return decoding._block_cached(
                cfg, x, dict(layer["p"], **whole), None, positions, k, v,
                mask, mask, layer["i"], decoding._write_stack(layer["i"]),
                state, route, None)

        (x, k, v, _, _), _ = lax.scan(
            body, (x, cache.k, cache.v, None, None), tree)
        return decoding.lm_head(cfg, params, x), k

    def ours(params, cache):
        logits, cache, aux = decoding.forward_cached(
            cfg, params, tokens, positions, cache, mask, mask)
        assert aux == {}
        return logits, cache.k

    cache = decoding.init_cache(cfg, 2, 12)
    want, got = jax.jit(parents)(params, cache), jax.jit(ours)(params, cache)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    assert jax.jit(ours).lower(params, cache).as_text() \
        == jax.jit(parents).lower(params, cache).as_text().replace(
            "jit_parents", "jit_ours")


def test_prefill_then_decode_through_forward_cached_is_the_reference(params):
    """Unequal prompts prefilled together into slots longer than they are
    (rows scattered into cache layer t * 3 + i, the indexed layer attended),
    then 10 decode steps beside each other: every position's logits AND its
    exit distribution against ONE full forward of the reference."""
    prompts = [_prompt(2, 21), _prompt(3, 13)]
    tokens = np.zeros((2, 21), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
    lengths = jnp.asarray([21, 13])
    logits, pdf, cache = _prefill(params, jnp.asarray(tokens), lengths,
                                  decoding.init_cache(CFG, 2, 48))
    np.testing.assert_allclose(np.asarray(pdf).sum(0), 1.0, atol=1e-6)
    seqs = [list(p) for p in prompts]
    system = [[np.asarray(logits[i, len(p) - 1])]
              for i, p in enumerate(prompts)]
    pdfs = [[np.asarray(pdf[:, i, len(p) - 1])] for i, p in enumerate(prompts)]
    tok = np.array([s[0].argmax() for s in system], np.int32)
    cache = _decode_on(params, cache, tok, seqs, system, pdfs, 10)
    _holds_to_the_reference(params, seqs, system, pdfs)
    # every pass wrote rows of its own: no two cache layers hold the same
    rows = np.asarray(cache.k[:, 0, :21])
    assert all(np.abs(rows[a] - rows[b]).max() > 1e-3
               for a in range(6) for b in range(a))
    # the whole prompt's logits, and the training forward's (no cache)
    ref, _ = R.logits(params, np.asarray(prompts[0])[None], CONF)
    out = R.compare_logits(np.asarray(logits[:1]), np.asarray(ref))
    assert out["rms_err_over_std"] < TOL, out
    plain = T.forward(CFG, params, jnp.asarray(prompts[0])[None])
    assert R.compare_logits(np.asarray(plain), np.asarray(ref))[
        "rms_err_over_std"] < TOL


def test_the_batchers_programs_install_and_decode_the_reference(params):
    """Through `ContinuousBatcher`'s own prefill (a row cache of the bucket's
    length: the fresh rows ARE cache layer t * 3 + i) and install, then
    decode steps: logits and exit distribution against the reference."""
    cb = ContinuousBatcher(CFG, params, max_len=64, slots=2)
    cb.shutdown()
    prompts = [_prompt(4, 19), _prompt(5, 30)]
    system = []
    for slot, prompt in enumerate(prompts):
        last, row_k, row_v, *rest = cb._prefill(prompt)
        assert not rest  # the program returns no `exit_pdf`
        assert row_k.shape == (6, cb._bucket(len(prompt)), 4, 32)
        cb.cache = cb._install_jit(cb.cache, *cb._pad_row(row_k, row_v), slot,
                                   len(prompt))
        system.append([np.asarray(last)])
    assert cb.prefill_attention_path == {"prefill_32": "dense"}
    seqs = [list(p) for p in prompts]
    pdfs = [[], []]
    tok = np.array([s[0].argmax() for s in system], np.int32)
    cb.cache = _decode_on(params, cb.cache, tok, seqs, system, pdfs, 8)
    for slot in range(2):  # the prefill program kept no exit distribution
        pdfs[slot].insert(0, np.asarray(R.logits(
            params, np.asarray(prompts[slot])[None], CONF)[1][:, 0, -1]))
    _holds_to_the_reference(params, seqs, system, pdfs)


def test_the_scheduler_serves_it_and_slots_are_taken_again(params):
    """Through `submit`: five requests on two slots (a slot installs,
    finishes and is taken again), greedy tokens the reference ranks first at
    every position; the rows-held and rows-read counters count 6 layers."""
    cb = ContinuousBatcher(CFG, params, max_len=64, slots=2)
    try:
        prompts = [_prompt(10 + i, n) for i, n in enumerate((19, 9, 33, 12, 5))]
        futs = [cb.submit(p, SamplingParams(max_tokens=12)) for p in prompts]
        outs = [f.result(300) for f in futs]
        assert cb._kv_rows(np.array([5, 9])) == (6 * 14, 6 * (16 + 16))
    finally:
        cb.shutdown()
    for prompt, out in zip(prompts, outs):
        assert len(out) == 12
        ref, _ = R.logits(params, np.asarray(prompt + out[:-1])[None], CONF,
                          last=12)
        got = R.compare_tokens(out, np.asarray(ref[0]))
        assert got["argmax_agree"] == 1.0, got
    st = cb.stats
    assert st["admitted"] == st["finished"] == 5 and not st["failed"]
    assert st["kv_rows_held"] and st["kv_rows_held"] % 6 == 0
    assert st["kv_rows_read"] % 6 == 0
    assert "state_installs" not in st and "moe_assignments" not in st


def test_a_reused_slot_shows_nothing_of_its_last_occupant(params):
    """One slot: a long prompt that decodes on, then a short one: its answer
    is a fresh engine's, in any of three slots; an install overwrites all six
    cache layers of the slot and no other slot's."""
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
    cb.cache = cb._empty_cache()._replace(
        k=jnp.ones_like(cb.cache.k), v=jnp.ones_like(cb.cache.v))
    _, row_k, row_v = cb._prefill(short)
    cache = cb._install_jit(cb.cache, *cb._pad_row(row_k, row_v), 0, 5)
    assert not np.asarray(cache.k[:, 0, 16:]).any()
    assert (np.asarray(cache.k[:, 0, :5]) != 1).all()
    assert (np.asarray(cache.k[:, 1]) == 1).all()


def test_pages_and_the_kv_channel_size_by_what_is_kept(params):
    """`PagedBatcher`'s pools and the disaggregated prefill's row have one
    layer a pass and layer (`cfg.full_layers`); paged decoding, a shared
    prefix among it, gives the slots' tokens."""
    from ray_tpu.models import disagg_prefill
    from ray_tpu.models.paged_kv import PagedBatcher

    assert disagg_prefill._row_shape(CFG, 64) == (2, 6, 64, 4, 32)
    shared = _prompt(20, 32)
    prompts = [shared + _prompt(21, 5), _prompt(22, 9), shared + _prompt(23, 3)]
    sp = SamplingParams(max_tokens=8)
    dense = ContinuousBatcher(CFG, params, max_len=64, slots=2)
    try:
        want = [dense.submit(p, sp).result(300) for p in prompts]
    finally:
        dense.shutdown()
    paged = PagedBatcher(CFG, params, max_len=64, slots=2, page_size=16,
                         extra_pages=4)
    try:
        assert paged.cache.k.shape[0] == 6
        got = [paged.submit(p, sp).result(300) for p in prompts]
        assert paged.stats["prefix_hit_tokens"] == 32
        assert paged._kv_rows(np.array([5, 9]))[1] == 6 * 2 * 64
    finally:
        paged.shutdown()
    assert got == want


# What the system differs from the reference by at these widths is under 2e-5
# (above). Each fault below is another model by a wide margin: the limit a
# check holds the system to lies between.
@pytest.mark.parametrize("fault,floor", [
    (dict(passes=1), 0.3), (dict(drop=("sandwich",)), 0.3),
    (dict(drop=("between",)), 0.1), (dict(one_cache_from=16), 0.05),
    (dict(precision="bfloat16"), 1e-2)],
    ids=["a_pass_less", "no_sandwich", "no_norm_between", "one_cache",
         "bfloat16_sums"])
def test_each_fault_is_another_model(params, fault, floor):
    """A pass less, the norm behind a sublayer dropped, the final norm
    between passes dropped, decode steps whose passes read pass 0's rows
    (one cache for all), and bfloat16 accumulation each move the last 8
    positions' logits far beyond what the system differs by."""
    tokens = np.asarray(_prompt(11, 24))[None]
    whole, _ = R.logits(params, tokens, CONF, last=8)
    other, _ = R.logits(params, tokens, CONF, last=8, **fault)
    err = R.compare_logits(np.asarray(other[0]), np.asarray(whole[0]))
    assert err["rms_err_over_std"] > floor > 100 * TOL, err


def test_the_stated_precision_stands_between_float32_and_a_lower_one(params):
    """`precision="stated"` (bfloat16 operands and stored values, float32
    sums) moves the logits by what that precision costs (0.019 of a standard
    deviation through 12 sublayers of 128 wide: the chip's check reads 0.2
    through 384), under what a bfloat16 accumulator does."""
    tokens = np.asarray(_prompt(11, 24))[None]
    whole, _ = R.logits(params, tokens, CONF, last=8)
    stated, _ = R.logits(params, tokens, CONF, last=8, precision="stated")
    lower, _ = R.logits(params, tokens, CONF, last=8, precision="bfloat16")
    err = [R.compare_logits(np.asarray(other[0]), np.asarray(whole[0]))[
        "rms_err_over_std"] for other in (stated, lower)]
    assert 100 * TOL < err[0] < err[1], err


def test_one_cache_is_a_fault_of_the_decode_steps_alone(params):
    """`one_cache_from` P leaves the positions before P as they were."""
    tokens = np.asarray(_prompt(11, 24))[None]
    whole, _ = R.logits(params, tokens, CONF)
    other, _ = R.logits(params, tokens, CONF, one_cache_from=16)
    np.testing.assert_allclose(other[0, :16], whole[0, :16], atol=1e-5)
    assert np.abs(np.asarray(other[0, 16] - whole[0, 16])).max() > 1e-2


def test_the_exit_distribution_holds_the_gate(params):
    """A dropped gate changes no logit at the threshold 1 and every exit
    probability; the distribution sums to one, the last pass takes what is
    left, and a threshold a token's first pass reaches makes it leave there."""
    tokens = np.asarray(_prompt(12, 16))[None]
    whole, pdf = R.logits(params, tokens, CONF)
    other, flat = R.logits(params, tokens, CONF, drop=("gate",))
    np.testing.assert_array_equal(other, whole)
    np.testing.assert_allclose(flat, 0.5)
    assert np.abs(np.asarray(pdf) - 0.5).max() > 0.05
    np.testing.assert_allclose(np.asarray(pdf).sum(0), 1.0, atol=1e-6)
    lam = jax.nn.sigmoid(jnp.asarray([[-1.0, 2.0], [0.5, -3.0], [9.0, 9.0]]))
    got = np.asarray(T.exit_pdf(lam[:, None]))[:, 0]
    np.testing.assert_allclose(got, np.asarray(R.exit_distribution(
        [g[None] for g in lam]))[:, 0], atol=1e-7)
    np.testing.assert_allclose(got[2], (1 - lam[0]) * (1 - lam[1]), atol=1e-7)
    at = R.leaves_at(jnp.asarray(got), 0.5)
    assert at.tolist() == [1, 0]  # 0.27 then 0.72: the second; 0.88: the first
    assert R.leaves_at(jnp.asarray(got), 1.0).tolist() == [2, 2]
    early, _ = R.logits(params, tokens, dict(CONF, early_exit_threshold=0.3))
    assert np.abs(np.asarray(early - whole)).max() > 1e-2


@pytest.fixture
def kernels_through_the_interpreter(monkeypatch):
    """The chip's path on the CPU (steered here, not by an option of the
    program): `_on_tpu` says yes, every Pallas call runs interpreted, slots
    in blocks of 16 rows, no scores small enough to stay dense."""
    import jax.experimental.pallas as pl

    from ray_tpu.ops import attention as A

    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    monkeypatch.setattr(A, "DECODE_BLOCK_ROWS", 16)
    monkeypatch.setattr(A, "DECODE_THIN_BLOCK_ROWS", 16)
    monkeypatch.setattr(A, "DENSE_SCORES_BYTES", 0)
    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    return A


def test_the_kernels_serve_it_and_say_so(kernels_through_the_interpreter):
    """Heads of 128 and a prompt in the 128 bucket: the flash forward over a
    prefill's fresh rows and `decode_attention` over the held rows of cache
    layer t * layers + i (the layer index is the kernel's own argument), and
    the engine says which it compiled; the tokens are the reference's."""
    cfg = T.config("ouro_debug", hidden=256, heads=2, kv_heads=2,
                   head_dim=128, layers=2, max_seq=256)
    conf = dict(CONF, num_hidden_layers=2)
    params = T.init_params(cfg, jax.random.key(9))
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, 512, n).tolist() for n in (100, 70)]
    cb = ContinuousBatcher(cfg, params, max_len=192, slots=2)
    try:
        outs = [f.result(600) for f in [
            cb.submit(p, SamplingParams(max_tokens=6)) for p in prompts]]
    finally:
        cb.shutdown()
    assert cb.prefill_attention_path == {"prefill_128": "flash"}
    assert cb.decode_attention_path == {"decode": "kernel"}
    for prompt, out in zip(prompts, outs):
        ref, _ = R.logits(params, np.asarray(prompt + out[:-1])[None], conf,
                          last=6)
        got = R.compare_tokens(out, np.asarray(ref[0]))
        assert got["max_shortfall_over_std"] < 1e-3, got
