"""Mamba-1 mixers and dense MLPs beside an attention of ONE KV head through
the serving engine (models/nemotron_h.py's kinds "ssm1" and "mlp";
ai21labs/AI21-Jamba2-3B), against the plain reference
(`benchmarks/reference_jamba.py`) at toy widths on the CPU: two periods of
four published layers with the attention third = 16 sublayers, 256 channels
with a state of 16 and steps through a rank of 10, 4 query heads on 1 KV
head, the head tied to the embedding."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_jamba as R
from ray_tpu.models import decoding, families, nemotron_h as N
from ray_tpu.models import transformer as T
from ray_tpu.models.continuous_batching import ContinuousBatcher
from ray_tpu.models.decoding import SamplingParams
from ray_tpu.ops import ssd

CFG = T.config("jamba_debug")
# what `reference_jamba` reads, as a `config.json` spells it: 8 published
# layers, the attention at 2 of every 4
CONF = {"num_hidden_layers": 8, "attn_layer_period": 4,
        "attn_layer_offset": 2, "num_experts": 1, "hidden_size": CFG.hidden,
        "num_attention_heads": CFG.heads,
        "num_key_value_heads": CFG.kv_heads, "rms_norm_eps": CFG.norm_eps}


@pytest.fixture(scope="module")
def params():
    """Seeded weights; the three small norms' weights and D, ones as
    initialised, drawn here so that a weight left out or misplaced shows."""
    out = T.init_params(CFG, jax.random.key(5))
    mixers = out["blocks"]["ssm1"]
    for i, name in enumerate(("dt_norm", "b_norm", "c_norm", "d")):
        mixers[name] = 1 + 0.3 * jax.random.normal(
            jax.random.key(20 + i), mixers[name].shape, mixers[name].dtype)
    return out


@pytest.fixture
def kernels_through_the_interpreter(monkeypatch):
    """The chip's path on the CPU (steered here, not by an option of the
    program): `_on_tpu` says yes, every Pallas call runs interpreted, slots
    in blocks of 16 rows."""
    import jax.experimental.pallas as pl

    from ray_tpu.ops import attention as A

    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    monkeypatch.setattr(A, "DECODE_BLOCK_ROWS", 16)
    monkeypatch.setattr(A, "DECODE_THIN_BLOCK_ROWS", 16)
    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    return A


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n).tolist()


@functools.partial(jax.jit, static_argnames="cfg")
def _step(params, tok, cache, active, cfg=CFG):
    positions = cache.lengths[:, None]
    kv_mask = jnp.arange(cache.k.shape[2])[None, :] <= positions
    rows = jnp.where(active, cache.lengths + 1, 0)
    logits, cache, _ = decoding.forward_cached(
        cfg, params, tok[:, None], positions, cache, kv_mask,
        active[:, None], rows=rows)
    return logits[:, 0], cache._replace(
        lengths=jnp.where(active, cache.lengths + 1, cache.lengths))


def _step_logits(cb, tok, active):
    """One decode step of the batcher's own program body, its logits kept."""
    logits, cb.cache = _step(cb.params, jnp.asarray(tok), cb.cache,
                             jnp.asarray(active), cfg=cb.cfg)
    return np.asarray(logits)


def _install(cb, slot, prompt):
    last, row_k, row_v, mat, conv = cb._prefill(prompt)
    cb.cache = cb._install_jit(cb.cache, *cb._pad_row(row_k, row_v), slot,
                               len(prompt), None, None, None, mat, conv)
    return last, row_k, mat, conv


def test_the_family_states_both_mixers_and_refuses_by_name():
    """The pattern is the family's: its kinds, its loop off the list, what a
    sequence keeps, the parameter count by hand; a field it needs and lacks,
    a field of another family, and both kinds of mixer in one configuration
    are each refused by name."""
    assert families.of(CFG) is N
    assert CFG.keeps == ("k", "v", "mat", "conv") and CFG.stateful
    assert N.runs(CFG.layer_kinds)[:3] == [
        (("ssm1", "mlp"), 2), (("gqa",), 1), (("mlp", "ssm1"), 3)]
    k, mat, conv = CFG.kept(64)
    assert (k.layers, k.rows, k.shape) == (2, 64, (1, 32))
    assert (mat.layers, mat.shape, mat.dtype) == (6, (16, 256), jnp.float32)
    assert conv.shape == (3 * 256,)  # the convolution runs over u alone
    h, c, n, r, m = 128, 256, 16, 10, 192
    mixer = h * 2 * c + 4 * c + c + c * (r + 2 * n) + r + 2 * n \
        + r * c + c + n * c + c + c * h
    attention = 2 * h * 4 * 32 + 2 * h * 32
    assert CFG.num_params() == 6 * (mixer + h) + 2 * (attention + h) \
        + 8 * (3 * h * m + h) + 512 * h + h  # the head IS the embedding
    assert "unembed" not in T.init_params(CFG, jax.random.key(0))
    with pytest.raises(ValueError, match="ssm_dt_rank"):
        T.config("jamba_debug", ssm_dt_rank=0)
    with pytest.raises(ValueError, match="window"):
        T.config("jamba_debug", window=8)
    with pytest.raises(ValueError, match="no ssm layer beside it"):
        T.config("jamba_debug", layers=17, ssm_groups=1,
                 layer_kinds=CFG.layer_kinds + ("ssm",))


def test_prefill_then_decode_is_the_reference(params):
    """Prompts of 21 and 70 tokens (unequal, the second through the 128
    bucket: shorter than its bucket and no whole number of the scan's chunks
    of 8) prefilled by the batcher's own program, installed, then 14 decode
    steps beside each other, well past the convolution's window of 3: every
    position's logits against ONE full forward of the reference."""
    cb = ContinuousBatcher(CFG, params, max_len=128, slots=2)
    cb.shutdown()
    prompts = [_prompt(2, 21), _prompt(3, 70)]
    firsts = []
    for slot, prompt in enumerate(prompts):
        last, row_k, mat, conv = _install(cb, slot, prompt)
        assert row_k.shape == (2, cb._bucket(len(prompt)), 1, 32)
        assert mat.shape == (6, 16, 256) and mat.dtype == jnp.float32
        assert conv.shape == (6, 3 * 256)
        firsts.append(np.asarray(last))
    # the decode step was traced at the engine's build (PR 66)
    assert cb.ssm_path == {"decode": "state:plain",
                           "prefill_32": "scan:plain",
                           "prefill_128": "scan:plain"}
    seqs = [list(p) for p in prompts]
    system = [[f] for f in firsts]
    tok = np.array([int(f.argmax()) for f in firsts], np.int32)
    for _ in range(14):
        for s, t in zip(seqs, tok):
            s.append(int(t))
        logits = _step_logits(cb, tok, [True, True])
        for slot in range(2):
            system[slot].append(logits[slot])
        tok = logits.argmax(-1).astype(np.int32)
    for slot in range(2):
        n = len(system[slot])
        ref = R.logits(params, np.asarray(seqs[slot])[None], CONF, last=n)
        out = R.compare_logits(np.stack(system[slot]), np.asarray(ref[0]))
        assert out["rms_err_over_std"] < 2e-5, (slot, out)
        assert out["argmax_agree"] == 1.0


def test_a_batch_of_unequal_prompts_is_each_alone(params):
    """`Generator`'s ONE padded prefill of prompts of 5, 23 and 14 tokens
    (every sequence's state and window those at its TRUE last position: a
    pad has dt 0) then decode: the greedy tokens the reference ranks first."""
    prompts = [_prompt(30, 5), _prompt(31, 23), _prompt(32, 14)]
    outs = decoding.Generator(CFG, params, max_len=64).generate(
        prompts, SamplingParams(max_tokens=8))
    for prompt, out in zip(prompts, outs):
        ref = R.logits(params, np.asarray(prompt + out[:-1])[None], CONF,
                       last=8)
        assert R.compare_tokens(out, np.asarray(ref[0]))["argmax_agree"] == 1.0


def _recurrence(h, a, dt, x, b, c):
    """The recurrence a position at a time in numpy, channel by state: h [B,
    N, C], a [N, C], dt, x [B, S, C], b, c [B, S, N] -> (h, o [B, S, C])."""
    h, o = np.array(h, np.float64), []
    for t in range(dt.shape[1]):
        h = np.exp(dt[:, t, None, :] * a) * h \
            + b[:, t, :, None] * (dt[:, t] * x[:, t])[:, None, :]
        o.append((h * c[:, t, :, None]).sum(1))
    return h, np.stack(o, 1)


def _scan_inputs(seed, b, s, n, lanes):
    rng = np.random.default_rng(seed)
    a = -np.exp(rng.normal(size=(n, lanes))).astype(np.float32)
    a[:, 0], a[:, 1] = -300.0, 0.0  # a channel that forgets all, one nothing
    dt = (0.1 * np.abs(rng.normal(size=(b, s, lanes)))).astype(np.float32)
    dt[1, s - 10:] = 0  # a run of pads: the state must pass them unchanged
    x, bm, cm = (rng.normal(size=shape).astype(np.float32) for shape in (
        (b, s, lanes), (b, s, n), (b, s, n)))
    h0 = rng.normal(size=(b, n, lanes)).astype(np.float32)
    return h0, a, dt, x, bm, cm


@pytest.mark.parametrize("chunk", [4, 32, 128])
def test_the_plain_scan_is_the_recurrence(chunk):
    """`selective_scan_plain` against the recurrence a position at a time,
    from a state that is not zero, over 70 positions (a last chunk that is
    not whole; one chunk longer than the sequence)."""
    h0, a, dt, x, bm, cm = _scan_inputs(chunk, 2, 70, 16, 24)
    want, o_want = _recurrence(h0, a, dt, x, bm, cm)
    at_60, _ = _recurrence(h0, a, dt[:, :60], x[:, :60], bm[:, :60],
                           cm[:, :60])
    got, o = jax.jit(functools.partial(ssd.selective_scan_plain, chunk=chunk))(
        h0, a, dt, dt * x, bm, cm)
    np.testing.assert_allclose(o, o_want, atol=5e-5)
    np.testing.assert_allclose(got, want, atol=5e-5)
    np.testing.assert_allclose(got[1], at_60[1], atol=5e-5)
    assert np.isfinite(np.asarray(got)).all()


@pytest.mark.parametrize("s,chunk", [(44, 16), (70, 256), (64, 32)])
def test_the_scan_kernel_is_the_recurrence(
        kernels_through_the_interpreter, s, chunk):
    """`selective_scan` through the interpreter: a chunk that does not divide
    the prompt, a chunk longer than it, one that divides it; two blocks of
    channels; pads honoured (`row_mask`: dt 0) to the bit."""
    h0, a, dt, x, bm, cm = _scan_inputs(s, 2, s, 16, 1024)
    assert ssd.selective_scan_takes(jnp.asarray(h0), 8192)
    assert not ssd.selective_scan_takes(jnp.asarray(h0[..., :384]), 8192)
    assert not ssd.selective_scan_takes(jnp.asarray(h0, jnp.bfloat16), 8192)
    want, o_want = _recurrence(h0, a, dt, x, bm, cm)
    got, o = jax.jit(functools.partial(ssd.selective_scan, chunk=chunk))(
        h0, a, dt, dt * x, bm, cm)
    assert o.shape == x.shape
    np.testing.assert_allclose(o, o_want, atol=5e-5)
    np.testing.assert_allclose(got, want, atol=5e-5)
    short, _ = jax.jit(functools.partial(ssd.selective_scan, chunk=chunk))(
        h0[1:], a, dt[1:, :s - 10], (dt * x)[1:, :s - 10], bm[1:, :s - 10],
        cm[1:, :s - 10])
    np.testing.assert_array_equal(got[1], short[0])


def test_the_state_kernel_is_the_step(kernels_through_the_interpreter):
    """`selective_state_update` through the interpreter against
    `selective_step` and the recurrence on the same stack: a layer that is
    not the first, the other layers untouched, a sequence that takes no part
    (dt 0) kept bit for bit."""
    layers, b, n, lanes, layer = 3, 4, 16, 512, 1
    h0, a, dt, x, bm, cm = _scan_inputs(7, b, 12, n, lanes)
    dt, x, bm, cm = dt[:, 0], x[:, 0], bm[:, 0], cm[:, 0]
    dt[2] = 0
    mat = jnp.asarray(np.random.default_rng(1).normal(
        size=(layers, b, n, lanes)), jnp.float32).at[layer].set(h0)
    assert ssd.ssm_state_update_takes(mat)
    want, o_want = ssd.selective_step(mat[layer], a, dt, dt * x, bm, cm)
    by_hand, o_hand = _recurrence(h0, a, dt[:, None], x[:, None],
                                  bm[:, None], cm[:, None])
    got, o = jax.jit(ssd.selective_state_update)(
        mat, layer, a, dt, dt * x, bm, cm)
    np.testing.assert_allclose(got[layer], want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(o, o_want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[layer], by_hand, atol=2e-5)
    np.testing.assert_allclose(o, o_hand[:, 0], atol=5e-5)
    np.testing.assert_array_equal(got[layer, 2], mat[layer, 2])
    np.testing.assert_array_equal(got[0], mat[0])
    np.testing.assert_array_equal(got[2], mat[2])


@pytest.mark.parametrize("heads", [20, 4])
def test_decode_attention_on_one_bfloat16_kv_head(
        kernels_through_the_interpreter, heads):
    """ONE bfloat16 KV head under several query heads: `decode_attention_
    takes` it (half a 32-bit word a position: its rows as they lie) and the
    kernel is `_attend_cached` on the same stack; rows beyond a slot's are
    NaN here and must not reach the output; a slot without rows gets zeros."""
    A = kernels_through_the_interpreter
    n, t, d, block, layer = 3, 64, 128, 16, 2
    rows = jnp.asarray([0, 1, block, block + 1, t, 0, 37], jnp.int32)
    b = rows.shape[0]
    ks = jax.random.split(jax.random.key(heads), 3)
    q = jax.random.normal(ks[0], (b, heads, d), jnp.float32).astype(
        jnp.bfloat16)
    k, v = (jax.random.normal(key, (n, b, t, 1, d), jnp.float32).astype(
        jnp.bfloat16) for key in ks[1:])
    assert A.decode_attention_takes(k, v)
    assert not A.decode_attention_takes(k[:, :, :24], v[:, :, :24])
    held = jnp.arange(t)[None, :] < rows[:, None]
    want = decoding._attend_cached(q[:, None], k[layer], v[layer],
                                   jnp.full((b, 1), t), held)[:, 0]
    stale = ~held[None, :, :, None, None]
    got = jax.jit(A.decode_attention)(
        q, jnp.where(stale, jnp.nan, k), jnp.where(stale, jnp.nan, v),
        jnp.int32(layer), rows)
    assert got.shape == q.shape and got.dtype == q.dtype
    live = np.asarray(rows) > 0
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert np.linalg.norm(got[live] - want[live]) \
        <= 4e-3 * np.linalg.norm(want[live])
    assert not got[~live].any()


def test_the_kernels_serve_it_and_say_so(kernels_through_the_interpreter):
    """The three kernels inside the engine's own programs (the interpreter;
    128-wide heads and channels in whole inner blocks so that each takes its
    shape), bfloat16 rows: the programs say which path they compiled, and the
    tokens are the plain programs' own."""
    wide = dict(head_dim=128, ssm_heads=512, max_seq=64, layers=6,
                layer_kinds=("ssm1", "mlp", "gqa", "mlp", "ssm1", "mlp"),
                dtype=jnp.bfloat16)
    cfg = T.config("jamba_debug", **wide)
    params = T.init_params(cfg, jax.random.key(1))
    prompts = [_prompt(40, 13), _prompt(41, 27)]

    def serve(cb):
        cb.shutdown()
        logits = []
        for slot, prompt in enumerate(prompts):
            logits.append(np.asarray(_install(cb, slot, prompt)[0]))
        tok = np.array([int(l.argmax()) for l in logits], np.int32)
        for _ in range(3):
            logits.append(_step_logits(cb, tok, [True, True]))
            tok = logits[-1].argmax(-1).astype(np.int32)
        cb._decode_impl(cb.params, jnp.asarray(tok), cb.cache, cb._rng,
                        jnp.zeros(2), jnp.zeros(2, jnp.int32),
                        jnp.ones(2, bool))  # the step's own trace
        return np.concatenate([np.atleast_2d(l) for l in logits])

    cb = ContinuousBatcher(cfg, params, max_len=64, slots=2)
    got = serve(cb)
    assert cb.ssm_path == {"prefill_16": "scan:kernel",
                           "prefill_32": "scan:kernel",
                           "decode": "state:kernel"}
    assert cb.decode_attention_path == {"decode": "kernel"}
    kernels_through_the_interpreter._on_tpu = lambda: False
    plain = ContinuousBatcher(cfg, params, max_len=64, slots=2)
    want = serve(plain)
    assert plain.ssm_path["decode"] == "state:plain"
    assert plain.decode_attention_path == {"decode": "dense"}
    err = R.compare_logits(got, want)
    assert err["rms_err_over_std"] < 0.02, err


def test_a_short_prompt_leaves_its_true_last_window_and_state(params):
    """The prefill program of a prompt shorter than its bucket (21 of 32, and
    no multiple of the chunk of 8) leaves the state and the convolution
    window that the same 21 tokens leave a position at a time."""
    cb = ContinuousBatcher(CFG, params, max_len=64, slots=1)
    cb.shutdown()
    prompt = _prompt(12, 21)
    _, _, mat, conv = _install(cb, 0, prompt)
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


def test_the_scheduler_serves_it_beside_busy_slots(params):
    """Through `submit`: admit, pump, lookahead and retire; greedy tokens the
    reference ranks first at every position; every state installed is given
    back; the bytes of state the steps rewrote."""
    cb = ContinuousBatcher(CFG, params, max_len=64, slots=3)
    try:
        prompts = [_prompt(4, 19), _prompt(5, 9), _prompt(6, 33),
                   _prompt(7, 12)]
        futs = [cb.submit(p, SamplingParams(max_tokens=16)) for p in prompts]
        outs = [f.result(300) for f in futs]
    finally:
        cb.shutdown()
    for prompt, out in zip(prompts, outs):
        assert len(out) == 16
        ref = R.logits(params, np.asarray(prompt + out[:-1])[None], CONF,
                       last=16)
        got = R.compare_tokens(out, np.asarray(ref[0]))
        assert got["argmax_agree"] == 1.0, got
    st = cb.stats
    assert st["state_installs"] == st["state_resets"] == 4
    assert "moe_assignments" not in st  # no layer routes
    assert st["kv_rows_held"] % CFG.layers_of("gqa") == 0
    # a step's every active sequence: 6 mixers' states and windows, each way
    a_sequence = 2 * 6 * (16 * 256 * 4 + 3 * 256 * 4)
    assert st["state_bytes_rewritten"] % a_sequence == 0
    assert st["steps"] <= st["state_bytes_rewritten"] // a_sequence \
        <= 3 * st["steps"]
    assert not np.asarray(cb.cache.mat).any()
    assert not np.asarray(cb.cache.conv).any()


def test_a_reused_slot_shows_nothing_of_its_last_occupant(params):
    """One slot: a long prompt that decodes on, then a short one: its answer
    is the one a fresh engine gives (state and window reset); an install
    overwrites the slot's whole state, window and rows, a step leaves a free
    slot's state as it is, and eviction clears the slot's alone."""
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
    _, row_k, mat, conv = _install(cb, 0, short)
    cache = cb.cache
    assert not np.asarray(cache.k[:, 0, 16:]).any()
    assert (np.asarray(cache.k[:, 0, :5]) != 1).all()
    np.testing.assert_array_equal(cache.mat[:, 0], mat)
    np.testing.assert_array_equal(cache.conv[:, 0], conv)
    assert (np.asarray(cache.mat[:, 1]) == 1).all()
    mat = np.asarray(mat)
    _step_logits(cb, np.zeros(2, np.int32), [True, False])
    assert (np.asarray(cb.cache.mat[:, 1]) == 1).all()
    assert (np.asarray(cb.cache.conv[:, 1]) == 1).all()
    assert np.abs(np.asarray(cb.cache.mat[:, 0]) - mat).max() > 0
    cleared = cb._reset_state_jit(cb.cache, 0)
    assert not np.asarray(cleared.mat[:, 0]).any()
    assert not np.asarray(cleared.conv[:, 0]).any()
    assert (np.asarray(cleared.mat[:, 1]) == 1).all()


# What the system differs from the reference by at these widths is under 2e-5
# (above). Each change below is another model by a wide margin: the limit a
# check holds the system to lies between.
@pytest.mark.parametrize("change,floor", [
    (dict(state="bfloat16"), 2e-4), (dict(scan_sum="bfloat16"), 2e-4),
    (dict(precision="bfloat16"), 2e-3), (dict(drop=("norms",)), 2e-2),
    (dict(drop=("conv_bias",)), 2e-2), (dict(drop=("d",)), 2e-2),
    (dict(drop=("dt_bias",)), 2e-2)])
def test_the_reference_without_a_part_is_another_model(params, change, floor):
    """A bfloat16 state, a bfloat16 sum in the scan, bfloat16 matmuls and
    each `drop=` switch move the reference's logits far beyond what the
    system differs by: a limit of 1e-4 refuses every one."""
    tokens = np.asarray(_prompt(11, 24))[None]
    whole = R.logits(params, tokens, CONF)
    other = R.logits(params, tokens, CONF, **change)
    err = R.compare_logits(np.asarray(other[0]), np.asarray(whole[0]))
    assert err["rms_err_over_std"] > floor, err


def test_the_stated_precision_stands_between_float32_and_a_lower_one(params):
    """`precision="stated"` (bfloat16 operands and stream, float32 sums and
    state) moves the float32 parameters' reference by a rounding of the
    stream and by less than a bfloat16 accumulator does."""
    tokens = np.asarray(_prompt(11, 24))[None]
    whole = np.asarray(R.logits(params, tokens, CONF)[0])
    stated, lower = (
        R.compare_logits(np.asarray(R.logits(
            params, tokens, CONF, precision=p)[0]), whole)["rms_err_over_std"]
        for p in ("stated", "bfloat16"))
    assert 1e-4 < stated < lower, (stated, lower)


@pytest.mark.parametrize("path", ["plain", "kernel"])
def test_the_scan_alone_refuses_a_bfloat16_state_and_sum(
        request, params, path):
    """`serve_jamba.scan_check`, the number of the chip's check that
    isolates the scan: the program's scan (plain, and the kernel through the
    interpreter) is the float32 recurrence far inside its limit, and a
    bfloat16 state or a bfloat16 sum in the read-out far outside it."""
    from benchmarks import harness

    if path == "kernel":
        request.getfixturevalue("kernels_through_the_interpreter")
    runner = harness.load_module("runners", "serve_jamba")
    a_log = jnp.tile(params["blocks"]["ssm1"]["a_log"][0], (1, 2))  # 512
    out = runner.scan_check(
        a_log, 3000007001, 200, CFG.ssm_chunk,
        [("state", dict(state="bfloat16")),
         ("sum", dict(scan_sum="bfloat16"))])
    assert out["path"] == path and out["ok"], out
    assert max(out["o_rms_err_over_std"],
               out["state_rms_err_over_std"]) < runner.SCAN_RMS_MAX / 10, out
    lower = out["second_readings"]
    assert lower["state"]["o_rms_err_over_std"] > 10 * runner.SCAN_RMS_MAX
    assert lower["state"]["state_rms_err_over_std"] > 10 * runner.SCAN_RMS_MAX
    assert lower["sum"]["o_rms_err_over_std"] > 10 * runner.SCAN_RMS_MAX, out


def test_the_layer_types_follow_the_period():
    """Layer i attends iff i % attn_layer_period == attn_layer_offset: the
    published 28 layers have their attentions at 7 and 21."""
    kinds = R.layer_types({"num_hidden_layers": 28, "attn_layer_period": 14,
                           "attn_layer_offset": 7})
    assert [i for i, k in enumerate(kinds) if k == "gqa"] == [7, 21]
    assert CFG.layer_kinds[::2] == tuple(R.layer_types(CONF))
