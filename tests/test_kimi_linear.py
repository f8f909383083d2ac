"""Delta-rule linear-attention layers beside latent-attention layers through
the serving engine (models/kimi_linear.py): a float32 matrix state a head
with a convolution window, one latent row a position, a sigmoid router with
a selection bias, a held share of the experts, against the plain reference
(`benchmarks/reference_kimi_linear.py`) at toy widths on the CPU: 11 layers
(kda, two periods of kda kda mla kda, kda mla), 4 heads of 16, a latent of 32
+ 8, 16 experts in two shares of 8, top-4."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_kimi_linear as R
from ray_tpu.models import decoding, kimi_linear as K
from ray_tpu.models import transformer as T
from ray_tpu.models.continuous_batching import ContinuousBatcher
from ray_tpu.models.decoding import SamplingParams

CFG = T.config("kimi_linear_debug")


def published(cfg) -> dict:
    """The keys `reference_kimi_linear` reads, as a `config.json` spells
    them (layers numbered from 1)."""
    kinds = cfg.kinds
    return {
        "num_hidden_layers": cfg.layers, "rms_norm_eps": cfg.norm_eps,
        "first_k_dense_replace": 1,
        "linear_attn_config": {
            "kda_layers": [l + 1 for l, k in enumerate(kinds) if k == "kda"],
            "full_attn_layers": [l + 1 for l, k in enumerate(kinds)
                                 if k == "mla"]},
        "num_experts_per_token": cfg.experts_per_token,
        "moe_renormalize": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scale,
        "experts_held_first": cfg.experts_held[0] if cfg.experts_held else 0,
    }


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


def _step_logits(cb, tok, active):
    """One decode step of the batcher's own program body, its logits kept."""
    logits, cb.cache, aux = _step(cb.params, jnp.asarray(tok), cb.cache,
                                  jnp.asarray(active))
    return np.asarray(logits), aux


def test_prefill_then_decode_is_the_reference(params):
    """Prompts of 21 and 70 tokens (the second through the 128 bucket: four
    chunks of the scan, and shorter than its bucket) prefilled by the
    batcher's own program, installed, then 14 decode steps beside each other:
    every position's logits against ONE full forward of the reference."""
    cb = ContinuousBatcher(CFG, params, max_len=128, slots=2)
    cb.shutdown()
    prompts = [_prompt(2, 21), _prompt(3, 70)]
    firsts = []
    for slot, prompt in enumerate(prompts):
        last, row_k, row_v, mat, conv, latent, load, choice, reached = \
            cb._prefill(prompt)
        bucket = cb._bucket(len(prompt))
        assert row_k.shape[0] == 0 and mat.shape == (8, 4, 16, 16)
        assert mat.dtype == jnp.float32
        assert conv.shape == (8, 3 * 3 * 4 * 16)
        assert latent.shape == (3, bucket, 128)
        assert not np.asarray(latent[..., 40:]).any()
        assert choice.shape == (10, bucket, 4)
        assert int(load.sum()) == len(prompt) * 4 * 10  # pad rows not counted
        cb.cache = cb._install_jit(cb.cache, *cb._pad_row(row_k, row_v), slot,
                                   len(prompt), None, None, None, mat, conv,
                                   latent)
        firsts.append(np.asarray(last))
    seqs = [list(p) for p in prompts]
    system = [[f] for f in firsts]
    tok = np.array([int(f.argmax()) for f in firsts], np.int32)
    for _ in range(14):
        for s, t in zip(seqs, tok):
            s.append(int(t))
        logits, aux = _step_logits(cb, tok, [True, True])
        assert int(aux["expert_load"].sum()) == 2 * 4 * 10
        for slot in range(2):
            system[slot].append(logits[slot])
        tok = logits.argmax(-1).astype(np.int32)
    conf = published(CFG)
    for slot in range(2):
        n = len(system[slot])
        ref, _ = R.logits(params, np.asarray(seqs[slot])[None], conf, last=n)
        out = R.compare_logits(np.stack(system[slot]), np.asarray(ref[0]))
        assert out["rms_err_over_std"] < 2e-4, (slot, out)
        assert out["argmax_agree"] == 1.0


@pytest.fixture
def scan_through_the_interpreter(monkeypatch):
    """`ops.delta_rule.chunk_scan` where `kda_chunks` chooses it: the chip's
    path on the CPU."""
    import jax.experimental.pallas as pl

    from ray_tpu.ops import attention as A

    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))


@pytest.mark.parametrize("steps", ["to_one", "to_two"])
@pytest.mark.parametrize("path, chunk", [
    ("plain", 4), ("plain", 32), ("plain", 128),
    ("kernel", 16), ("kernel", 32), ("kernel", 64)])
def test_the_chunked_scan_is_the_token_scan(path, chunk, steps, request):
    """State and outputs of `kda_chunks` against `kda_step` a position at a
    time, by both of its spellings (the scan of XLA operations at heads of
    8; the kernel `ops.delta_rule.chunk_scan` through the interpreter at
    heads of 128, one, two and four sub-chunks a chunk), from a state that
    is not zero, over 70 positions (a last chunk that is not whole; one
    chunk longer than the sequence), one channel decaying by e^-30 a
    position (its `1 / G` would overflow within a chunk), one not at all,
    one key repeated thirty times (the system's entries are then as large
    as they get), a run of pad positions (beta 0, decay 1) that must leave
    the state alone, bit for bit where a call is pads alone, six heads
    (two of the kernel's blocks of three), and steps beta in (0, 1) or, as
    `kda_neg_eigval` makes them, in (0, 2) (the system's entries twice as
    large)."""
    from ray_tpu.ops import delta_rule, traced

    kernel = path == "kernel"
    if kernel:
        request.getfixturevalue("scan_through_the_interpreter")
    b, s, h, d = 2, 70, 6, 128 if kernel else 8
    ks = jax.random.split(jax.random.key(chunk), 6)
    q, k, v = (jax.random.normal(key, (b, s, h, d)) for key in ks[:3])
    q = q * (8 / d) ** 0.5  # of the length it has at heads of 8
    k = k.at[0, 10:40].set(k[0, 10])  # one key thirty times over: products of 1
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    log_a = -jnp.exp(jax.random.normal(ks[3], (b, s, h, d)))
    log_a = log_a.at[..., 0].set(-30.0).at[..., 1].set(0.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    if steps == "to_two":
        beta = 2.0 * beta
        assert float(beta.max()) > 1.7
    log_a = log_a.at[1, 60:].set(0.0)
    beta = beta.at[1, 60:].set(0.0)
    state = jax.random.normal(ks[5], (b, h, d, d))
    assert delta_rule.chunk_scan_takes(state, q) == kernel
    assert h // delta_rule.SCAN_HEADS and h % delta_rule.SCAN_HEADS
    want, outs = state, []
    step = jax.jit(K.kda_step)
    for t in range(s):
        want, o = step(want, q[:, t], k[:, t], v[:, t], log_a[:, t],
                       beta[:, t])
        outs.append(o)
        if t == 59:
            at_60 = want
    scan = jax.jit(functools.partial(K.kda_chunks, chunk=chunk))
    with traced.booked("delta_rule") as seen:
        got, o = scan(state, q, k, v, log_a, beta)
    assert seen == {f"scan:{path}"}
    # steps up to two: `tests/test_solar_open2.py`'s tolerance for them
    atol = 2e-5 if steps == "to_one" else 5e-5
    np.testing.assert_allclose(o, jnp.stack(outs, 1), atol=atol)
    np.testing.assert_allclose(got, want, atol=atol)
    np.testing.assert_array_equal(want[1], at_60[1])  # pads changed nothing
    assert np.isfinite(np.asarray(got)).all()
    still, o = scan(state, q, k, v, jnp.zeros_like(log_a),
                    jnp.zeros_like(beta))
    np.testing.assert_array_equal(still, state)  # pads alone: bit for bit
    np.testing.assert_allclose(
        o, jnp.einsum("bshk,bhkv->bshv", q, state), atol=2e-5)


def test_the_absorbed_step_is_the_expanded_form(params):
    """One latent layer: positions 0..19 prefilled (expanded: keys and values
    of every head built from the latent), then position 20 by a decode step
    (absorbed: the latent rows read as they lie), against positions 0..20
    prefilled at once; and the rows the step left in the stack are the
    prefill's."""
    p = jax.tree.map(lambda a: a[1], params["blocks"]["mla"])
    x = jax.random.normal(jax.random.key(0), (2, 21, CFG.hidden))
    pos = jnp.broadcast_to(jnp.arange(21), (2, 21))
    stack = jnp.zeros((3, 2, 32, CFG.latent_row))
    whole, full = K.mla_attention(CFG, x, p, pos, stack, None,
                                  jnp.ones((2, 21), bool), 1)
    _, stack = K.mla_attention(CFG, x[:, :20], p, pos[:, :20], stack, None,
                               jnp.ones((2, 20), bool), 1)
    step, stack = K.mla_attention(
        CFG, x[:, 20:], p, pos[:, 20:], stack,
        jnp.arange(32)[None] <= pos[:, 20:], jnp.ones((2, 1), bool), 1,
        rows=jnp.array([21, 21]))
    np.testing.assert_allclose(step[:, 0], whole[:, 20], atol=1e-5)
    np.testing.assert_array_equal(stack, full)
    assert not np.asarray(stack[0]).any() and not np.asarray(stack[2]).any()


@pytest.fixture
def kernels_through_the_interpreter(monkeypatch):
    """`tests/test_mimo.py`'s: the chip's path on the CPU."""
    import jax.experimental.pallas as pl

    from ray_tpu.ops import attention as A

    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    monkeypatch.setattr(A, "DENSE_SCORES_BYTES", 0)
    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    return A


# a latent sublayer as this model runs it, and as DeepSeek-V3's family does
MLA_FIELDS = {
    "plain": dict(),
    "low_rank_rotated_scaled": dict(mla_q_rank=24, mla_rotate=True,
                                    mla_scales=(1.5, 2.0)),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("spelled", ["whole", "blocked"])
@pytest.mark.parametrize("fields", sorted(MLA_FIELDS))
def test_a_latent_prefill_through_the_flash_kernel_is_the_expanded_one(
        kernels_through_the_interpreter, monkeypatch, fields, spelled, dtype):
    """One latent sublayer over 256 fresh positions, heads of 128 + 64 and
    values of 128 (the published widths: keys of 192 in 256 lanes), a
    sequence of 256 rows and one of 200 with pad rows behind it: the flash
    forward gives every REAL row what `_attend_expanded` gives, as one
    [H, S, S] array and 96 queries at a time (a last block that is not
    whole), and leaves the same latent rows; each spelling books itself."""
    A = kernels_through_the_interpreter
    cfg = T.config("kimi_linear_debug", head_dim=128, mla_rope_dim=64,
                   dtype=dtype, **MLA_FIELDS[fields])
    p = jax.tree.map(lambda a: a[1], T.init_params(
        cfg, jax.random.key(7))["blocks"]["mla"])
    b, s = 2, 256
    x = jax.random.normal(jax.random.key(1), (b, s, cfg.hidden)).astype(dtype)
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    real = jnp.arange(s)[None] < jnp.array([256, 200])[:, None]
    stack = jnp.zeros((3, b, s, cfg.latent_row), dtype)

    def run():
        with decoding.fresh_rows_attended() as seen:
            out, rows = jax.jit(functools.partial(K.mla_attention, cfg))(
                x, p, pos, stack, real, real, 1)
        return out - x, rows, seen

    got, rows, seen = run()
    assert seen == {"flash"}
    monkeypatch.setattr(A, "_on_tpu", lambda: False)
    if spelled == "blocked":
        monkeypatch.setattr(K, "PREFILL_LOGITS_MAX", 0)
        monkeypatch.setattr(K, "PREFILL_QUERY_BLOCK", 96)
    want, want_rows, seen = run()
    assert seen == {"dense"}
    np.testing.assert_array_equal(rows, want_rows)
    got, want = (np.asarray(a, np.float32)[np.asarray(real)]
                 for a in (got, want))
    assert np.isfinite(got).all() and np.abs(want).max() > 0.1
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, atol=2e-5)
    else:  # one rounding to bf16, another order of summation
        assert np.linalg.norm(got - want) <= 6e-3 * np.linalg.norm(want)


@pytest.mark.parametrize("where", ["off_the_chip", "a_toy_width_on_it"])
def test_a_shape_the_flash_kernel_refuses_stays_expanded(
        params, monkeypatch, where):
    """What `flash_attention_takes` refuses runs the expanded form and the
    engine says "dense" for the bucket: any shape off the chip, and on it
    heads of 16 + 8 beside values of 16 (no whole lanes), with the plain
    spelling's very numbers."""
    from ray_tpu.ops import attention as A

    def prefill():
        cb = ContinuousBatcher(CFG, params, max_len=128, slots=1)
        cb.shutdown()
        out = cb._prefill(_prompt(4, 100))
        # (what `engine_stats()["prefill_attention_path"]` carries)
        assert cb.prefill_attention_path == {"prefill_128": "dense"}
        return out

    want = prefill()
    if where == "a_toy_width_on_it":
        monkeypatch.setattr(A, "_on_tpu", lambda: True)
        monkeypatch.setattr(A, "DENSE_SCORES_BYTES", 0)
    for a, b in zip(prefill(), want):
        np.testing.assert_array_equal(a, b)


def test_the_latent_kernel_reads_the_held_rows(monkeypatch):
    """`latent_decode_attention` through the interpreter against the XLA
    spelling on the same stack: a layer that is not the first; slots that
    hold no row, one, a block, a block and one, every row. What lies beyond a
    slot's rows is NaN here and must not reach the output."""
    import jax.experimental.pallas as pl

    from ray_tpu.ops import attention as A

    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    monkeypatch.setattr(A, "DECODE_BLOCK_ROWS", 16)
    monkeypatch.setattr(A, "DECODE_THIN_BLOCK_ROWS", 16)
    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    n, t, width, value, layer, heads = 3, 64, 256, 128, 1, 6
    rows = jnp.asarray([0, 1, 16, 17, t, 0, 37], jnp.int32)
    b = rows.shape[0]
    ks = jax.random.split(jax.random.key(0), 2)
    q = jax.random.normal(ks[0], (b, heads, width)).astype(jnp.bfloat16)
    stack = jax.random.normal(ks[1], (n, b, t, width)).astype(jnp.bfloat16)
    held = jnp.arange(t)[None] < rows[:, None]
    assert A.latent_decode_attention_takes(stack, value)
    monkeypatch.setattr(A, "_on_tpu", lambda: False)
    want = K.latent_attend(q, stack, layer, rows, held, value, 0.1)
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    got = jax.jit(functools.partial(K.latent_attend, value_dim=value,
                                    sm_scale=0.1))(
        q, jnp.where(held[None, :, :, None], stack, jnp.nan), layer, rows,
        held)
    assert got.shape == (b, heads, value) and got.dtype == jnp.float32
    live = np.asarray(rows) > 0
    # one rounding of the probabilities to bfloat16, another order of sums
    assert np.linalg.norm(got[live] - want[live]) <= 4e-3 * np.linalg.norm(
        want[live])
    np.testing.assert_allclose(got[live], want[live], rtol=2e-2, atol=1e-2)
    assert not np.asarray(got[~live]).any()


def test_the_state_kernel_is_the_step(monkeypatch):
    """`ops.delta_rule.state_update` through the interpreter against
    `kda_step` on the same stack: a layer that is not the first, the other
    layers untouched, a sequence that takes no part (decay 1, beta 0) kept
    bit for bit."""
    import jax.experimental.pallas as pl

    from ray_tpu.ops import attention as A, delta_rule

    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    n, b, h, d, layer = 3, 4, 2, 128, 1
    ks = jax.random.split(jax.random.key(3), 6)
    mat = jax.random.normal(ks[0], (n, b, h, d, d))
    q, k, v = (jax.random.normal(key, (b, h, d)) for key in ks[1:4])
    log_a = -jnp.exp(jax.random.normal(ks[4], (b, h, d))).at[2].set(0.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (b, h))).at[2].set(0.0)
    assert delta_rule.state_update_takes(mat)
    want, o_want = K.kda_step(mat[layer], q, k, v, log_a, beta)
    got, o = jax.jit(delta_rule.state_update)(mat, layer, q, k, v, log_a,
                                              beta)
    np.testing.assert_allclose(got[layer], want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(o, o_want, rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(got[layer, 2], mat[layer, 2])
    np.testing.assert_array_equal(got[0], mat[0])
    np.testing.assert_array_equal(got[2], mat[2])


def test_the_scheduler_serves_it_beside_busy_slots(params):
    """Through `submit`: admit, pump, lookahead and retire; greedy tokens the
    reference ranks first at every position; every state installed is given
    back; the expert counters whole."""
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
    # a half held: no cap, so the programs gathered a row for every
    # assignment of every row they ran (pad rows and free slots among them)
    assert st["moe_rows_gathered"] >= st["moe_assignments"] \
        > st["moe_assignments_held"]
    assert st["moe_rows_gathered"] % (4 * CFG.sparse_layers) == 0
    assert st["moe_calls_whole_layout"] == 0
    # a latent layer's rows are read as held, a linear layer keeps none
    assert st["kv_rows_held"] % CFG.layers_of("mla") == 0
    # every slot was given back: no matrix state, no window is anyone's
    assert not np.asarray(cb.cache.mat).any()
    assert not np.asarray(cb.cache.conv).any()


def test_a_reused_slot_shows_nothing_of_its_last_occupant(params):
    """One slot: a long prompt that decodes on, then a short one: its answer
    is the one a fresh engine gives; a release clears the states; an install
    overwrites the slot's whole latent rows, and a step leaves a free slot's
    state as it is."""
    long_one, short = _prompt(8, 40), _prompt(9, 5)
    cb = ContinuousBatcher(CFG, params, max_len=64, slots=2)
    try:
        cb.submit(long_one, SamplingParams(max_tokens=20)).result(300)
        reused = cb.submit(short, SamplingParams(max_tokens=12)).result(300)
    finally:
        cb.shutdown()
    fresh_cb = ContinuousBatcher(CFG, params, max_len=64, slots=2)
    try:
        fresh = fresh_cb.submit(short, SamplingParams(max_tokens=12)
                                ).result(300)
    finally:
        fresh_cb.shutdown()
    assert reused == fresh
    ones = {n: jnp.ones_like(getattr(cb.cache, n))
            for n in ("mat", "conv", "latent")}
    cb.cache = cb._empty_cache()._replace(**ones)
    _, row_k, row_v, mat, conv, latent, *_ = cb._prefill(short)
    cache = cb._install_jit(cb.cache, *cb._pad_row(row_k, row_v), 0, 5, None,
                            None, None, mat, conv, latent)
    # its own rows of bucket length (pad positions masked by the length),
    # then zeros: nothing of the ones that were there
    assert not np.asarray(cache.latent[:, 0, 16:]).any()
    assert (np.asarray(cache.latent[:, 0, :16, :40]) != 1).all()
    np.testing.assert_array_equal(cache.mat[:, 0], mat)
    np.testing.assert_array_equal(cache.conv[:, 0], conv)
    assert (np.asarray(cache.mat[:, 1]) == 1).all()
    mat, conv = np.asarray(mat), np.asarray(conv)
    # a step for slot 0 alone: slot 1's states stay; then slot 0 is released
    cb.cache = cache
    _step_logits(cb, np.zeros(2, np.int32), [True, False])
    assert (np.asarray(cb.cache.mat[:, 1]) == 1).all()
    assert (np.asarray(cb.cache.conv[:, 1]) == 1).all()
    assert np.abs(np.asarray(cb.cache.mat[:, 0]) - mat).max() > 0
    cleared = cb._reset_state_jit(cb.cache, 0)
    assert not np.asarray(cleared.mat[:, 0]).any()
    assert not np.asarray(cleared.conv[:, 0]).any()
    assert (np.asarray(cleared.mat[:, 1]) == 1).all()


def test_the_shares_add_up_to_the_uncut_layer(params):
    """The routed parts the two shares give (experts 0-7 here, 8-15 on the
    other chip) plus the shared expert, which both compute alike, counted
    ONCE, are the uncut reference's whole layer; and the program's layer for
    a share is that share's part plus the shared expert."""
    whole = dataclasses.replace(CFG, experts_held=None)
    sparse = T.init_params(whole, jax.random.key(6))["blocks"]["sparse"]
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
        parts = [R.routed_part(y, w, stacks, layer * 16 + first, first, 8)
                 for first in (0, 8)]
        shared = R.swiglu(y, small["shared_gate"], small["shared_up"],
                          small["shared_down"])
        uncut = R.routed_part(y, w, stacks, layer * 16, 0, 16) + shared
    np.testing.assert_allclose(parts[0] + parts[1] + shared, uncut, atol=1e-5)
    assert np.abs(np.asarray(parts[0])).max() > 0.01
    for share, first in enumerate((0, 8)):
        cfg = dataclasses.replace(CFG, experts_held=(first, 8))
        p = dict(small, **{n: sparse[n][:, first:first + 8]
                           for n in R.EXPERT_LEAVES})
        # the program's x + routed + shared, from x = 0 and ln_mlp = 1: y is
        # the normed input, so hand it the rows whose norm is y
        x, load, picked, reached = K.sparse_mlp(
            cfg, y[None] * 0, dict(p, ln_mlp=p["ln_mlp"] * 0), None, layer,
            K.router)
        assert not np.asarray(x).any()  # zeros in, zeros out: no bias
        routed, _ = T.moe_dropless(cfg, y[None], p, None, layer,
                                   K.router(cfg, y, p))
        np.testing.assert_allclose(routed[0], parts[share], atol=1e-5)
        np.testing.assert_array_equal(np.sort(K.router(cfg, y, p)[1]),
                                      np.sort(chosen))


def test_absent_assignments_add_exactly_zero(params):
    """A token whose experts are all on the other chip gets exactly zero from
    the routed part here."""
    cfg = dataclasses.replace(CFG, experts_held=(8, 8))
    sparse = params["blocks"]["sparse"]
    p = {n: a[0] for n, a in sparse.items() if n not in R.EXPERT_LEAVES}
    p.update({n: sparse[n] for n in R.EXPERT_LEAVES})
    y = jax.random.normal(jax.random.key(1), (1, 9, CFG.hidden))
    weights = jnp.full((9, 4), 0.5)
    experts = jnp.tile(jnp.arange(4), (9, 1)).at[3].set(
        jnp.array([8, 9, 1, 2]))
    out, load = T.moe_dropless(cfg, y, p, None, 0, (weights, experts))
    assert not np.asarray(out[0, :3]).any() and not np.asarray(out[0, 4:]).any()
    assert np.abs(np.asarray(out[0, 3])).max() > 0
    assert int(load[:8].sum()) == 9 * 4 - 2


@pytest.mark.parametrize("change", [
    dict(drop=("decay",)), dict(drop=("beta",)), dict(drop=("gate",)),
    dict(drop=("conv",)), dict(drop=("bias",)), dict(drop=("scale",)),
    dict(drop=("shared",)), dict(drop=("rope",)), dict(state="bfloat16")])
def test_the_reference_without_a_part_is_another_model(params, change):
    """Each part the reference can leave out moves its logits far beyond
    what the system differs by (2e-4 above); the selection bias moves the
    sets that are chosen."""
    tokens = np.asarray(_prompt(11, 24))[None]
    conf = published(CFG)
    whole, routes = R.logits(params, tokens, conf)
    other, other_routes = R.logits(params, tokens, conf, **change)
    err = R.compare_logits(np.asarray(other[0]), np.asarray(whole[0]))
    floor = 2e-3 if change.get("state") else 2e-2
    assert err["rms_err_over_std"] > floor, err
    if change.get("drop") == ("bias",):
        assert (np.sort(routes["chosen"][0])
                != np.sort(other_routes["chosen"][0])).any()


@pytest.mark.parametrize("change, says", [
    (dict(layer_kinds=("kda", "full")), "unknown layer kinds"),
    (dict(layer_kinds=("kda", "ssm")), "unknown layer kinds"),
    (dict(layers=10), "whole periods"),
    (dict(kda_conv=0), "kda_conv 0 is its taps"),
    (dict(mla_latent=0), r"mla_latent \+ mla_rope_dim values a position"),
    (dict(dense_mlp_hidden=0), "dense_mlp_hidden is its width"),
    (dict(gqa_gate=True), "gqa_gate is a gkv layer's output gate"),
    (dict(window=8), "window: no field of .*kimi_linear"),
    (dict(kv_heads=2), "all alike: kv_heads 2"),
    (dict(tie_embeddings=True), "tie_embeddings: no field of"),
    (dict(router_score="tanh"), "unknown router_score"),
    (dict(experts_held=(12, 8)), "no share of num_experts"),
    (dict(layer_kinds=(), lead_kind="full", tail_kinds=()),
     "kda_conv, mla_latent.*no field of .*transformer"),
])
def test_the_configuration_is_validated(change, says):
    with pytest.raises(ValueError, match=says):
        dataclasses.replace(CFG, **change)


def test_each_refusal_names_what_it_refuses(params):
    from ray_tpu.models.disagg_prefill import DisaggPrefillEngine
    from ray_tpu.models.paged_kv import PagedBatcher

    assert CFG.stateful and CFG.keeps == ("mat", "conv", "latent")
    with pytest.raises(ValueError, match="mat, conv, latent.*pages hold no"):
        PagedBatcher(CFG, params, max_len=64, slots=2, page_size=16)
    with pytest.raises(ValueError, match="mat, conv, latent.*KV channel"):
        DisaggPrefillEngine(CFG, params, max_len=64)
    with pytest.raises(ValueError, match="layer pattern.*cached forward"):
        T.forward(CFG, params, jnp.zeros((1, 8), jnp.int32))
    cache = decoding.init_cache(CFG, 1, 16)
    with pytest.raises(ValueError, match="layer pattern.*no other cache"):
        decoding.forward_cached(
            CFG, params, jnp.zeros((1, 1), jnp.int32),
            jnp.zeros((1, 1), jnp.int32), cache, jnp.ones((1, 16), bool),
            jnp.ones((1, 1), bool), access=lambda layer: None)
    # the sigmoid router is the window-and-full family's too since its second
    # model (PR 58); a latent the experts work in is not
    dataclasses.replace(T.config("laguna_debug"), router_score="sigmoid")
    with pytest.raises(ValueError, match="moe_latent: no field of .*laguna"):
        dataclasses.replace(T.config("laguna_debug"), moe_latent=16)
