"""MiMo-V2-Flash's block through the serving engine (models/laguna.py, the list
form): window layers of 8 with a learned sink beside full layers, a KV-head
count by kind, keys of 24 (carried in 32: two pieces of 16) beside values of
16, a value scale, sigmoid top-2 of 8 experts in two shares of 4, against the
plain reference (`benchmarks/reference_mimo.py`) at toy widths on the CPU."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_mimo as R
from ray_tpu.models import decoding, laguna, pattern
from ray_tpu.models import transformer as T
from ray_tpu.models.continuous_batching import ContinuousBatcher
from ray_tpu.models.decoding import SamplingParams
from ray_tpu.ops import attention as A

CFG = T.config("mimo_v2_debug")
# The limits of the comparison with the reference, at these widths in float32
# on the CPU. Logits, RMS error over the reference's standard deviation: the
# system is the reference to float32 rounding through 7 layers (1e-6 to 2e-5
# over seeds; Laguna's toy reads the same), a reference that accumulates its
# projections in bfloat16 reads 2e-3 to 2e-2: the limit lies a decade from
# each.
LOGITS_RMS_MAX = 2e-4
# Every greedy token is the reference's first: with float32 on both sides the
# largest logit is the same one (no near-tie survives 1e-5 of the spread).
ARGMAX_AGREE_MIN = 1.0


def published(cfg) -> dict:
    """The keys `reference_mimo` reads, as a `config.json` spells them."""
    return {
        "num_hidden_layers": cfg.layers, "head_dim": cfg.hd,
        "v_head_dim": cfg.value_dim, "num_attention_heads": cfg.heads,
        "num_key_value_heads": cfg.kv_heads,
        "swa_num_key_value_heads": cfg.window_kv_heads,
        "layernorm_epsilon": cfg.norm_eps, "sliding_window": cfg.window,
        "hybrid_layer_pattern": [int(k == "window") for k in cfg.kinds],
        "moe_layer_freq": [0] + [1] * (cfg.layers - 1),
        "partial_rotary_factor": cfg.partial_rotary,
        "rope_theta": cfg.rope_theta, "swa_rope_theta": cfg.window_rope_theta,
        "attention_value_scale": cfg.value_scale,
        "add_swa_attention_sink_bias": cfg.window_sink,
        "add_full_attention_sink_bias": False,
        "num_experts_per_tok": cfg.experts_per_token,
        "norm_topk_prob": cfg.norm_topk_prob, "routed_scaling_factor": None,
        "experts_held_first": cfg.experts_held[0] if cfg.experts_held else 0,
    }


@pytest.fixture(scope="module")
def params():
    return T.init_params(CFG, jax.random.key(5))


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n).tolist()


def test_the_pattern_and_its_counts():
    assert CFG.kinds == ("full", "window", "window", "full", "window",
                         "window", "window")
    assert (CFG.full_layers, CFG.window_layers, CFG.sparse_layers) == (2, 5, 6)
    assert not CFG.stateful
    shapes = {p: s for p, (s, _, _) in laguna.leaves(CFG).items()}
    assert shapes[("blocks", "full", "wq")] == (2, 128, 8, 24)
    assert shapes[("blocks", "full", "wk")] == (2, 128, 2, 24)  # 2 KV heads
    assert shapes[("blocks", "window", "wk")] == (5, 128, 4, 24)  # 4
    assert shapes[("blocks", "window", "wv")] == (5, 128, 4, 16)  # values 16
    assert shapes[("blocks", "window", "wo")] == (5, 8, 16, 128)
    assert shapes[("blocks", "window", "sink")] == (5, 8)
    assert ("blocks", "full", "sink") not in shapes
    assert ("blocks", "full", "wg") not in shapes  # no gate
    assert ("blocks", "sparse", "shared_up") not in shapes  # no shared expert
    assert shapes[("blocks", "sparse", "wi_gate")] == (6, 4, 128, 64)  # HELD
    assert shapes[("blocks", "sparse", "router")] == (6, 128, 8)  # all
    assert shapes[("blocks", "sparse", "router_bias")] == (6, 8)
    assert CFG.num_params() == sum(
        int(np.prod(s)) for s in shapes.values())
    axes = T.param_axes(CFG)
    made = jax.eval_shape(lambda: T.init_params(CFG, jax.random.key(0)))
    for path, leaf in jax.tree_util.tree_leaves_with_path(made):
        node = axes
        for k in path:
            node = node[k.key]
        assert len(node) == leaf.ndim, path


@pytest.mark.parametrize("kinds, cut", [
    # the benchmark's 11 layers behind the lead: 4 window, full, 5 window
    ((1,) * 4 + (0,) + (1,) * 5, [(1, 4), (1, 1), (1, 5)]),
    # the published 48: 4 window, full, then seven times 5 window + full, read
    # as seven periods of (4 window, full, window), then 4 window and full
    ((1,) * 4 + (0,) + ((1,) * 5 + (0,)) * 7, [(6, 7), (1, 4), (1, 1)]),
    # the debug preset
    ((1, 1, 0, 1, 1, 1), [(1, 2), (1, 1), (1, 3)]),
])
def test_the_loop_is_read_off_the_list(kinds, cut):
    names = tuple("window" if k else "full" for k in kinds)
    got = pattern.runs(names, laguna.RUN_MAX)
    assert [(len(unit), n) for unit, n in got] == cut
    assert sum(len(unit) * n for unit, n in got) == len(kinds)


def test_what_a_sequence_keeps_by_kind():
    """(iv) `kept` / `init_cache`: each kind's rows by its own KV heads, keys
    of 24 in two pieces of the values' 16."""
    full, ring = CFG.kept(64)
    assert (full.fields, full.layers, full.rows) == (("k", "v"), 2, 64)
    assert full.shapes == ((4, 16), (2, 16))  # 2 KV heads: 4 key pieces
    assert (ring.fields, ring.layers, ring.rows) == (("ring_k", "ring_v"), 5, 8)
    assert ring.shapes == ((8, 16), (4, 16))  # 4 KV heads: 8 key pieces
    cache = decoding.init_cache(CFG, 3, 64)
    assert cache.k.shape == (2, 3, 64, 4, 16)
    assert cache.v.shape == (2, 3, 64, 2, 16)
    assert cache.ring_k.shape == (5, 3, 8, 8, 16)
    assert cache.ring_v.shape == (5, 3, 8, 4, 16)
    assert cache.state is None and cache.mat is None
    assert laguna.key_row(CFG) == 32 and laguna.value_dim(CFG) == 16
    # Laguna's statement is what it was: one shape for K and V
    for kept in T.config("laguna_debug").kept(64):
        assert kept.shapes == () and kept.shape == (2, 16)


# -- (ii) the sink ---------------------------------------------------------------

def _qkv(seed, s, heads=8, kv=4, d=32, dv=16, b=2):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(b, s, heads, d)), jnp.float32),
            jnp.asarray(rng.normal(size=(b, s, kv, d)), jnp.float32),
            jnp.asarray(rng.normal(size=(b, s, kv, dv)), jnp.float32),
            jnp.asarray(rng.normal(size=(heads,)), jnp.float32))


def _by_the_formula(q, k, v, sink, window, sm_scale):
    """ISSUE 58's window layer, position by position in numpy: p_ij =
    exp(s_ij - m) / (exp(b - m) + sum exp(s_ij' - m)), m = max(b, max s)."""
    q, k, v, sink = (np.asarray(a, np.float64) for a in (q, k, v, sink))
    b, s, h, _ = q.shape
    rep = h // k.shape[2]
    out = np.zeros((b, s, h, v.shape[-1]))
    for n in range(b):
        for r in range(h):
            for i in range(s):
                js = np.arange(max(0, i - window + 1), i + 1)
                logits = k[n, js, r // rep] @ q[n, i, r] * sm_scale
                m = max(sink[r], logits.max())
                e = np.exp(logits - m)
                p = e / (np.exp(sink[r] - m) + e.sum())
                out[n, i, r] = p @ v[n, js, r // rep]
    return out


def _ring_steps(q, k, v, sink, window, sm_scale):
    """The same positions one decode step at a time through
    `laguna._ring_attention` (off the chip: the ring's dense spelling)."""
    b, s, h, d = q.shape
    kvh, dv = k.shape[2], v.shape[3]
    cfg = dataclasses.replace(CFG, window=window)
    ring_k = jnp.zeros((1, b, window, kvh * (d // dv), dv), jnp.float32)
    ring_v = jnp.zeros((1, b, window, kvh, dv), jnp.float32)
    out = []
    for i in range(s):
        ring_k, ring_v, o = laguna._ring_attention(
            cfg, q[:, i:i + 1], k[:, i:i + 1], v[:, i:i + 1],
            jnp.full((b, 1), i), jnp.ones((b, 1), bool), ring_k, ring_v, 0,
            None, sink, sm_scale)
        out.append(o)
    return jnp.concatenate(out, axis=1)


def _dense(q, k, v, sink, window, sm_scale):
    """`decoding._attend_cached` under the window as a key-length mask, one
    query position at a time."""
    b, s = q.shape[:2]
    out = []
    for i in range(s):
        seen = (jnp.arange(s) <= i) & (jnp.arange(s) > i - window)
        out.append(decoding._attend_cached(
            q[:, i:i + 1], k, v, jnp.full((b, 1), i),
            jnp.broadcast_to(seen, (b, s)), sink, sm_scale))
    return jnp.concatenate(out, axis=1)


SPELLINGS = {
    "band": lambda q, k, v, sink, w, sc: laguna._attend_band(
        q, k, v, w, sink, sc),
    "ring": _ring_steps,
    "dense": _dense,
}


@pytest.mark.parametrize("spelling", sorted(SPELLINGS))
def test_a_finite_sink_matches_the_formula(spelling):
    """Two and a half windows; the logits' factor is the head's own width's
    (24), not the 32 it is carried in."""
    q, k, v, sink = _qkv(1, 21)
    got = SPELLINGS[spelling](q, k, v, sink, 8, 24 ** -0.5)
    want = _by_the_formula(q, k, v, sink, 8, 24 ** -0.5)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-6)
    # and the sink is no small term: without it the output is another
    bare = SPELLINGS[spelling](q, k, v, None, 8, 24 ** -0.5)
    assert float(jnp.abs(bare - got).max()) > 0.05


@pytest.mark.parametrize("spelling", sorted(SPELLINGS))
def test_a_sink_of_minus_infinity_is_sinkless_attention_bit_for_bit(spelling):
    q, k, v, _ = _qkv(2, 21)
    gone = jnp.full((8,), -jnp.inf, jnp.float32)
    with_sink = SPELLINGS[spelling](q, k, v, gone, 8, None)
    without = SPELLINGS[spelling](q, k, v, None, 8, None)
    assert np.array_equal(np.asarray(with_sink), np.asarray(without))


# -- the kernels, through the interpreter -------------------------------------------

@pytest.fixture
def kernels_through_the_interpreter(monkeypatch):
    """`tests/test_llm.py`'s: the chip's path on the CPU."""
    import jax.experimental.pallas as pl

    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    monkeypatch.setattr(A, "DECODE_BLOCK_ROWS", 16)
    monkeypatch.setattr(A, "DECODE_THIN_BLOCK_ROWS", 16)
    monkeypatch.setattr(A, "DENSE_SCORES_BYTES", 0)
    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    return A


def _close(got, want, dtype):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, atol=2e-5)
    else:  # one rounding to bf16, another order of summation
        assert np.linalg.norm(got - want) <= 6e-3 * np.linalg.norm(want)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kv_heads, sink", [(4, False), (8, True)])
def test_decode_attention_takes_keys_in_pieces_and_a_sink(
        kernels_through_the_interpreter, kv_heads, sink, dtype):
    """The published layouts: 64 query heads on 4 KV heads (full, no sink)
    and on 8 (window, a sink), keys of 256 in two pieces of 128 beside values
    of 128, the logits by 1 / sqrt(192); slots that hold no row, one, a
    block and one, every row; NaN beyond a slot's rows."""
    rng = np.random.default_rng(kv_heads)
    b, t, h, d, dv = 5, 48, 64, 256, 128
    rows = jnp.asarray([0, 1, 17, 33, 48], jnp.int32)
    k = jnp.asarray(rng.normal(size=(2, b, t, kv_heads, d)), dtype)
    v = jnp.asarray(rng.normal(size=(2, b, t, kv_heads, dv)), dtype)
    q = jnp.asarray(rng.normal(size=(b, h, d)), dtype)
    s = jnp.asarray(rng.normal(size=(h,)), jnp.float32) if sink else None
    held = jnp.arange(t)[None, :] < rows[:, None]
    want = decoding._attend_cached(
        q[:, None], k[1], v[1], (rows - 1)[:, None], held, s, 192 ** -0.5)[:, 0]
    want = jnp.where((rows > 0)[:, None, None], want, 0)
    poison = jnp.where(held[None, :, :, None, None], 0, jnp.nan).astype(dtype)
    pieces = A.key_pieces(k + poison, dv)
    assert pieces.shape == (2, b, t, kv_heads * 2, dv)
    assert A.decode_attention_takes(pieces, v + poison)
    got = A.decode_attention(q, pieces, v + poison, 1, rows, sink=s,
                             sm_scale=192 ** -0.5)
    assert got.shape == (b, h, dv) and not np.isnan(np.asarray(
        got, np.float32)).any()
    _close(got, want, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_forward_takes_a_value_width_and_shared_kv_heads(
        kernels_through_the_interpreter, dtype):
    """q and k of 256, v of 128, 8 query heads on 2 KV heads read by index:
    against `mha_reference` over the repeated heads, the logits by 1 /
    sqrt(192)."""
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(1, 256, 8, 256)), dtype)
    k = jnp.asarray(rng.normal(size=(1, 256, 2, 256)), dtype)
    v = jnp.asarray(rng.normal(size=(1, 256, 2, 128)), dtype)
    assert A.flash_attention_takes(q, k, v)
    got = A.flash_attention(q, k, v, True, 192 ** -0.5, 128, 128)
    want = A.mha_reference(q, *A.gqa_expand(k, v, 8), sm_scale=192 ** -0.5)
    assert got.shape == (1, 256, 8, 128)
    _close(got, want, dtype)
    with pytest.raises(NotImplementedError, match="forward's alone"):
        jax.grad(lambda q: A.flash_attention(q, k, v).astype(
            jnp.float32).sum())(q)


def test_the_engine_takes_the_kernels_where_the_widths_are_whole_lanes(
        kernels_through_the_interpreter):
    """A prefill from position 0 books "flash" for its full layers and the
    decode step reads both kinds' rows through the kernel: keys of 192 (in
    256) beside values of 128, against the dense spelling's tokens."""
    cfg = T.config("mimo_v2_debug", head_dim=192, value_dim=128, layers=4,
                   layer_kinds=("full", "window", "full", "window"))
    params = T.init_params(cfg, jax.random.key(2))
    prompt = _prompt(11, 100)
    cb = ContinuousBatcher(cfg, params, max_len=256, slots=2)
    try:
        got = cb.submit(prompt, SamplingParams(max_tokens=6)).result(600)
        assert cb.prefill_attention_path == {"prefill_128": "flash"}
    finally:
        cb.shutdown()
    ref, _ = R.logits(params, np.asarray(prompt + got[:-1])[None],
                      published(cfg), last=6)
    assert R.compare_tokens(got, np.asarray(ref[0]))["argmax_agree"] == 1.0


# -- (i) prefill, then decode through the cache -------------------------------------

@jax.jit
def _step(params, tok, cache, mask):
    positions = cache.lengths[:, None]
    kv_mask = jnp.arange(cache.k.shape[2])[None, :] <= cache.lengths[:, None]
    logits, cache, aux = decoding.forward_cached(
        CFG, params, tok[:, None], positions, cache, kv_mask, mask[:, None])
    return logits[:, 0], aux, cache._replace(
        lengths=jnp.where(mask, cache.lengths + 1, cache.lengths))


def _system_logits(params, prompts, steps):
    """Every position's logits from the last prompt position on, of each
    prompt: the batcher's prefill program and install, then `steps` decode
    steps of all slots together through `forward_cached` on its cache."""
    cb = ContinuousBatcher(CFG, params, max_len=64, slots=len(prompts))
    cb.shutdown()
    firsts = []
    for slot, prompt in enumerate(prompts):
        last, row_k, row_v, ring_k, ring_v, load, choice, reached = \
            cb._prefill(prompt)
        bucket = cb._bucket(len(prompt))
        assert choice.shape == (6, bucket, 2)
        assert row_k.shape == (2, bucket, 4, 16)
        assert row_v.shape == (2, bucket, 2, 16)
        assert ring_k.shape == (5, 8, 8, 16) and ring_v.shape == (5, 8, 4, 16)
        assert int(load.sum()) == len(prompt) * 2 * 6  # pad rows not counted
        cb.cache = cb._install_jit(cb.cache, *cb._pad_row(row_k, row_v), slot,
                                   len(prompt), None, ring_k, ring_v)
        firsts.append(np.asarray(last))
    seqs = [list(p) for p in prompts]
    system = [[f] for f in firsts]
    tok = np.array([int(f.argmax()) for f in firsts], np.int32)
    active = jnp.ones(len(prompts), bool)
    for _ in range(steps):
        for s, t in zip(seqs, tok):
            s.append(int(t))
        logits, aux, cb.cache = _step(cb.params, jnp.asarray(tok), cb.cache,
                                      active)
        assert int(aux["expert_load"].sum()) == len(prompts) * 2 * 6
        for slot in range(len(prompts)):
            system[slot].append(np.asarray(logits[slot]))
        tok = np.asarray(logits).argmax(-1).astype(np.int32)
    return seqs, [np.stack(s) for s in system]


@pytest.fixture(scope="module")
def served(params):
    """Prompts of 13, 21 and 30 tokens (the longest nearly four windows,
    each shorter than its bucket) and 22 decode steps: positions reach 51,
    six times round the ring of 8."""
    return _system_logits(params, [_prompt(1, 13), _prompt(2, 21),
                                   _prompt(3, 30)], 22)


@pytest.mark.parametrize("slot", [0, 1, 2])
def test_prefill_then_decode_against_the_reference(params, served, slot):
    seqs, system = served
    ref, _ = R.logits(params, np.asarray(seqs[slot])[None], published(CFG),
                      last=len(system[slot]))
    out = R.compare_logits(system[slot], np.asarray(ref[0]))
    assert out["rms_err_over_std"] < LOGITS_RMS_MAX, out
    assert out["argmax_agree"] >= ARGMAX_AGREE_MIN


def test_a_reference_that_accumulates_in_bfloat16_fails_the_limits(
        params, served):
    """(v) The same comparison against the reference in the nearest precision
    below: at least one limit refuses it."""
    seqs, system = served
    ref, _ = R.logits(params, np.asarray(seqs[1])[None], published(CFG),
                      last=len(system[1]), precision="bfloat16")
    out = R.compare_logits(system[1], np.asarray(ref[0]))
    assert out["rms_err_over_std"] > 5 * LOGITS_RMS_MAX \
        or out["argmax_agree"] < ARGMAX_AGREE_MIN, out


@pytest.mark.parametrize("part", ["sink", "window", "value_scale", "rope",
                                  "bias"])
def test_the_reference_without_a_part_is_another_model(params, part):
    """What the chip check's second readings rest on."""
    seq = np.asarray(_prompt(10, 30))[None]
    conf = published(CFG)
    ref, _ = R.logits(params, seq, conf, last=8)
    other, routes = R.logits(params, seq, conf, last=8, drop=(part,))
    assert R.compare_logits(np.asarray(other[0]),
                            np.asarray(ref[0]))["rms_err_over_std"] > 0.02


def test_the_scheduler_serves_it_beside_busy_slots(params):
    """Through `submit`: admit, pump, lookahead and retire; greedy tokens the
    reference ranks first at every position, the expert counters whole."""
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
    assert st["moe_assignments"] == 2 * st["moe_rows"] * CFG.sparse_layers
    assert st["moe_assignments_held"] == sum(st["moe_expert_load"][:4])
    assert st["moe_assignments_held"] + sum(st["moe_expert_load"][4:]) \
        == st["moe_assignments"]
    assert 0.2 < st["moe_assignments_held"] / st["moe_assignments"] < 0.8
    assert 0 < st["moe_experts_reached"] <= 4 * CFG.sparse_layers * st["steps"]
    # a half held: no cap, so the programs gathered a row for every
    # assignment of every row they ran (pad rows and free slots among them)
    assert st["moe_rows_gathered"] >= st["moe_assignments"] \
        > st["moe_assignments_held"]
    assert st["moe_rows_gathered"] % (2 * CFG.sparse_layers) == 0
    assert st["moe_calls_whole_layout"] == 0


def test_a_reused_slot_shows_nothing_of_its_last_occupant(params):
    """(iv) One slot: a long prompt that decodes round the ring, then a prompt
    shorter than the window: its answer is the one a fresh engine gives, and
    after its install both stacks' rows beyond its length are zero."""
    long_one, short = _prompt(8, 40), _prompt(9, 5)
    cb = ContinuousBatcher(CFG, params, max_len=64, slots=1)
    try:
        cb.submit(long_one, SamplingParams(max_tokens=20)).result(300)
        # every ring row holds a key: its 24 values, then the 8 zeros of the
        # width it is carried in (the second piece's second half)
        ring = np.asarray(cb.cache.ring_k)  # [5, 1, 8, 8 pieces, 16]
        assert np.abs(ring[:, 0, :, 0::2]).min(axis=(0, 2, 3)).min() > 0
        assert np.abs(ring[:, 0, :, 1::2, 8:]).max() == 0.0
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
    cb.cache = jax.tree.map(jnp.ones_like, cb._empty_cache())
    _, row_k, row_v, ring_k, ring_v, *_ = cb._prefill(short)
    cache = cb._install_jit(cb.cache, *cb._pad_row(row_k, row_v), 0, 5, None,
                            ring_k, ring_v)
    # a ring keeps real positions alone; a slot's rows are the bucket's 16
    # (its pad positions masked by the length), then zeros
    for stack, beyond in ((cache.ring_k, 5), (cache.ring_v, 5),
                          (cache.k, 16), (cache.v, 16)):
        assert float(jnp.abs(stack[:, 0, beyond:]).max()) == 0.0
        assert float(jnp.abs(stack[:, 0, :5]).max()) > 0.0


# -- (iii) the held share --------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts the two shares give (experts 0-3 here, 4-7 on the
    other chip; no shared expert: nothing to count once) are the uncut
    reference's whole layer."""
    whole = dataclasses.replace(CFG, experts_held=None)
    sparse = T.init_params(whole, jax.random.key(6))["blocks"]["sparse"]
    y = jnp.asarray(np.random.default_rng(0).normal(size=(2, 7, CFG.hidden)),
                    jnp.float32)
    layer = 3
    small = {n: a[layer] for n, a in sparse.items()
             if n not in laguna.EXPERT_LEAVES}
    routing = laguna.sigmoid_router(CFG, y.reshape(14, -1), small)
    parts = []
    for first in (0, 4):
        share = dataclasses.replace(CFG, experts_held=(first, 4))
        stacks = {n: sparse[n][:, first:first + 4]
                  for n in laguna.EXPERT_LEAVES}
        out, load = T.moe_dropless(share, y, dict(small, **stacks), None,
                                   layer, routing)
        assert int(load.sum()) == 14 * 2  # the load is over all 8
        parts.append(out.reshape(14, -1))
    stacks = {n: sparse[n].reshape(-1, *sparse[n].shape[2:])
              for n in laguna.EXPERT_LEAVES}
    with jax.default_matmul_precision("highest"):
        w, chosen, _ = R.router_weights(
            y.reshape(14, -1), small, top_k=2, norm_topk_prob=True, scale=1.0)
        want = R.routed_part(y.reshape(14, -1), w, stacks, layer * 8, 0, 8)
    assert np.array_equal(np.sort(np.asarray(chosen)),
                          np.sort(np.asarray(routing[1])))
    np.testing.assert_allclose(parts[0] + parts[1], want, atol=2e-5)
    # and neither share alone is the layer
    assert float(jnp.abs(parts[0] - want).max()) > 1e-2
    assert float(jnp.abs(parts[1] - want).max()) > 1e-2


def test_the_reference_follows_a_tie_and_refuses_another_set():
    """`router_weights(follow=...)` by selection score (sigmoid + bias): the
    system's set is taken where its lowest expert lies within the margin of
    the reference's k-th; the weights are the scores WITHOUT the bias."""
    y = jnp.eye(3, dtype=jnp.float32)
    scores = jnp.asarray([[0.60, 0.50, 0.31, 0.30],   # 3rd and 4th: a tie
                          [0.60, 0.50, 0.40, 0.20],   # no tie
                          [0.60, 0.50, 0.40, 0.20]])
    small = {"router": jnp.log(scores / (1 - scores)),
             "router_bias": jnp.asarray([0.0, 0.0, 0.0, 0.005])}
    follow = jnp.asarray([[0, 1, 3], [0, 1, 3], [2, 1, 0]])
    w, chosen, gap = R.router_weights(
        y, small, top_k=3, norm_topk_prob=True, scale=1.0, follow=follow)
    assert sorted(chosen[0].tolist()) == [0, 1, 3]
    assert 0 < float(gap[0]) < 0.01
    assert sorted(chosen[1].tolist()) == [0, 1, 2] and float(gap[1]) == -1.0
    assert float(gap[2]) == 0.0
    np.testing.assert_allclose(
        w[0], np.array([0.60, 0.50, 0.0, 0.30]) / 1.40, rtol=1e-5)
    routes = R._routes(chosen[None], np.asarray(gap)[None])
    assert (routes["followed"], routes["refused"], routes["pairs"]) == (1, 1, 3)


# -- refusals ---------------------------------------------------------------------

@pytest.mark.parametrize("change, says", [
    (dict(layers=8), "names every one of the 8 layers"),
    (dict(layer_kinds=("window",) + CFG.layer_kinds[1:]),
     "the first a full one"),
    (dict(window_kv_heads=3), "whole groups of kv_heads"),
    (dict(value_dim=32), "value_dim is at most"),
    (dict(head_gate=True, window_sink=False, lead_kind="full"),
     "whole periods"),
    (dict(mla_latent=8), "mla_latent: no field of .*laguna"),
])
def test_the_configuration_is_validated(change, says):
    with pytest.raises(ValueError, match=says):
        dataclasses.replace(CFG, **change)


def test_each_refusal_names_the_pattern(params):
    from ray_tpu.models.disagg_prefill import DisaggPrefillEngine
    from ray_tpu.models.paged_kv import PagedBatcher

    with pytest.raises(ValueError, match="ring_k, ring_v.*pages hold no ring"):
        PagedBatcher(CFG, params, max_len=64, slots=2, page_size=16)
    with pytest.raises(ValueError, match="ring_k, ring_v.*KV channel"):
        DisaggPrefillEngine(CFG, params, max_len=64)
    with pytest.raises(ValueError, match="layer pattern.*cached forward"):
        T.forward(CFG, params, jnp.zeros((1, 8), jnp.int32))
