"""LLM library tests (reference: python/ray/llm tests): KV-cache decode
correctness vs the full forward, batched generation, Data batch
inference, and the Serve deployment (batched + streaming)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import ray_tpu

from ray_tpu.models import transformer as T
from ray_tpu.models.decoding import (
    Generator, KVCache, SamplingParams, _attend_cached, forward_cached,
    init_cache,
)
from ray_tpu.ops.attention import NEG_INF


def _tiny_cfg():
    # fp32 so the cached and uncached paths argmax identically
    return T.config("debug", dtype=jnp.float32, param_dtype=jnp.float32)


def _attend_reference(q, k_cache, v_cache, q_pos, kv_len_mask):
    """Plain float32 attention over the cache: K/V repeated per query head
    and upcast whole, which `_attend_cached` itself must never do."""
    rep = q.shape[2] // k_cache.shape[2]
    k = jnp.repeat(k_cache, rep, axis=2).astype(jnp.float32)
    v = jnp.repeat(v_cache, rep, axis=2).astype(jnp.float32)
    logits = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32),
                        k) / q.shape[-1] ** 0.5
    key_pos = jnp.arange(k.shape[1])
    mask = (kv_len_mask[:, None, None, :]
            & (q_pos[:, None, :, None] >= key_pos[None, None, None, :]))
    probs = jax.nn.softmax(jnp.where(mask, logits, NEG_INF), axis=-1)
    return jnp.einsum("bhst,bthd->bshd", probs, v).astype(q.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s", [1, 7])
@pytest.mark.parametrize("rep", [1, 4])
def test_attend_cached_matches_plain_reference(rep, s, dtype):
    """Grouped-query attention on the cache's own dtype against the plain
    reference on the same values; rows 1 and 2 are ragged, row 2 holds a
    single valid slot."""
    b, t, kvh, d = 3, 24, 2, 16
    ks = jax.random.split(jax.random.key(rep * 10 + s), 3)
    q = jax.random.normal(ks[0], (b, s, kvh * rep, d), jnp.float32)
    k_cache = jax.random.normal(ks[1], (b, t, kvh, d), jnp.float32)
    v_cache = jax.random.normal(ks[2], (b, t, kvh, d), jnp.float32)
    q, k_cache, v_cache = (a.astype(dtype) for a in (q, k_cache, v_cache))
    lengths = jnp.asarray([t, 9, 1])
    kv_len_mask = jnp.arange(t)[None, :] < lengths[:, None]
    # the queries are the newest s positions (all of them at 0 in row 2)
    q_pos = jnp.maximum(lengths[:, None] - s + jnp.arange(s)[None, :], 0)

    got = jax.jit(_attend_cached)(q, k_cache, v_cache, q_pos, kv_len_mask)
    want = _attend_reference(q, k_cache, v_cache, q_pos, kv_len_mask)
    assert got.shape == q.shape and got.dtype == q.dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, atol=1e-5)
    else:
        # both round their float32 result to bf16 once; a sum taken in
        # another order may tip a value over to its neighbour (2**-8)
        assert np.linalg.norm(got - want) <= 1e-3 * np.linalg.norm(want)
        np.testing.assert_allclose(got, want, rtol=2.0 ** -7)
    # row 2 sees one key: every query returns that key's value, per group
    only_value = np.repeat(np.asarray(v_cache[2, 0], np.float32), rep, axis=0)
    np.testing.assert_allclose(
        got[2], np.broadcast_to(only_value, got[2].shape), atol=1e-6)


@pytest.fixture
def kernels_through_the_interpreter(monkeypatch):
    """The chip's path on the CPU: `_on_tpu` says yes (steered here, not by
    an option of the program) and every Pallas call runs interpreted. Blocks
    of 16 rows, so that a toy slot has several, and no scores small enough to
    stay with `_attend_cached`, so that a toy bucket goes a long prompt's
    way."""
    import functools

    import jax.experimental.pallas as pl

    from ray_tpu.ops import attention as A

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
        np.testing.assert_allclose(got, want, atol=1e-5)
    else:  # as for `_attend_cached`: one rounding to bf16, another order
        assert np.linalg.norm(got - want) <= 4e-3 * np.linalg.norm(want)
        np.testing.assert_allclose(got, want, rtol=2.0 ** -6, atol=2.0 ** -8)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("heads,kv_heads", [
    (32, 8), (16, 16), (8, 2), (48, 8), (72, 8)])
def test_decode_attention_reads_the_rows_held_and_no_others(
        kernels_through_the_interpreter, heads, kv_heads, dtype):
    """The kernel against `_attend_cached` on the same stack, for the five
    head layouts of the serve configurations (groups of 4, 1, 4, 6 and 9),
    a layer that is not the first, and slots that hold no row, one, a
    block, a block and one, every row. Whatever lies beyond a slot's rows
    (stale, or another's) is NaN here and must not reach the output; a slot
    without rows gets zeros."""
    A = kernels_through_the_interpreter
    n, t, d, block, layer = 3, 64, 128, 16, 2
    rows = jnp.asarray([0, 1, block, block + 1, t, 0, 37], jnp.int32)
    b = rows.shape[0]
    ks = jax.random.split(jax.random.key(heads), 3)
    q = jax.random.normal(ks[0], (b, heads, d), jnp.float32).astype(dtype)
    k, v = (jax.random.normal(key, (n, b, t, kv_heads, d), jnp.float32
                              ).astype(dtype) for key in ks[1:])
    held = jnp.arange(t)[None, :] < rows[:, None]
    want = _attend_cached(q[:, None], k[layer], v[layer],
                          jnp.full((b, 1), t), held)[:, 0]
    stale = ~held[None, :, :, None, None]
    got = jax.jit(A.decode_attention)(
        q, jnp.where(stale, jnp.nan, k), jnp.where(stale, jnp.nan, v),
        jnp.int32(layer), rows)
    assert got.shape == q.shape and got.dtype == q.dtype
    live = np.asarray(rows) > 0
    _close(got[live], want[live], dtype)
    assert not np.asarray(got[~live], np.float32).any()
    assert A.decode_block(t, kv_heads * d * q.dtype.itemsize) == block


# heads, KV heads, pieces of a key (keys of 256 beside values of 128: 2), a sink
TAIL_LAYOUTS = {
    "group-of-4-on-8": (32, 8, 1, False), "group-of-1-on-16": (16, 16, 1, False),
    "group-of-4-on-2": (8, 2, 1, False), "group-of-6-on-8": (48, 8, 1, False),
    "group-of-9-on-8": (72, 8, 1, False), "one-kv-head": (20, 1, 1, False),
    "keys-in-two-pieces": (8, 4, 2, False),
    "keys-in-two-pieces-and-a-sink": (16, 8, 2, True)}


@pytest.mark.parametrize("layout,dtype", [
    (layout, dtype) for layout in sorted(TAIL_LAYOUTS)
    for dtype in (jnp.float32, jnp.bfloat16)
    # ONE KV head's layout is a 2-byte stack's
    if (layout, dtype) != ("one-kv-head", jnp.float32)])
def test_decode_attention_copies_and_multiplies_a_last_block_by_its_rows(
        kernels_through_the_interpreter, monkeypatch, layout, dtype):
    """Blocks of 64 rows, products in pieces of 32, copies in granules of
    16: a slot's LAST block is copied as the granules it holds (the binary
    pieces of that count) and multiplied as the whole pieces that hold
    rows. Slots on every edge of that walk (no row, 1, a granule - 1, a
    granule, + 1, a piece - 1, a piece, + 1, a block - 1, a block, + 1,
    t - 1, t), every head layout of the serve configurations, ONE KV head,
    keys in pieces, a sink: against `_attend_cached` on the same stack.
    What lies beyond a slot's rows is NaN in the stack, and what a buffer
    keeps from the block before is the other slots' rows: neither reaches
    the output."""
    A = kernels_through_the_interpreter
    heads, kv_heads, pieces, sink = TAIL_LAYOUTS[layout]
    block, piece = 64, 32
    monkeypatch.setattr(A, "DECODE_BLOCK_ROWS", block)
    monkeypatch.setattr(A, "DECODE_THIN_BLOCK_ROWS", block)
    monkeypatch.setattr(A, "DECODE_SUB_ROWS", piece)
    n, t, d, dv, layer = 2, 192, 128 * pieces, 128, 1
    assert A.decode_block(
        t, kv_heads * d * jnp.dtype(dtype).itemsize) == block
    g = A.decode_granule(block)
    assert g == 16
    rows = jnp.asarray([0, 1, g - 1, g, g + 1, piece - 1, piece, piece + 1,
                        block - 1, block, block + 1, 2 * block + g + 3,
                        t - 1, t], jnp.int32)
    b = rows.shape[0]
    ks = jax.random.split(jax.random.key(heads), 4)
    q = jax.random.normal(ks[0], (b, heads, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (n, b, t, kv_heads, d), jnp.float32
                          ).astype(dtype)
    v = jax.random.normal(ks[2], (n, b, t, kv_heads, dv), jnp.float32
                          ).astype(dtype)
    s = jax.random.normal(ks[3], (heads,), jnp.float32) if sink else None
    held = jnp.arange(t)[None, :] < rows[:, None]
    want = _attend_cached(q[:, None], k[layer], v[layer],
                          jnp.full((b, 1), t), held, s)[:, 0]
    stale = ~held[None, :, :, None, None]
    stack = A.key_pieces(jnp.where(stale, jnp.nan, k), dv)
    assert A.decode_attention_takes(stack, v)
    got = jax.jit(A.decode_attention)(
        q, stack, jnp.where(stale, jnp.nan, v), jnp.int32(layer), rows,
        sink=s)
    assert got.shape == (b, heads, dv) and got.dtype == q.dtype
    live = np.asarray(rows) > 0
    _close(got[live], want[live], dtype)
    assert not np.asarray(got[~live], np.float32).any()


@pytest.mark.parametrize("t,row_bytes,granule", [
    (512, 4096, 16), (2048, 512, 16), (10240, 2048, 16), (12288, 256, 16),
    (24, 1024, 8), (40, 4096, 8)])
def test_decode_attention_copies_whole_granules_and_no_block(
        t, row_bytes, granule):
    """What `_kv_rows` books and the kernel's wrapper rounds by is ONE
    function: a slot's rows rounded up to the granule of its blocks (16
    rows, 8 where a slot is not whole 16s), so no slot costs more than its
    rows and a granule less one, whatever block they end in."""
    from ray_tpu.ops import attention as A

    block = A.decode_block(t, row_bytes)
    g = A.decode_granule(block)
    assert g == granule and block % g == 0 and t % block == 0
    rows = np.array([0, 1, g - 1, g, g + 1, block - 1, block, block + 1,
                     t // 2 - 1, t - g - 1, t - 1, t])
    rows = rows[(rows >= 0) & (rows <= t)]
    copied = A.decode_rows_copied(rows, t, row_bytes)
    assert copied.sum() == sum(-(-r // g) * g for r in rows.tolist())
    assert (copied >= rows).all() and (copied <= rows + g - 1).all()
    assert (copied <= t).all() and copied[0] == 0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_over_a_ring(kernels_through_the_interpreter, dtype):
    """A window layer's ring: position p at row p mod window. The rows held
    are stated as `min(pos + 1, window)`, a prefix, for sequences below, at
    and past the window; against the mask of the rows that hold a position
    (`laguna._ring_positions`), with NaN in the rows that hold none."""
    from ray_tpu.models.laguna import _ring_positions

    A = kernels_through_the_interpreter
    n, w, kvh, h, d, layer = 2, 32, 2, 18, 128, 1
    pos = jnp.asarray([0, 5, w - 2, w - 1, w, 3 * w + 7], jnp.int32)
    b = pos.shape[0]
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (b, h, d), jnp.float32).astype(dtype)
    k, v = (jax.random.normal(key, (n, b, w, kvh, d), jnp.float32
                              ).astype(dtype) for key in ks[1:])
    holds = _ring_positions(pos, w) >= 0
    want = _attend_cached(q[:, None], k[layer], v[layer],
                          jnp.full((b, 1), w), holds)[:, 0]
    empty = ~holds[None, :, :, None, None]
    got = jax.jit(A.decode_attention)(
        q, jnp.where(empty, jnp.nan, k), jnp.where(empty, jnp.nan, v),
        jnp.int32(layer), jnp.minimum(pos + 1, w))
    _close(got, want, dtype)


@pytest.mark.parametrize("name", ["debug", "zaya_debug", "laguna_debug"])
def test_the_decode_step_with_the_kernel_gives_the_xla_steps_tokens(
        kernels_through_the_interpreter, monkeypatch, name):
    """One `ContinuousBatcher` a cache kind (plain slots, slots and a state,
    slots and a ring): requests join and leave over a dozen steps, the pump's
    passes made by hand. With the kernel in the step (S == 1 over a stack
    decides, nothing else) the tokens are the XLA step's, and the counters
    say exactly what the steps held and read: a request of n prompt tokens
    and m answers runs m - 1 steps at n + 1 .. n + m - 1 rows."""
    from concurrent.futures import Future

    from ray_tpu.models.continuous_batching import (
        ContinuousBatcher, _Request)

    A = kernels_through_the_interpreter
    cfg = T.config(name, dtype=jnp.float32, param_dtype=jnp.float32,
                   head_dim=128)  # whole lanes: `decode_attention_takes`
    params = T.init_params(cfg, jax.random.key(2))
    slots, max_len = 3, 64
    rng = np.random.default_rng(7)
    arrivals = [(0, 5, 13), (0, 17, 4), (2, 30, 7), (5, 3, 6), (8, 11, 5)]
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist()
               for _, n, _ in arrivals]

    def serve():
        cb = ContinuousBatcher(cfg, params, max_len=max_len, slots=slots)
        cb.shutdown()  # the pump is gone: the passes below are the test's
        reqs = [_Request(list(p), SamplingParams(max_tokens=m), Future(), None)
                for p, (_, _, m) in zip(prompts, arrivals)]
        todo = sorted((at, i) for i, (at, _, _) in enumerate(arrivals))
        for _ in range(100):
            while todo and todo[0][0] <= cb.stats["steps"]:
                cb._waiting.put(reqs[todo.pop(0)[1]])
            cb._step()
            if not todo and all(r.future.done() for r in reqs):
                break
        return [r.future.result(timeout=0) for r in reqs], cb.stats

    traced, real = [], A.decode_attention  # one call a layer (kind) traced
    monkeypatch.setattr(A, "decode_attention", lambda *a, **kw: (
        traced.append(a[1].shape[2]), real(*a, **kw))[1])
    got, stats = serve()
    assert set(traced) == {max_len, cfg.window} - {0}
    monkeypatch.setattr(A, "_on_tpu", lambda: False)
    del traced[:]
    want, _ = serve()
    assert traced == [] and got == want
    assert [len(g) for g in got] == [m for _, _, m in arrivals]
    assert stats["steps"] >= 12 and stats["max_active"] == slots

    def rows_of(t, granule):  # (held, read) over every step of every request
        lens = np.concatenate([np.minimum(np.arange(n + 1, n + m), t)
                               for _, n, m in arrivals])
        return np.array([lens.sum(), (-(-lens // granule) * granule).sum()])

    total = cfg.full_layers * rows_of(max_len, 16)
    if cfg.window_layers:  # a ring of 16 rows is one granule
        total += cfg.window_layers * rows_of(
            cfg.window, A.decode_granule(cfg.window))
    assert (stats["kv_rows_held"], stats["kv_rows_read"]) == tuple(total)
    assert stats["kv_rows_read"] < stats["steps"] * slots * (
        cfg.full_layers * max_len + cfg.window_layers * cfg.window)


def _prefill_qkv(s, heads, kv_heads, dtype=jnp.bfloat16, d=128, seed=0):
    ks = jax.random.split(jax.random.key(seed + s + heads), 3)
    return [jax.random.normal(key, (1, s, n, d), jnp.float32).astype(dtype)
            for key, n in zip(ks, (heads, kv_heads, kv_heads))]


@pytest.mark.parametrize("bucket,length", [(128, 77), (256, 150)])
@pytest.mark.parametrize("heads,kv_heads", [(32, 8), (16, 16), (8, 2)])
def test_a_prefill_from_position_0_attends_with_the_flash_kernel(
        kernels_through_the_interpreter, heads, kv_heads, bucket, length):
    """The fresh rows of a prefill from position 0 (`FreshRows`, what
    `_write_stack` hands over for S rows into a cache of S rows) through
    `attend_held` on the chip's path: the flash forward kernel over the
    expanded KV heads under the causal rule alone, against `_attend_cached`
    under the causal rule AND the length's mask, on the REAL rows, for the
    head layouts of the one block's cells and ZAYA1 (groups of 4, 1 and 4)
    and a length inside the bucket. A pad row sees other keys there than
    here and is nobody's to read."""
    from ray_tpu.models import decoding as D

    q, k, v = _prefill_qkv(bucket, heads, kv_heads)
    pos = jnp.arange(bucket)[None]
    mask = pos < length
    with D.fresh_rows_attended() as seen:
        got = jax.jit(D.attend_held)(q, D.FreshRows(k, v), pos, mask)
    assert seen == {"flash"}
    want = _attend_cached(q, k, v, pos, mask)
    assert got.shape == want.shape and got.dtype == want.dtype
    _close(got[:, :length], want[:, :length], jnp.bfloat16)


@pytest.mark.parametrize("held", [
    "a-stack-layer", "gathered-rows", "one-token", "a-narrow-head",
    "a-short-bucket", "another-dtype"])
def test_attend_held_keeps_attend_cached(
        kernels_through_the_interpreter, monkeypatch, held):
    """On the chip's path too everything but a prefill from position 0 at a
    shape the kernel takes is `_attend_cached`, bit for bit: a prefill into a
    longer cache (a `StackLayer` with S > 1), `PagedBatcher`'s gathered
    dense rows, one token, and fresh rows the predicate refuses (a head of
    64, a bucket of 64 whose row statistics the chip's compiler refuses, q
    and k of two dtypes), which book "dense"."""
    from ray_tpu.models import decoding as D

    A = kernels_through_the_interpreter

    def no_kernel(*a, **kw):
        raise AssertionError("the flash kernel was called")

    monkeypatch.setattr(A, "flash_attention", no_kernel)
    s, t, d = {"one-token": (1, 1, 128), "a-narrow-head": (128, 128, 64),
               "a-short-bucket": (64, 64, 128),
               "a-stack-layer": (128, 256, 128)}.get(held, (128, 128, 128))
    q, _, _ = _prefill_qkv(s, 8, 2, d=d)
    _, k, v = _prefill_qkv(t, 8, 2, d=d)
    pos = jnp.arange(s)[None]
    mask = jnp.arange(t)[None] < max(s - 11, 1)
    fresh = held not in ("a-stack-layer", "gathered-rows")
    if held == "another-dtype":
        q = q.astype(jnp.float32)
    arg = D.FreshRows(k, v) if fresh else (k, v)
    if held == "a-stack-layer":
        arg = D.StackLayer(jnp.stack([k, k + 1]), jnp.stack([v, v + 1]),
                           jnp.int32(0))
    with D.fresh_rows_attended() as seen:
        got = jax.jit(D.attend_held)(q, arg, pos, mask)
    assert seen == ({"dense"} if fresh else set())
    want = jax.jit(_attend_cached)(q, k, v, pos, mask)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_flash_attention_takes_what_fits_and_what_it_can_save(monkeypatch):
    """The predicate beside the kernel, by what it sees of a call. What the
    kernel can run: whole lanes, whole 128s of positions (not 16 to 64),
    8,192 keys of bf16 at most (K and V of a head, two buffers each, inside
    `FLASH_KV_VMEM_BYTES`) or 4,096 of float32, one length, one dtype, a
    TPU. And what it can save: float32 scores [B, H, S, S] past the 112 MiB
    up to which XLA keeps them in fast memory: the cells' 2,048 buckets over
    32, 16 and 48 heads and 8 heads from 2,048 on, not ZAYA1's 1,024 bucket
    (32 MiB) nor the chat cell's 128 to 512 over 32 heads (2 to 32 MiB)."""
    from ray_tpu.ops import attention as A

    monkeypatch.setattr(A, "_on_tpu", lambda: True)

    def takes(s, h=32, d=128, dtype=jnp.bfloat16, sk=None, kdtype=None, b=1):
        q = jax.ShapeDtypeStruct((b, s, h, d), dtype)
        k = jax.ShapeDtypeStruct((b, sk or s, 2, d), kdtype or dtype)
        return A.flash_attention_takes(q, k)

    assert all(takes(s) for s in (1024, 1536, 2048, 4096, 8192))
    assert takes(2048, h=16) and takes(2048, h=48) and takes(2048, h=8)
    assert not any(takes(s) for s in (128, 256, 512, 768))
    assert not takes(1024, h=8) and not takes(1024, h=24)
    assert takes(512, b=4) and takes(1024, h=8, b=4)
    assert not takes(16384)
    assert takes(4096, dtype=jnp.float32) and takes(2048, d=256)
    assert not takes(8192, dtype=jnp.float32) and not takes(8192, d=256)
    monkeypatch.setattr(A, "DENSE_SCORES_BYTES", 0)
    assert all(takes(s, h=8) for s in (128, 256, 384, 512, 1024))
    assert not any(takes(s) for s in (1, 16, 32, 64, 200, 16384))
    assert not takes(2048, d=64) and not takes(2048, d=192)
    assert not takes(2048, sk=4096)
    assert not takes(2048, kdtype=jnp.float32)
    assert not takes(2048, dtype=jnp.float16, kdtype=jnp.bfloat16)
    monkeypatch.setattr(A, "_on_tpu", lambda: False)
    assert not takes(2048)


@pytest.mark.parametrize("name", ["debug", "zaya_debug", "laguna_debug"])
def test_the_engine_says_what_each_prefill_attended_with(
        kernels_through_the_interpreter, monkeypatch, name):
    """`attend_held`'s three callers (the one block, ZAYA1's `cca.attend`,
    Laguna's full layers) through `ContinuousBatcher`, prompts in three
    buckets: on the chip's path the 128 and 256 buckets' fresh rows take the
    flash kernel and the 16 bucket's keep `_attend_cached` (what decides is
    the shape, no family's name), `prefill_attention_path` names a path for
    every compiled bucket, and the greedy tokens are the ones the same
    engine gives off a TPU, where every bucket reads "dense"."""
    from ray_tpu.models.continuous_batching import ContinuousBatcher

    A = kernels_through_the_interpreter
    cfg = T.config(name, dtype=jnp.float32, param_dtype=jnp.float32,
                   head_dim=128)
    params = T.init_params(cfg, jax.random.key(4))
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist()
               for n in (9, 100, 190)]

    def served():
        cb = ContinuousBatcher(cfg, params, max_len=256, slots=2)
        try:
            return [cb.submit(p, SamplingParams(max_tokens=5)
                              ).result(timeout=600) for p in prompts], \
                cb.prefill_attention_path
        finally:
            cb.shutdown()

    got, paths = served()
    assert paths == {"prefill_16": "dense", "prefill_128": "flash",
                     "prefill_256": "flash"}
    monkeypatch.setattr(A, "_on_tpu", lambda: False)
    want, paths = served()
    assert paths == dict.fromkeys(
        ("prefill_16", "prefill_128", "prefill_256"), "dense")
    assert got == want and [len(g) for g in got] == [5, 5, 5]


def _forward_cached_plainly(cfg, params, tokens, positions, cache,
                            kv_len_mask):
    """`forward_cached` of a dense model the plain way: a Python loop that
    takes each layer's cache OUT of the stack, puts the fresh rows into
    that copy, attends against it with the plain reference, and stacks the
    copies again. What the layer scan must equal without ever doing it."""
    bidx = jnp.arange(tokens.shape[0])[:, None]
    x = params["embed"][tokens]
    new_k, new_v = [], []
    for i in range(cfg.layers):
        p = jax.tree.map(lambda a: a[i], params["blocks"])
        y = T._rms_norm(x, p["ln_attn"], cfg.norm_eps)
        q, k, v = (jnp.einsum("bsh,hnd->bsnd", y, p[w])
                   for w in ("wq", "wk", "wv"))
        q = T._rope(q, positions, cfg.rope_theta)
        k = T._rope(k, positions, cfg.rope_theta)
        new_k.append(cache.k[i].at[bidx, positions].set(k))
        new_v.append(cache.v[i].at[bidx, positions].set(v))
        attn = _attend_reference(q, new_k[-1], new_v[-1], positions,
                                 kv_len_mask)
        x = x + jnp.einsum("bsnd,ndh->bsh", attn, p["wo"])
        y = T._rms_norm(x, p["ln_mlp"], cfg.norm_eps)
        x = x + (jax.nn.silu(y @ p["wi_gate"]) * (y @ p["wi_up"])) \
            @ p["wo_mlp"]
    x = T._rms_norm(x, params["ln_f"], cfg.norm_eps)
    unembed = params.get("unembed")
    if unembed is None:
        unembed = params["embed"].T
    return x @ unembed, KVCache(jnp.stack(new_k), jnp.stack(new_v),
                                cache.lengths)


@pytest.mark.parametrize("heads,kv_heads", [(8, 2), (4, 4)])
def test_carried_cache_matches_a_plain_layer_loop(heads, kv_heads):
    """Prefill and 8 decode steps through `forward_cached`, whose layer scan
    carries the cache and writes rows into it in place (jitted with the
    cache donated, as the engines run it), against the plain per-layer
    loop: same logits, same cache contents, for a KV group of 4 and of 1.
    Ragged prompts; slot 2 is a free slot of a continuous batch: empty, it
    computes and writes at position 0 every step and never advances."""
    cfg = T.config("debug", dtype=jnp.float32, param_dtype=jnp.float32,
                   layers=3, heads=heads, kv_heads=kv_heads)
    params = T.init_params(cfg, jax.random.key(1))
    slots, max_len, s = 3, 32, 12
    rng = np.random.default_rng(heads)
    lengths = jnp.asarray([12, 5, 0], jnp.int32)
    active = lengths > 0
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (slots, s)),
                         jnp.int32)
    step = jax.jit(
        lambda tokens, positions, cache, kv_mask: forward_cached(
            cfg, params, tokens, positions, cache, kv_mask,
            jnp.ones(tokens.shape, bool))[:2],
        donate_argnums=(2,))

    def both(tokens, positions, kv_mask, got_cache, want_cache):
        want, want_cache = _forward_cached_plainly(
            cfg, params, tokens, positions, want_cache, kv_mask)
        got, got_cache = step(tokens, positions, got_cache, kv_mask)
        np.testing.assert_allclose(got, want, atol=2e-5)
        np.testing.assert_allclose(got_cache.k, want_cache.k, atol=1e-5)
        np.testing.assert_allclose(got_cache.v, want_cache.v, atol=1e-5)
        return got, got_cache, want_cache

    positions = jnp.arange(s)[None, :].repeat(slots, 0)
    kv_mask = jnp.arange(max_len)[None, :] < lengths[:, None]
    logits, got_cache, want_cache = both(
        tokens, positions, kv_mask, init_cache(cfg, slots, max_len),
        init_cache(cfg, slots, max_len))
    assert float(jnp.abs(want_cache.k[:, 0, :12]).min()) > 0  # rows written
    assert not want_cache.k[:, :, 12:].any()  # and no others
    tok = jnp.argmax(logits[jnp.arange(slots), jnp.maximum(lengths - 1, 0)],
                     axis=-1).astype(jnp.int32)
    for _ in range(8):
        kv_mask = jnp.arange(max_len)[None, :] <= lengths[:, None]
        logits, got_cache, want_cache = both(
            tok[:, None], lengths[:, None], kv_mask, got_cache, want_cache)
        tok = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
        lengths = jnp.where(active, lengths + 1, lengths)
    assert lengths.tolist() == [20, 13, 0]
    # each step wrote one row per slot and layer, the free slot's at 0
    # every time (the prefill's 12 rows are all it holds)
    assert bool(want_cache.k[:, 0, 19].any()) and \
        not want_cache.k[:, 0, 20:].any()
    assert not want_cache.k[:, 2, 12:].any()
    # a batcher's prefill: the prompt's bucket into a row cache of the
    # bucket's length, every row of which it writes (no scatter at all)
    both(tokens[:1], positions[:1], jnp.arange(s)[None, :] < 9,
         init_cache(cfg, 1, s), init_cache(cfg, 1, s))


@pytest.fixture(scope="module")
def tiny_model():
    cfg = _tiny_cfg()
    params = T.init_params(cfg, jax.random.key(0))
    return cfg, params


class TestKVCacheDecoding:
    def test_greedy_matches_full_forward(self, tiny_model):
        """Greedy decode through the KV cache must equal greedy decode
        re-running the full forward at every step."""
        cfg, params = tiny_model
        prompt = [5, 17, 3, 101, 42]
        n_new = 12

        # reference: recompute the whole sequence each step
        toks = list(prompt)
        ref = []
        for _ in range(n_new):
            logits = T.forward(cfg, params, jnp.asarray([toks], jnp.int32))
            nxt = int(jnp.argmax(logits[0, -1]))
            ref.append(nxt)
            toks.append(nxt)

        gen = Generator(cfg, params, max_len=64)
        out = gen.generate([prompt], SamplingParams(max_tokens=n_new))
        assert out[0] == ref

    def test_ragged_batch_matches_single(self, tiny_model):
        """Right-padded ragged prompts must decode exactly like each
        prompt alone (padding never leaks into attention)."""
        cfg, params = tiny_model
        gen = Generator(cfg, params, max_len=64)
        p1, p2 = [7, 9, 11], [100, 2, 3, 4, 5, 6, 88]
        sp = SamplingParams(max_tokens=8)
        batch = gen.generate([p1, p2], sp)
        solo1 = gen.generate([p1], sp)
        solo2 = gen.generate([p2], sp)
        assert batch[0] == solo1[0]
        assert batch[1] == solo2[0]

    def test_stream_matches_generate(self, tiny_model):
        cfg, params = tiny_model
        gen = Generator(cfg, params, max_len=64)
        prompt = [1, 2, 3]
        sp = SamplingParams(max_tokens=10)
        full = gen.generate([prompt], sp)[0]
        streamed = list(gen.generate_stream(prompt, sp))
        assert streamed == full

    def test_stop_token_halts(self, tiny_model):
        cfg, params = tiny_model
        gen = Generator(cfg, params, max_len=64)
        prompt = [1, 2, 3]
        free = gen.generate([prompt], SamplingParams(max_tokens=10))[0]
        stop = free[3]  # force a stop at the 4th emitted token
        out = gen.generate(
            [prompt], SamplingParams(max_tokens=10, stop_token_id=stop))[0]
        assert out == free[:3]

    def test_temperature_sampling_valid_ids(self, tiny_model):
        cfg, params = tiny_model
        gen = Generator(cfg, params, max_len=64)
        out = gen.generate(
            [[1, 2]], SamplingParams(max_tokens=12, temperature=1.0,
                                     top_k=20))[0]
        assert len(out) == 12
        assert all(0 <= t < cfg.vocab_size for t in out)


class TestEngine:
    def test_text_roundtrip_byte_tokenizer(self):
        from ray_tpu.llm import LLMConfig, LLMEngine

        cfg = LLMConfig(model="debug", max_len=64,
                        sampling=SamplingParams(max_tokens=6))
        eng = LLMEngine(cfg)
        outs = eng.generate(["hi", "hello there"])
        assert len(outs) == 2
        assert all(isinstance(o, str) for o in outs)
        # vocab was widened to cover the byte tokenizer's 257 ids
        assert eng.model_config.vocab_size >= 257


class TestBatchInference:
    def test_processor_over_dataset(self, ray_start_regular):
        import ray_tpu.data as data
        from ray_tpu.llm import LLMConfig, build_llm_processor

        cfg = LLMConfig(model="debug", max_len=64,
                        sampling=SamplingParams(max_tokens=4))
        process = build_llm_processor(cfg, prompt_column="prompt",
                                      output_column="generated")
        ds = data.from_items([{"prompt": f"msg {i}"} for i in range(6)])
        rows = process(ds).take_all()
        assert len(rows) == 6
        assert all(isinstance(r["generated"], str) for r in rows)
        assert all(r["prompt"].startswith("msg") for r in rows)


class TestServing:
    def test_deploy_call_and_stream(self, ray_start_regular):
        from ray_tpu import serve
        from ray_tpu.llm import LLMConfig, serve_llm

        cfg = LLMConfig(model="debug", max_len=64, name="llm-test",
                        sampling=SamplingParams(max_tokens=5),
                        batch_wait_timeout_s=0.01)
        handle = serve_llm(cfg)
        try:
            r1 = handle.remote("abc").result()
            assert isinstance(r1, str)
            # concurrent calls exercise the batched path
            rs = [handle.remote(f"p{i}") for i in range(4)]
            outs = [r.result() for r in rs]
            assert len(outs) == 4
            # streaming: text deltas arrive incrementally
            gen = handle.generate_stream.remote("abc")
            pieces = [ray_tpu.get(r, timeout=60) for r in gen]
            assert "".join(pieces) == r1
        finally:
            serve.shutdown()
