"""Tests for ray_tpu.ops attention kernels (CPU, virtual 8-device mesh)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P
from jax import shard_map

from ray_tpu.ops import blockwise_attention, flash_attention, gqa_expand, mha_reference
from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.parallel import MeshSpec, build_mesh


def _qkv(key, b=2, s=128, h=4, hkv=None, d=32, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d), dtype)
    k = jax.random.normal(kk, (b, s, hkv or h, d), dtype)
    v = jax.random.normal(kv, (b, s, hkv or h, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_blockwise_matches_reference(causal):
    q, k, v = _qkv(jax.random.PRNGKey(0))
    ref = mha_reference(q, k, v, causal=causal)
    out = blockwise_attention(q, k, v, causal=causal, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_blockwise_grads_match():
    q, k, v = _qkv(jax.random.PRNGKey(1), s=64)

    def loss_ref(q, k, v):
        return mha_reference(q, k, v).sum()

    def loss_blk(q, k, v):
        return blockwise_attention(q, k, v, block_k=16).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_blk = jax.grad(loss_blk, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_blk):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def test_flash_attention_fallback_and_grad():
    q, k, v = _qkv(jax.random.PRNGKey(2), s=64)
    ref = mha_reference(q, k, v)
    out = flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    g = jax.grad(lambda q: flash_attention(q, k, v).sum())(q)
    g_ref = jax.grad(lambda q: mha_reference(q, k, v).sum())(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=5e-5)


# (sq, sk, block_q, block_k, causal): the shapes of the kernels' interpret
# test (tests/test_chip_compile.py) and the train cells' own
FLASH_PLAN_CASES = [
    (128, 128, 64, 64, True), (128, 128, 64, 64, False),
    (100, 100, 64, 64, True), (72, 136, 64, 64, False),
    (256, 256, 64, 64, True), (200, 200, 64, 64, True),
    (320, 192, 64, 64, True), (192, 320, 64, 64, True),
    (136, 72, 64, 64, True), (72, 136, 64, 64, True), (136, 72, 64, 64, False),
    (256, 256, 64, 128, True), (256, 256, 128, 64, True),
    (200, 136, 32, 64, True), (40, 40, 64, 64, True),
    (2048, 2048, 512, 512, True), (2048, 2048, 256, 512, True),
]


def _brute_force_blocks(sq, sk, block_q, block_k, causal):
    """{(q block, k block): "bare" | "masked"} of the blocks with any
    allowed pair, by the [sq, sk] mask itself: masked = any pair of the
    block disallowed or padding."""
    nq, nk = -(-sq // block_q), -(-sk // block_k)
    allowed = np.zeros((nq * block_q, nk * block_k), bool)
    qi, ki = np.arange(sq)[:, None], np.arange(sk)[None, :]
    allowed[:sq, :sk] = (qi >= ki) if causal else True
    kinds = {}
    for j in range(nq):
        for i in range(nk):
            block = allowed[j * block_q:(j + 1) * block_q,
                            i * block_k:(i + 1) * block_k]
            if block.any():
                kinds[j, i] = "bare" if block.all() else "masked"
    return nq, nk, kinds


@pytest.mark.parametrize("sq,sk,block_q,block_k,causal", FLASH_PLAN_CASES)
def test_flash_block_plan_counts_what_the_mask_says(sq, sk, block_q, block_k,
                                                    causal):
    """`flash_block_plan` against a brute-force count over the mask, and the
    loop bounds both kinds of kernel program take (`_k_blocks` a q block,
    `_q_blocks` a k block) against the same blocks: one definition."""
    from ray_tpu.ops import attention as A

    plan = A.flash_block_plan(sq, sk, block_q, block_k, causal)
    block_q, block_k = A._flash_blocks(sq, sk, block_q, block_k)
    nq, nk, kinds = _brute_force_blocks(sq, sk, block_q, block_k, causal)
    assert plan == (nq * nk, len(kinds),
                    sum(kind == "masked" for kind in kinds.values()))
    by_q_block, by_k_block = {}, {}
    for j in range(nq):
        bare, end = A._k_blocks(j, sq, sk, block_q, block_k, causal)
        assert 0 <= bare <= end <= nk
        by_q_block.update({(j, i): "bare" for i in range(bare)})
        by_q_block.update({(j, i): "masked" for i in range(bare, end)})
    for i in range(nk):
        start, lo, hi, end = A._q_blocks(i, sq, sk, block_q, block_k, causal)
        assert 0 <= start <= lo <= hi <= end == nq
        by_k_block.update({(j, i): "masked" for j in range(start, lo)})
        by_k_block.update({(j, i): "bare" for j in range(lo, hi)})
        by_k_block.update({(j, i): "masked" for j in range(hi, nq)})
    assert by_q_block == kinds
    assert by_k_block == kinds


def test_flash_block_plan_of_the_train_cells():
    """Causal 2,048 x 2,048 at the default blocks: 10 of 16 blocks visited,
    4 of them masked; the parent's (256, 512) masked all 20 it visited."""
    from ray_tpu.ops import attention as A

    assert A.flash_block_plan(2048, 2048) == (16, 10, 4)
    assert A.flash_block_plan(2048, 2048, 256, 512) == (32, 20, 8)
    assert A.flash_block_plan(2048, 2048, causal=False) == (16, 16, 0)


def test_gqa_expand():
    q, k, v = _qkv(jax.random.PRNGKey(3), h=8, hkv=2)
    ke, ve = gqa_expand(k, v, 8)
    assert ke.shape[2] == 8
    np.testing.assert_allclose(np.asarray(ke[:, :, 0]), np.asarray(ke[:, :, 3]))


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_reference(causal):
    mesh = build_mesh(MeshSpec(sequence=4))
    b, s, h, d = 2, 64, 4, 16
    q, k, v = _qkv(jax.random.PRNGKey(4), b=b, s=s, h=h, d=d)
    ref = mha_reference(q, k, v, causal=causal)

    spec = P(None, "sequence", None, None)
    ring = shard_map(
        functools.partial(ring_attention, axis_name="sequence", causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )
    out = jax.jit(ring)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ring_attention_grads():
    mesh = build_mesh(MeshSpec(sequence=4))
    q, k, v = _qkv(jax.random.PRNGKey(5), b=1, s=32, h=2, d=8)
    spec = P(None, "sequence", None, None)
    ring = shard_map(
        functools.partial(ring_attention, axis_name="sequence", causal=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )
    g = jax.jit(jax.grad(lambda q, k, v: ring(q, k, v).sum(), argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(lambda q, k, v: mha_reference(q, k, v).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)
