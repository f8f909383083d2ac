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
