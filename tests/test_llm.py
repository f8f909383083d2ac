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
    Generator, SamplingParams, _attend_cached, init_cache,
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
