"""Continuous batching (VERDICT round 3 item 6; reference: vLLM
iteration-level scheduling, which the reference LLM library defers to):
admit/evict per decode step over a fixed-slot KV cache, slot reuse
under staggered arrivals, and the Serve integration."""

import threading
import time

import pytest

import jax
import jax.numpy as jnp

import ray_tpu
from ray_tpu.models import transformer as T
from ray_tpu.models.continuous_batching import ContinuousBatcher
from ray_tpu.models.decoding import Generator, SamplingParams
from ray_tpu.models.paged_kv import PagedBatcher


def _tiny_cfg():
    return T.config("debug", dtype=jnp.float32, param_dtype=jnp.float32)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = _tiny_cfg()
    params = T.init_params(cfg, jax.random.key(0))
    return cfg, params


class TestContinuousBatcher:
    def test_greedy_matches_static_generator(self, tiny_model):
        """The slot-scheduled path must produce exactly the static
        Generator's greedy completions."""
        cfg, params = tiny_model
        prompts = [[5, 17, 3], [100, 2, 3, 4, 5, 6, 88], [9], [1, 2]]
        sp = SamplingParams(max_tokens=10)
        ref = Generator(cfg, params, max_len=64).generate(prompts, sp)

        cb = ContinuousBatcher(cfg, params, max_len=64, slots=4)
        try:
            futs = [cb.submit(p, sp) for p in prompts]
            outs = [f.result(timeout=120) for f in futs]
        finally:
            cb.shutdown()
        assert outs == ref

    def test_staggered_arrivals_reuse_slots(self, tiny_model):
        """VERDICT acceptance: more requests than slots, arriving
        staggered — later requests join the RUNNING batch when a slot
        frees (admitted mid-decode, not at step 0), and every slot is
        reused. Reports tokens/s under load."""
        cfg, params = tiny_model
        cb = ContinuousBatcher(cfg, params, max_len=128, slots=2)
        sp_long = SamplingParams(max_tokens=40)
        sp_short = SamplingParams(max_tokens=5)
        try:
            t0 = time.perf_counter()
            first = [cb.submit([1, 2, 3], sp_long),
                     cb.submit([4, 5], sp_short)]
            # let decoding get going before the late arrivals
            while cb.stats["steps"] < 3:
                time.sleep(0.01)
            late = [cb.submit([7, 8, 9, 10], sp_short),
                    cb.submit([11], sp_long)]
            outs = [f.result(timeout=180) for f in first + late]
            dt = time.perf_counter() - t0
        finally:
            cb.shutdown()
        st = cb.stats
        assert all(len(o) > 0 for o in outs)
        assert st["admitted"] == 4
        assert st["max_active"] <= 2  # never more than the slot count
        # slot reuse: 4 requests through 2 slots requires re-admission
        assert st["finished"] == 4
        tps = st["tokens_out"] / dt
        print(f"continuous batching: {st['tokens_out']} tokens in "
              f"{dt:.2f}s = {tps:,.0f} tok/s (slots=2, requests=4)")

    def test_late_request_joins_mid_decode(self, tiny_model):
        """A request submitted while others are decoding is admitted at
        a step > 0 — iteration-level scheduling, not batch-drain."""
        cfg, params = tiny_model
        cb = ContinuousBatcher(cfg, params, max_len=128, slots=4)
        try:
            long_running = cb.submit([1, 2], SamplingParams(max_tokens=60))
            while cb.stats["steps"] < 5:
                time.sleep(0.01)
            was_running = not long_running.done()
            f = cb.submit([3, 4], SamplingParams(max_tokens=3))
            f.result(timeout=120)
            # admitted after decoding had begun, while the long request
            # was still active
            assert was_running
            assert cb.stats["last_admit_step"] >= 5
            long_running.result(timeout=180)
        finally:
            cb.shutdown()

    def test_stream_and_mixed_sampling(self, tiny_model):
        """Streaming submission interleaves with batch futures; per-slot
        sampling params (greedy + temperature) share one decode step."""
        cfg, params = tiny_model
        cb = ContinuousBatcher(cfg, params, max_len=64, slots=4)
        try:
            greedy = cb.submit([5, 6, 7], SamplingParams(max_tokens=8))
            sampled = cb.submit(
                [5, 6, 7],
                SamplingParams(max_tokens=8, temperature=0.9, top_k=20))
            stream_toks = list(cb.submit_stream(
                [9, 10], SamplingParams(max_tokens=6)))
            g = greedy.result(timeout=120)
            s = sampled.result(timeout=120)
        finally:
            cb.shutdown()
        assert len(g) == 8 and len(s) == 8 and len(stream_toks) == 6
        vocab = cfg.vocab_size
        assert all(0 <= t < vocab for t in s)
        # greedy stream must equal a fresh greedy run of the same prompt
        ref = Generator(cfg, params, max_len=64).generate(
            [[9, 10]], SamplingParams(max_tokens=6))[0]
        assert stream_toks == ref


class TestDonatedCache:
    """The decode step is given the cache to keep (`_jit_programs`)."""

    def test_a_step_leaves_no_reference_to_the_cache_it_was_given(
            self, tiny_model):
        cfg, params = tiny_model
        cb = ContinuousBatcher(cfg, params, max_len=64, slots=2)
        seen = []
        step = cb._decode_jit

        def watched(params, toks, cache, *rest):
            seen.append(cache)
            return step(params, toks, cache, *rest)

        cb._decode_jit = watched
        try:
            out = cb.submit([5, 17, 3], SamplingParams(max_tokens=4)
                            ).result(timeout=120)
        finally:
            cb.shutdown()
        assert len(out) == 4 and len(seen) == 3
        if not seen[0].k.is_deleted():
            pytest.skip(f"{jax.default_backend()} does not donate buffers")
        # every cache a step took is gone: the engine holds the one the
        # last step returned and nothing else
        assert all(c.k.is_deleted() and c.v.is_deleted() for c in seen)
        assert not cb.cache.k.is_deleted()

    @pytest.mark.parametrize("via", ["future", "stream"])
    @pytest.mark.parametrize("engine", [ContinuousBatcher, PagedBatcher],
                             ids=["slots", "pages"])
    def test_a_failed_step_fails_its_requests_and_the_next_is_served(
            self, tiny_model, engine, via):
        """A step that raises after it consumed the cache: the active
        requests fail, each with the exception (a stream too: not a short
        answer), the pump starts again from an empty cache (every slot is
        free), and the next request gets the static Generator's completion,
        not an error about a deleted array. Whichever cache the scheduler
        runs over."""
        cfg, params = tiny_model
        sp = SamplingParams(max_tokens=6)
        ref = Generator(cfg, params, max_len=64).generate([[7, 8, 9]], sp)
        cb = engine(cfg, params, max_len=64, slots=2)
        step, raised = cb._decode_jit, []

        def raises_once(*args):
            out = step(*args)  # the cache in `args` is consumed
            if not raised and int(args[6].sum()) == 2:  # both are active
                raised.append(True)
                raise RuntimeError("the device fell over")
            return out

        def doomed(prompt, errors):
            sampling = SamplingParams(max_tokens=40)
            try:
                if via == "stream":
                    list(cb.submit_stream(prompt, sampling))
                else:
                    cb.submit(prompt, sampling).result(timeout=120)
            except RuntimeError as e:
                errors.append(e)

        cb._decode_jit = raises_once
        errors = []
        try:
            callers = [threading.Thread(target=doomed, args=(p, errors),
                                        daemon=True)
                       for p in ([5, 17, 3], [1, 2])]
            for caller in callers:
                caller.start()
            for caller in callers:
                caller.join(timeout=120)
            assert cb.submit([7, 8, 9], sp).result(timeout=120) == ref[0]
            stats = dict(cb.stats)
        finally:
            cb.shutdown()
        assert [str(e) for e in errors] == ["the device fell over"] * 2
        assert stats["failed"] == 2 and stats["finished"] == 1
        assert not cb.cache.k.is_deleted()


class TestServeContinuous:
    def test_staggered_serving_traffic(self, ray_start_regular):
        """Serve replica under staggered mixed-length traffic: all
        requests complete and the engine's stats show slot reuse."""
        from ray_tpu import serve
        from ray_tpu.llm import LLMConfig, build_llm_deployment

        cfg = LLMConfig(
            model=_tiny_cfg(), max_len=96, name="cb_llm",
            sampling=SamplingParams(max_tokens=12),
            continuous_batching=True, cache_slots=2)
        handle = serve.run(build_llm_deployment(cfg), name="cb_llm")
        try:
            results = {}
            errors = []

            def call(i, text):
                try:
                    results[i] = handle.remote(text).result()
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

            threads = []
            for i, text in enumerate(["hello", "hi", "a longer prompt",
                                      "x", "mid size"]):
                th = threading.Thread(target=call, args=(i, text), daemon=True)
                th.start()
                threads.append(th)
                time.sleep(0.15)  # staggered arrivals
            for th in threads:
                th.join(timeout=300)
            assert not errors, errors
            assert len(results) == 5
            stats = handle.engine_stats.remote().result()
            assert stats["admitted"] == 5
            assert stats["max_active"] <= 2  # bounded by cache_slots
            assert stats["finished"] == 5
        finally:
            serve.shutdown()
