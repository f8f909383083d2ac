"""Paged KV cache + prefix reuse + disaggregated prefill (VERDICT r4
item 2; reference: vLLM PagedAttention / automatic prefix caching /
kv_transfer, which the reference LLM library defers to —
llm/_internal/serve/engines/vllm/)."""

import time
from concurrent.futures import Future

import pytest

import jax
import jax.numpy as jnp

import ray_tpu
from ray_tpu.models import transformer as T
from ray_tpu.models.continuous_batching import ContinuousBatcher, _Request
from ray_tpu.models.decoding import SamplingParams
from ray_tpu.models.paged_kv import PagedBatcher, PagedKV, prefix_keys


def _tiny_cfg():
    return T.config("debug", dtype=jnp.float32, param_dtype=jnp.float32)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = _tiny_cfg()
    params = T.init_params(cfg, jax.random.key(0))
    return cfg, params


class TestPagePool:
    def test_alloc_free_refcount(self):
        kv = PagedKV(num_pages=5, page_size=4)  # page 0 = trash
        a, b = kv.alloc(), kv.alloc()
        assert a != 0 and b != 0 and a != b
        kv.incref(a)
        kv.decref(a)
        assert a not in kv.free  # still referenced
        kv.decref(a)
        assert a in kv.free  # cached-free, content retained
        kv.incref(a)  # prefix hit resurrects it
        assert a not in kv.free
        kv.decref(a)
        kv.decref(b)

    def test_prefix_chain_and_eviction(self):
        kv = PagedKV(num_pages=4, page_size=2)
        keys = prefix_keys([1, 2, 3, 4, 5], page_size=2)
        assert len(keys) == 2  # only FULL pages hash
        p1, p2 = kv.alloc(), kv.alloc()
        kv.register_prefix(keys, [p1, p2])
        assert kv.lookup_prefix(keys) == [p1, p2]
        # chain property: a miss on page 0 stops the walk
        other = prefix_keys([9, 9, 3, 4], page_size=2)
        assert kv.lookup_prefix(other) == []
        # free both: they stay cached (rc=0, content+prefix retained)
        kv.decref(p1)
        kv.decref(p2)
        assert kv.lookup_prefix(keys) == [p1, p2]  # cached-free hit
        # alloc pressure: the never-used page goes first, THEN the LRU
        # cached page is reclaimed and its prefix entry evicted
        p3 = kv.alloc()
        assert p3 not in (p1, p2)
        p4 = kv.alloc()
        assert p4 == p1  # least recently freed cached page
        assert kv.lookup_prefix(keys) == []  # chain broken at page 0


class TestPagedBatcher:
    def test_greedy_matches_dense_batcher(self, tiny_model):
        """Paged attention must be bit-equivalent to the dense slot
        cache under greedy decoding."""
        cfg, params = tiny_model
        prompts = [[5, 17, 3], [100, 2, 3, 4, 5, 6, 88], [9], [1, 2]]
        sp = SamplingParams(max_tokens=10)
        dense = ContinuousBatcher(cfg, params, max_len=64, slots=4)
        try:
            ref = [f.result(timeout=120)
                   for f in [dense.submit(p, sp) for p in prompts]]
        finally:
            dense.shutdown()
        paged = PagedBatcher(cfg, params, max_len=64, slots=4,
                             page_size=16)
        try:
            outs = [f.result(timeout=120)
                    for f in [paged.submit(p, sp) for p in prompts]]
        finally:
            paged.shutdown()
        assert outs == ref

    def test_shared_prefix_prefills_once(self, tiny_model):
        """VERDICT acceptance (a): two requests sharing a long prefix —
        the second prefills ONLY the remainder, reusing the first's
        cached pages."""
        cfg, params = tiny_model
        page = 16
        shared = list(range(1, 33))  # exactly 2 full pages
        p1 = shared + [40, 41, 42]
        p2 = shared + [50, 51]
        sp = SamplingParams(max_tokens=4)
        pb = PagedBatcher(cfg, params, max_len=64, slots=2,
                          page_size=page, extra_pages=8)
        try:
            out1 = pb.submit(p1, sp).result(timeout=120)
            t1 = pb.stats["prefill_tokens"]
            assert t1 == len(p1)
            assert pb.stats["prefix_hit_tokens"] == 0
            out2 = pb.submit(p2, sp).result(timeout=120)
            t2 = pb.stats["prefill_tokens"] - t1
            # only the 2 tokens past the shared pages were prefilled
            assert t2 == len(p2) - 2 * page, pb.stats
            assert pb.stats["prefix_hit_tokens"] == 2 * page
            # and reuse did not change the result: compare against a
            # cold batcher with no cache to hit
            cold = PagedBatcher(cfg, params, max_len=64, slots=2,
                                page_size=page)
            try:
                ref2 = cold.submit(p2, sp).result(timeout=120)
            finally:
                cold.shutdown()
            assert out2 == ref2
            assert out1  # sanity: first request produced tokens
        finally:
            pb.shutdown()

    def test_no_recompilation_in_steady_state(self, tiny_model):
        """VERDICT acceptance (c): after warmup, further requests with
        new lengths in the same buckets add ZERO compiled programs."""
        cfg, params = tiny_model
        pb = PagedBatcher(cfg, params, max_len=64, slots=2, page_size=16)
        sp = SamplingParams(max_tokens=3)
        try:
            pb.submit([1, 2, 3], sp).result(timeout=120)
            pb.submit(list(range(20)), sp).result(timeout=120)
            decode_programs = pb.decode_cache_size()
            prefill_programs = len(pb._prefill_jits)
            # same buckets, different lengths/content — steady state
            for toks in ([7, 8], [9, 10, 11, 12], list(range(5, 23))):
                pb.submit(toks, sp).result(timeout=120)
            assert pb.decode_cache_size() == decode_programs == 1
            assert len(pb._prefill_jits) == prefill_programs
        finally:
            pb.shutdown()

    @pytest.mark.stress
    def test_overcommit_preempts_and_recovers(self, tiny_model):
        """Pool smaller than slots×pages_per_seq: lazy growth runs out,
        the youngest slot is preempted (recompute) and every request
        still completes with correct-length output."""
        cfg, params = tiny_model
        # 2 slots × 4 pages/seq would need 9 pages; give it 6
        pb = PagedBatcher(cfg, params, max_len=64, slots=2, page_size=16,
                          num_pages=6)
        sp = SamplingParams(max_tokens=40)
        try:
            futs = [pb.submit([i, i + 1, i + 2], sp) for i in range(3)]
            outs = [f.result(timeout=300) for f in futs]
            assert all(len(o) == 40 for o in outs)
            assert pb.stats["preempted"] >= 1, pb.stats
        finally:
            pb.shutdown()


    def test_a_slot_is_taken_back_on_a_drained_loop(self, tiny_model):
        """The pool runs out with a decode step in flight (the pump keeps
        one): the step is read and emitted before the victim is requeued,
        so it comes back over its prompt plus every token computed for it,
        and all three end with the tokens of a pool that never ran out."""
        cfg, params = tiny_model
        sp = SamplingParams(max_tokens=40)
        prompts = [[i, i + 1, i + 2] for i in range(3)]
        roomy = PagedBatcher(cfg, params, max_len=64, slots=2, page_size=16)
        try:
            want = [f.result(timeout=300)
                    for f in [roomy.submit(p, sp) for p in prompts]]
            assert roomy.stats["preempted"] == 0
        finally:
            roomy.shutdown()
        pb = PagedBatcher(cfg, params, max_len=64, slots=2, page_size=16,
                          num_pages=6)
        preempt, drain, seen, for_room, prompt_of = \
            pb._preempt, pb._drain, [], [], {}

        def watched_preempt(slot):
            req = pb._active[slot]
            prompt = prompt_of.setdefault(id(req), list(req.tokens))
            preempt(slot)
            seen.append((pb._inflight is None,
                         req.tokens == prompt + req.out))

        def watched_drain():
            starved = not pb.kv.free
            drained = drain()
            for_room.append(drained and starved)
            return drained

        pb._preempt, pb._drain = watched_preempt, watched_drain
        try:
            outs = [f.result(timeout=300)
                    for f in [pb.submit(p, sp) for p in prompts]]
            stats = dict(pb.stats)
        finally:
            pb.shutdown()
        assert outs == want
        assert stats["preempted"] == len(seen) >= 1 and stats["failed"] == 0
        # a step was in flight when the pool ran out, none at the preemption
        assert any(for_room)
        assert all(drained and whole for drained, whole in seen), seen
        assert stats["tokens_discarded"] == 0

    @pytest.mark.parametrize("max_len, max_tokens, preempt_at", [
        (64, 12, (6, 11)),
        # at the last row: it comes back with max_len tokens, which fill
        # the row and need no page for a decode write
        (32, 64, (31,)),
    ])
    def test_a_second_preemption_resumes_from_prompt_plus_output(
            self, tiny_model, max_len, max_tokens, preempt_at):
        """Preempted (at these lengths of its row), a request re-prefills
        over its prompt and every token it has emitted, each once (the
        second preemption used to append the whole output to tokens that
        already held its start), and ends with the tokens of an undisturbed
        run."""
        cfg, params = tiny_model
        prompt, sp = [5, 17, 3], SamplingParams(max_tokens=max_tokens)
        pb = PagedBatcher(cfg, params, max_len=max_len, slots=2,
                          page_size=16)
        want = pb.submit(prompt, sp).result(timeout=120)
        pb.shutdown()  # the pump is gone: the steps below are the test's
        req = _Request(list(prompt), sp, Future(), None)
        pb._waiting.put(req)
        for at in preempt_at:
            while req.slot < 0 or pb._host_len[req.slot] < at:
                pb._step()
            # the step that wrote row `at - 1` is in flight: a slot is taken
            # back on a drained loop, as `_grow_pages` does it
            assert pb._drain() and pb._host_len[req.slot] == at
            pb._preempt(req.slot)
            assert req.tokens == prompt + req.out
        while not req.future.done():
            pb._step()
        assert pb.stats["preempted"] == len(preempt_at)
        assert pb.stats["failed"] == 0
        assert req.future.result() == want


class TestDisaggregatedPrefill:
    def test_prefill_replica_feeds_decode_replica(self, ray_start_regular,
                                                  tiny_model):
        """VERDICT acceptance (b): prefill and decode run in separate
        actor processes; KV crosses through the shared-memory tensor
        channel; outputs match a single-process paged engine."""
        from ray_tpu.models.disagg_prefill import DisaggPrefillEngine

        cfg, params = tiny_model
        sp = SamplingParams(max_tokens=6)
        prompts = [[5, 17, 3], [9, 9, 2, 1], [42]]

        local = PagedBatcher(cfg, params, max_len=64, slots=4,
                             page_size=16)
        try:
            ref = [f.result(timeout=120)
                   for f in [local.submit(p, sp) for p in prompts]]
        finally:
            local.shutdown()

        eng = DisaggPrefillEngine(cfg, params, max_len=64, slots=4,
                                  page_size=16)
        try:
            refs = [eng.generate(p, sp) for p in prompts]
            outs = [ray_tpu.get(r, timeout=300) for r in refs]
            assert outs == ref
            stats = eng.stats()
            # the decode replica never ran a prompt prefill itself
            assert stats["prefill_tokens"] == 0, stats
            assert stats["admitted"] == len(prompts)
        finally:
            eng.shutdown()
