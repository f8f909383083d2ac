"""Continuous batching (VERDICT round 3 item 6; reference: vLLM
iteration-level scheduling, which the reference LLM library defers to):
admit/evict per decode step over a fixed-slot KV cache, slot reuse
under staggered arrivals, and the Serve integration."""

import threading
import time
from concurrent.futures import Future

import pytest

import jax
import jax.numpy as jnp
import numpy as np

import ray_tpu
from ray_tpu.models import transformer as T
from ray_tpu.models.continuous_batching import (
    ContinuousBatcher,
    _Request,
    _sample_per_slot,
)
from ray_tpu.models.decoding import Generator, SamplingParams
from ray_tpu.models.paged_kv import PagedBatcher
from ray_tpu.ops.attention import NEG_INF


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

    def test_the_pumps_three_clocks(self, tiny_model):
        """`pump_step_s` (wall), `pump_sync_s` (of it, the waits for the
        device) and `pump_cpu_s` (the thread's own CPU) grow with the steps,
        the two parts never beyond the whole, and stand still while the pump
        idles; a stream says how many ids it holds that nobody took."""
        cfg, params = tiny_model
        cb = ContinuousBatcher(cfg, params, max_len=64, slots=2)
        clocks = ("pump_step_s", "pump_sync_s", "pump_cpu_s")
        try:
            assert [cb.stats[k] for k in clocks] == [0.0, 0.0, 0.0]
            seen = [dict(cb.stats)]
            for _ in range(2):
                cb.submit([5, 17, 3], SamplingParams(max_tokens=6)
                          ).result(timeout=120)
                while cb._inflight is not None or cb._active:
                    time.sleep(0.01)  # the pass that retired it is booked
                time.sleep(0.05)
                seen.append(dict(cb.stats))
            time.sleep(0.3)  # engine.idle: nothing moves
            idle = dict(cb.stats)
            stream = cb.submit_stream([5, 17, 3], SamplingParams(max_tokens=6))
            first = next(stream)
            while cb.stats["finished"] < 3:
                time.sleep(0.01)
            assert stream.backlog() == 6  # five ids and the end's marker
            assert [first] + list(stream) == cb.submit(
                [5, 17, 3], SamplingParams(max_tokens=6)).result(timeout=120)
            assert stream.backlog() == 0 and list(stream) == []
        finally:
            cb.shutdown()
        for before, after in zip(seen, seen[1:]):
            assert after["steps"] > before["steps"]
            assert all(after[k] > before[k] for k in clocks)
        for st in seen[1:]:
            assert 0 < st["pump_sync_s"] <= st["pump_step_s"]
            assert 0 < st["pump_cpu_s"] <= st["pump_step_s"]
        assert {k: idle[k] for k in clocks + ("steps",)} == \
            {k: seen[-1][k] for k in clocks + ("steps",)}
        assert all(isinstance(cb.stats[k], float) for k in clocks)

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


BOTH_CACHES = pytest.mark.parametrize(
    "engine, more", [(ContinuousBatcher, {}),
                     (PagedBatcher, {"page_size": 16})],
    ids=["slots", "pages"])


def _sample_every_row_sorted(logits, rng, temps, topks):
    """`_sample_per_slot` as it was before sampling followed its rows (PR
    30's, to the letter): the argmax, the full sort and the draw for every
    row, whatever was asked. Frozen here as the oracle."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    f32 = logits.astype(jnp.float32)
    scaled = f32 / jnp.maximum(temps, 1e-6)[:, None]
    v = logits.shape[-1]
    sorted_desc = -jnp.sort(-scaled, axis=-1)
    idx = jnp.clip(topks - 1, 0, v - 1)[:, None]
    kth = jnp.take_along_axis(sorted_desc, idx, axis=1)
    filtered = jnp.where(
        (topks[:, None] > 0) & (scaled < kth), NEG_INF, scaled)
    sampled = jax.random.categorical(rng, filtered, axis=-1).astype(
        jnp.int32)
    return jnp.where(temps > 0.0, sampled, greedy)


ROWS, VOCAB = 6, 97
EVERY_ROW = [True] * ROWS
# name -> (temperatures, top-ks, active rows); an inactive row's values are
# what its last request left in the slot
SAMPLING_MIXES = {
    "all_greedy": ([0.0] * ROWS, [0] * ROWS, EVERY_ROW),
    "greedy_rows_that_name_a_top_k": (
        [0.0] * ROWS, [0, 5, 0, 1, 0, VOCAB], EVERY_ROW),
    "all_sampled_unfiltered": (
        [1.5, 0.7, 1.0, 2.0, 0.3, 1.1], [0] * ROWS, EVERY_ROW),
    "mixed_temperatures": (
        [0.0, 0.9, 0.0, 1.3, 0.0, 0.0], [0] * ROWS, EVERY_ROW),
    "top_k_1": ([0.0, 0.9, 1.4, 0.0, 1.0, 0.8], [0, 1, 0, 0, 1, 0],
                EVERY_ROW),
    "top_k_5": ([1.2, 0.9, 0.0, 0.0, 1.0, 0.8], [5, 0, 5, 0, 5, 0],
                EVERY_ROW),
    "top_k_whole_vocabulary": (
        [1.2, 0.9, 0.0, 1.7, 1.0, 0.8], [VOCAB, 0, 0, VOCAB, 3, 0],
        EVERY_ROW),
    "stale_row_beside_greedy_rows": (
        [0.0, 0.9, 0.0, 0.0, 1.3, 0.0], [0, 20, 0, 0, 0, 0],
        [True, False, True, True, False, False]),
    "stale_top_k_beside_an_unfiltered_draw": (
        [0.0, 0.9, 1.1, 0.0, 1.3, 0.0], [0, 20, 0, 0, 4, 7],
        [True, False, True, True, False, True]),
    "stale_row_beside_a_top_k_draw": (
        [0.8, 0.9, 0.0, 0.0, 1.3, 0.0], [3, 20, 0, 0, 0, 0],
        [True, False, True, False, False, True]),
}


def _primitives(jaxpr, inside_cond=False):
    """(primitive name, whether a `cond`'s branch holds it) of every
    equation of `jaxpr`, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, inside_cond
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(
                sub, inside_cond or eqn.primitive.name == "cond")


class TestWeightsLieAsTheDecodeStepReadsThem:
    """The engine compiles its decode step with the weights' layouts on the
    device the step's own to choose and re-lays the leaves that differ once,
    at its build (`ContinuousBatcher._lay_weights`). On host devices the
    compiler chooses the default, so nothing moves: the tree, the counters
    and the tokens are the ones the tree gave before PR 66."""

    @pytest.mark.parametrize("batcher,kwargs", [
        (ContinuousBatcher, {}), (PagedBatcher, {"page_size": 16})],
        ids=["slots", "pages"])
    def test_the_tree_its_counters_and_its_greedy_tokens(
            self, tiny_model, batcher, kwargs):
        cfg, given = tiny_model
        kept = jax.tree.map(np.asarray, given)
        prompt = np.random.default_rng(61).integers(
            0, cfg.vocab_size, 11).tolist()
        cb = batcher(cfg, given, max_len=64, slots=2, **kwargs)
        try:
            out = cb.submit(prompt, SamplingParams(max_tokens=8)).result(
                timeout=120)
            stats = dict(cb.stats)
        finally:
            cb.shutdown()
        assert jax.tree.structure(cb.params) == jax.tree.structure(given)
        assert jax.tree.all(jax.tree.map(
            lambda leaf, was: leaf.shape == was.shape
            and leaf.dtype == was.dtype
            and np.array_equal(np.asarray(leaf), was), cb.params, kept))
        assert stats["weights_relaid"] == {}
        assert stats["weights_relaid_bytes"] == 0
        # as the parent commit's batchers answered the same prompt
        assert out == [26, 258, 367, 479, 405, 338, 106, 69]
        assert out == Generator(cfg, given, max_len=64).generate(
            [prompt], SamplingParams(max_tokens=8))[0]
        # every program is told the formats the decode step chose, and
        # lowering one with plain shapes says so
        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), cb.params)
        assert jax.tree.structure(cb._formats) == jax.tree.structure(shapes)
        lowered = cb._decode_jit.lower(shapes, *cb._step_shapes())
        assert lowered.compile().input_formats[0][0] == cb._formats

    @pytest.mark.parametrize("batcher,kwargs", [
        (ContinuousBatcher, {}), (PagedBatcher, {"page_size": 16})],
        ids=["slots", "pages"])
    def test_a_leaf_the_step_reads_in_another_layout_is_moved_once(
            self, monkeypatch, batcher, kwargs):
        """The whole path on host devices, where the compiler itself never
        chooses: the decode step is compiled as if `Layout.AUTO` had answered
        "`wq` by head" (what the chip's compiler answers), so the engine
        moves that one leaf, deletes the one it was given, tells every
        prefill program, and the tokens are the ones of the default layout."""
        from jax.experimental.layout import Format, Layout
        from ray_tpu.models import continuous_batching as CB

        cfg = _tiny_cfg()
        given = T.init_params(cfg, jax.random.key(0))
        kept = jax.tree.map(np.asarray, given)
        by_head = jax.tree_util.tree_map_with_path(
            lambda path, leaf: Format(
                Layout((0, 2, 1, 3)) if path[-1].key == "wq" else None,
                leaf.sharding), given)
        real = CB._weights_first
        monkeypatch.setattr(
            CB, "_weights_first", lambda impl, formats, **kw: real(
                impl, by_head if isinstance(formats, Format) else formats,
                **kw))
        prompt = np.random.default_rng(61).integers(
            0, cfg.vocab_size, 11).tolist()
        cb = batcher(cfg, given, max_len=64, slots=2, **kwargs)
        try:
            out = cb.submit(prompt, SamplingParams(max_tokens=8)).result(
                timeout=120)
        finally:
            cb.shutdown()
        wq = cb.params["blocks"]["wq"]
        assert cb.stats["weights_relaid"] == {"blocks/wq": [0, 2, 1, 3]}
        assert cb.stats["weights_relaid_bytes"] == wq.nbytes
        assert wq.format.layout.major_to_minor == (0, 2, 1, 3)
        assert given["blocks"]["wq"].is_deleted()
        assert cb.params["blocks"]["wk"] is given["blocks"]["wk"]
        assert jax.tree.all(jax.tree.map(
            lambda leaf, was: np.array_equal(np.asarray(leaf), was),
            cb.params, kept))
        assert out == [26, 258, 367, 479, 405, 338, 106, 69]
        # lowered with plain shapes, the step is compiled for that choice
        told = cb._decode_jit.lower(
            jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                         cb.params), *cb._step_shapes()).compile()
        assert told.input_formats[0][0]["blocks"]["wq"].layout \
            .major_to_minor == (0, 2, 1, 3)
        assert cb._formats["blocks"]["wq"].layout.major_to_minor \
            == (0, 2, 1, 3)

    def test_an_engines_generator_and_batcher_hold_the_same_leaves(self):
        from ray_tpu.llm import LLMConfig
        from ray_tpu.llm.engine import ContinuousLLMEngine

        engine = ContinuousLLMEngine(LLMConfig(
            model="debug", max_len=64, cache_slots=2))
        try:
            ours = jax.tree.leaves(engine.batcher.params)
            theirs = jax.tree.leaves(engine.generator.params)
            assert len(ours) == len(theirs) > 0
            assert all(a is b for a, b in zip(ours, theirs))
            assert not any(leaf.is_deleted() for leaf in ours)
        finally:
            engine.shutdown()


class TestSamplingFollowsItsRows:
    """Sampling does what its ACTIVE rows ask for: the argmax alone for
    greedy rows, the draw only where a row has a temperature, the sort only
    where such a row has a top-k; no active row's token differs from the
    function that did all of it for every row."""

    @pytest.mark.parametrize("mix", sorted(SAMPLING_MIXES))
    def test_active_rows_get_the_tokens_of_the_full_sort(self, mix):
        temps, topks, active = (np.asarray(a, t) for a, t in zip(
            SAMPLING_MIXES[mix], (np.float32, np.int32, bool)))
        oracle, sampler = jax.jit(_sample_every_row_sorted), \
            jax.jit(_sample_per_slot)
        for seed in range(3):
            logits = 3.0 * jax.random.normal(
                jax.random.key(100 + seed), (ROWS, VOCAB), jnp.float32)
            key = jax.random.key(seed)
            want = np.asarray(oracle(logits, key, temps, topks))
            got = np.asarray(sampler(logits, key, temps, topks, active))
            assert got.dtype == np.int32 and got.shape == (ROWS,)
            assert (got[active] == want[active]).all(), (seed, got, want)
            greedy = np.asarray(jnp.argmax(logits, axis=-1))
            assert (got[active & (temps == 0)]
                    == greedy[active & (temps == 0)]).all()
            if mix == "all_sampled_unfiltered":  # the draw is a draw
                assert (got != greedy).any()
            if mix == "top_k_1":  # and the filter a filter
                assert (got[topks == 1] == greedy[topks == 1]).all()

    def test_the_sort_and_the_draw_are_inside_conditionals(self):
        """The sampler's jaxpr: the argmax is unconditional; the random bits
        and the sort are in a `cond`'s branches, the sort one `cond` deeper
        than the draw, and no sort is outside every branch."""
        jaxpr = jax.make_jaxpr(_sample_per_slot)(
            jnp.zeros((ROWS, VOCAB)), jax.random.key(0), jnp.zeros(ROWS),
            jnp.zeros(ROWS, jnp.int32), jnp.ones(ROWS, bool)).jaxpr
        seen = set(_primitives(jaxpr))
        assert ("argmax", False) in seen and ("cond", False) in seen
        assert ("sort", True) in seen and ("sort", False) not in seen
        assert ("random_bits", True) in seen
        assert ("random_bits", False) not in seen
        outer = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
        assert len(outer) == 1
        # branch 0 is the predicate's False: the argmax as it is
        greedy_branch, draw_branch = outer[0].params["branches"]
        assert not list(_primitives(greedy_branch.jaxpr))
        drawn = dict(_primitives(draw_branch.jaxpr))
        assert drawn["random_bits"] is False and drawn["sort"] is True

    @BOTH_CACHES
    def test_steps_are_counted_by_what_their_active_rows_ask_for(
            self, tiny_model, engine, more):
        """A greedy run counts no sampled and no sorted step. Beside a
        request that draws from its top 5 and one that draws unfiltered,
        the greedy requests get the greedy run's tokens; `steps_sorted`
        rises while the first is active and `steps_sampled` while either
        is, and both stop when they retire though their slots keep the
        stale temperature and top-k."""
        cfg, params = tiny_model
        greedy_prompts = [[5, 6, 7], [9, 10]]

        def run(extra):
            cb = engine(cfg, params, max_len=64, slots=4, **more)
            cb.shutdown()  # the pump is gone: the passes below are the test's
            reqs = [_Request(list(p), sp, Future(), None)
                    for p, sp in [(p, SamplingParams(max_tokens=14))
                                  for p in greedy_prompts] + extra]
            for r in reqs:
                cb._waiting.put(r)
            seen = []  # after every pass: (steps, sampled, sorted)
            for _ in range(100):
                cb._step()
                seen.append(tuple(cb.stats[k] for k in (
                    "steps", "steps_sampled", "steps_sorted")))
                if all(r.future.done() for r in reqs):
                    break
            return cb, [r.future.result(timeout=0) for r in reqs], seen

        cb, plain, seen = run([])
        assert cb.stats["steps"] == 13
        assert cb.stats["steps_sampled"] == cb.stats["steps_sorted"] == 0
        gen = Generator(cfg, params, max_len=64)
        assert plain == gen.generate(greedy_prompts,
                                     SamplingParams(max_tokens=14))

        cb, mixed, seen = run([
            ([5, 6, 7], SamplingParams(max_tokens=4, temperature=0.9,
                                       top_k=5)),
            ([3, 4], SamplingParams(max_tokens=7, temperature=1.2))])
        assert mixed[:2] == plain and len(mixed[2]) == 4 \
            and len(mixed[3]) == 7
        # a first token comes from the admit: 3 and 6 decode steps
        assert cb.stats["steps"] == 13
        assert cb.stats["steps_sorted"] == 3
        assert cb.stats["steps_sampled"] == 6
        assert seen[:7] == [(1, 1, 1), (2, 2, 2), (3, 3, 3), (4, 4, 3),
                            (5, 5, 3), (6, 6, 3), (7, 6, 3)]
        # the slots they left keep what they asked for: nobody reads it
        assert sorted(cb._temps[cb._temps > 0].tolist()) == \
            pytest.approx([0.9, 1.2])
        assert (cb._topks > 0).sum() == 1
        assert seen[-1] == (13, 6, 3)


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
    @BOTH_CACHES
    def test_a_failed_step_fails_its_requests_and_the_next_is_served(
            self, tiny_model, engine, more, via):
        """A step that raises after it consumed the cache, with the step
        before it still in flight: what that one computed goes out first,
        then the active requests fail, each with the exception (a stream
        too: not a short answer), the pump starts again from an empty cache
        (every slot is free), and the next request gets the static
        Generator's completion, not an error about a deleted array.
        Whichever cache the scheduler runs over."""
        cfg, params = tiny_model
        sp = SamplingParams(max_tokens=6)
        ref = Generator(cfg, params, max_len=64).generate([[7, 8, 9]], sp)
        cb = engine(cfg, params, max_len=64, slots=2, **more)
        step, raised, rows = cb._decode_jit, [], []

        def raises_once(*args):
            out = step(*args)  # the cache in `args` is consumed
            both = int(args[6].sum()) == 2
            if not raised and both and cb._inflight is not None:
                raised.append(True)
                raise RuntimeError("the device fell over")
            if not raised:
                rows.append(int(args[6].sum()))
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
            failed_with = dict(cb.stats)
            assert cb.submit([7, 8, 9], sp).result(timeout=120) == ref[0]
            stats = dict(cb.stats)
        finally:
            cb.shutdown()
        assert [str(e) for e in errors] == ["the device fell over"] * 2
        assert stats["failed"] == 2 and stats["finished"] == 1
        # a first token each and a token a row of every step that ran, the
        # one in flight when the next raised among them
        assert rows[-1] == 2 and failed_with["tokens_out"] == 2 + sum(rows)
        assert not cb.cache.k.is_deleted()


def _steps_with_no_step_ahead(arrivals, slots):
    """Decode steps of the plain loop (read every step before the next is
    dispatched) over [(queued once this many steps have run, tokens the
    request gets)]: a pass admits from the queue into the free slots, then
    one step gives every active request a token; a request's first token
    comes from its prefill."""
    todo, waiting, active, steps = sorted(arrivals), [], [], 0
    while todo or waiting or active:
        while todo and todo[0][0] <= steps:
            waiting.append(todo.pop(0)[1])
        while waiting and len(active) < slots:
            active.append(waiting.pop(0) - 1)
            active = [n for n in active if n > 0]
        assert active or not todo, "the schedule leaves the loop idle"
        if active:
            steps += 1
            active = [n - 1 for n in active if n > 1]
    return steps


class TestOneStepAhead:
    """The pump dispatches step n+1 before it reads step n: the same tokens
    in the same steps, requests counted out with no step spent on them, and
    only a stop token seen a step late."""

    @BOTH_CACHES
    def test_staggered_mixed_lengths_take_the_plain_loops_steps(
            self, tiny_model, engine, more):
        """Requests of mixed `max_tokens` (one counted out by the cache's
        last row, one by its first token) queued at set step counts: each
        gets the static Generator's tokens, in exactly the steps of the
        loop that ran no step ahead, nearly all of them dispatched ahead,
        none thrown away."""
        cfg, params = tiny_model
        slots, max_len = 2, 32
        arrivals = [  # (queued once this many steps have run, prompt, max)
            (0, [1, 2, 3], 20), (0, [4, 5], 5), (2, [7, 8, 9, 10], 5),
            (6, list(range(30, 58)), 12), (8, [11], 1), (8, [12, 13], 9)]
        gen = Generator(cfg, params, max_len=max_len)
        want = [gen.generate([p], SamplingParams(max_tokens=m))[0]
                for _, p, m in arrivals]
        assert [len(w) for w in want] == [20, 5, 5, 5, 1, 9]  # 28 + 5 > 32

        cb = engine(cfg, params, max_len=max_len, slots=slots, **more)
        cb.shutdown()  # the pump is gone: the passes below are the test's
        reqs = [_Request(list(p), SamplingParams(max_tokens=m), Future(), None)
                for _, p, m in arrivals]
        todo = sorted(zip([at for at, _, _ in arrivals], range(len(reqs))))
        for _ in range(200):
            while todo and todo[0][0] <= cb.stats["steps"]:
                cb._waiting.put(reqs[todo.pop(0)[1]])
            cb._step()
            if not todo and all(r.future.done() for r in reqs):
                break
        assert [r.future.result(timeout=0) for r in reqs] == want
        st = cb.stats
        assert st["steps"] == _steps_with_no_step_ahead(
            [(at, len(w)) for (at, _, _), w in zip(arrivals, want)], slots)
        assert st["tokens_discarded"] == 0 and st["failed"] == 0
        # a step has none before it only after an admit (4 passes admit)
        assert st["steps"] == 20 and st["steps_ahead"] == 20 - 4
        assert cb._inflight is None and not cb._active

    @BOTH_CACHES
    def test_a_stop_token_ends_the_request_and_its_step_in_flight_is_dropped(
            self, tiny_model, engine, more):
        """The stop token is read with the next step already in flight: the
        stream ends before it, that step's token for the slot is counted
        as discarded and never emitted, and the slot's next occupant (the
        install overwrites the row the dropped step wrote) is right token
        for token."""
        cfg, params = tiny_model
        gen = Generator(cfg, params, max_len=64)
        free_run = gen.generate([[5, 17, 3]], SamplingParams(max_tokens=12))[0]
        # a token a decode step samples, with more to come by count
        at = next(i for i in range(2, 10) if free_run[i] not in free_run[:i])
        stopped = SamplingParams(max_tokens=12, stop_token_id=free_run[at])
        assert gen.generate([[5, 17, 3]], stopped)[0] == free_run[:at]
        sp = SamplingParams(max_tokens=8)
        want_next = gen.generate([[9, 4, 4, 1]], sp)[0]

        cb = engine(cfg, params, max_len=64, slots=1, **more)
        try:
            first = cb.submit_stream([5, 17, 3], stopped)
            head = next(first)  # queued at the generator's first pass
            following = cb.submit([9, 4, 4, 1], sp)  # waits for the slot
            assert [head, *first] == free_run[:at]
            assert following.result(timeout=120) == want_next
            stats = dict(cb.stats)
        finally:
            cb.shutdown()
        assert stats["tokens_discarded"] == 1
        assert stats["tokens_out"] == at + len(want_next)
        # a step a token after each first one, the stop token's, and the
        # one that was dropped
        assert stats["steps"] == (at - 1) + 1 + 1 + (len(want_next) - 1)
        assert stats["finished"] == 2 and stats["failed"] == 0

    @BOTH_CACHES
    def test_shutdown_with_a_step_in_flight_resolves_every_caller(
            self, tiny_model, engine, more):
        cfg, params = tiny_model
        cb = engine(cfg, params, max_len=512, slots=2, **more)
        long = SamplingParams(max_tokens=400)
        streamed, errors = [], []

        def stream():
            try:
                streamed.extend(cb.submit_stream([1, 2], long))
            except RuntimeError as e:
                errors.append(e)

        try:
            futs = [cb.submit([5, 17, 3], long), cb.submit([9], long)]
            caller = threading.Thread(target=stream, daemon=True)
            caller.start()
            while cb.stats["steps_ahead"] < 3:
                time.sleep(0.01)
        finally:
            cb.shutdown()
        assert cb._inflight is not None and not cb._thread.is_alive()
        for f in futs:
            with pytest.raises(RuntimeError, match="was shut down"):
                f.result(timeout=10)
        caller.join(timeout=10)
        assert not caller.is_alive() and not errors
        assert cb.stats["failed"] == 0


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
            # the pump's clocks and the process's CPU seconds: what says
            # whether this replica is CPU-bound
            assert 0 < stats["pump_cpu_s"] <= stats["pump_step_s"]
            assert 0 < stats["process_cpu_s"] < \
                handle.engine_stats.remote().result()["process_cpu_s"]
            # the process as a producer of streams (nothing streamed here)
            assert stats["stream_items_sent"] >= stats["stream_calls"] >= 0
            # every compiled prefill bucket says what its fresh rows were
            # attended with: off a TPU, `_attend_cached`
            buckets = stats["prefill_attention_path"]
            assert buckets and set(buckets.values()) == {"dense"}
            assert all(b.startswith("prefill_") for b in buckets)
            assert stats["admitted"] == 5
            assert stats["max_active"] <= 2  # bounded by cache_slots
            assert stats["finished"] == 5
        finally:
            serve.shutdown()
