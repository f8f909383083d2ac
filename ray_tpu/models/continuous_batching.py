"""Continuous batching: admit/evict sequences per decode step over a
fixed-slot KV cache.

Reference: the reference LLM library defers serving to vLLM
(python/ray/llm/_internal/serve/engines/vllm/) whose core idea is
iteration-level scheduling — new requests join the running batch the
moment a slot frees, instead of waiting for the whole batch to drain.
This is the TPU-native version:

- the KV cache has a FIXED number of slots (rows) and a fixed max_len —
  static shapes, so XLA compiles exactly three programs (prefill per
  length bucket, row install, one decode step) and never recompiles in
  steady state,
- one jitted decode step advances ALL active slots together (free slots
  compute too and are masked out — on TPU the batch dimension is padded
  anyway, wasted rows cost nothing vs. a recompile),
- per-slot sampling (temperature / top-k) is vectorized so requests
  with different SamplingParams share one device step,
- admission: a waiting request prefills into a standalone single-row
  cache (bucketed lengths bound compile count) and the row is scattered
  into its slot; eviction: stop-token / max_tokens / cache-full frees
  the slot the same step, and the next waiting request takes it.

``ContinuousBatcher.submit()`` is thread-safe and returns a Future; a
pump thread runs steps while any request is active or waiting — the
Serve replica's concurrent handlers all feed one device loop, keeping
the MXU busy under mixed-length traffic.

``ContinuousBatcher`` is the one scheduler: how a request's lifecycle is
run (queue, admission, first token, step, emit, retire, failure, spans,
counters) is here and nowhere else. WHERE K/V rows are kept is behind
six methods it calls and never looks into (``_empty_cache``,
``_prefill_into``, ``_decode``, ``_release``, ``_make_room``,
``_refused_for_now``): this class answers them for fixed slots, and
``models/paged_kv.PagedBatcher`` overrides them for refcounted pages with
prefix reuse and preemption. A subclass, not a cache object held by the
scheduler: the programs are methods that the benchmark and
``tests/test_chip_compile.py`` lower from a bare instance, and both kinds
of cache share ``_decode_impl`` through ``forward_cached``. What a cache
holds for one request rides on the request as ``_Request.kv``, which the
scheduler never reads. ``PrefillPrograms``, the scheduler's base, is the
prompt program alone: what a prefill replica constructs
(``models/disagg_prefill.py``).

A family may keep more than K/V rows a sequence and says what in one
statement (``TransformerConfig.kept``): rows of ``max_len`` a slot (``k`` /
``v``, or one ``latent`` row a position), rows in a RING of fewer (``ring_k``
/ ``ring_v``) and STATES that every step reads and rewrites (``state``: the
position before; ``mat`` / ``conv``: a float32 matrix a head, a convolution's
window). The engine walks that statement and names no family: the cache is
built from it, a prefill returns all of it (``_row_of``), rows of bucket
length and the rest as the prompt's TRUE length leaves it, install writes the
whole of each into the slot (``_slot_fields``: nothing of the slot's last
occupant stays visible) and ``_kv_rows`` sums over its rows. The decode step
keeps all of it (donated together), advances active slots alone, and a slot
given back has its states cleared. Pages hold K/V rows and one ``state`` a
slot: ``PagedBatcher`` refuses a configuration that keeps anything else.
"""

from __future__ import annotations

import dataclasses
import inspect
import math
import queue
import threading
import time
from concurrent.futures import Future
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Format, Layout

from ray_tpu.models.decoding import (
    STATES,
    KVCache,
    SamplingParams,
    _write_stack,
    forward_cached,
    init_cache,
)
from ray_tpu.models.transformer import TransformerConfig
from ray_tpu.observability import schema as spans
from ray_tpu.observability.timeline import setup_phase
from ray_tpu.observability.tracing import device_span
from ray_tpu.ops import traced
from ray_tpu.ops.attention import (
    NEG_INF, decode_block, decode_rows_copied)
from ray_tpu.parallel.bootstrap import FirstCall, program_phase


# passes of the pump between two bookings of its clocks (`_book`)
BOOK_EVERY = 32
# what a cache may keep a slot behind `k`, `v` and `lengths`, in the order a
# prefill program returns it and install takes it
KEPT = KVCache._fields[3:]


def _sample_per_slot(logits, rng, temps, topks, active):
    """Vectorized sampling: per-row temperature (0 = greedy) and top-k
    (0 = unfiltered). logits [B, V] -> ids [B]. The work follows what the
    `active` rows ask for, decided inside the program: every row's argmax
    always; the scaling and the draw over [B, V] only in a step where some
    active row has a temperature; the full-vocabulary sort, the k-th
    threshold and the filter only where such a row also has a top-k. A row's
    id is the same in every branch it can come out of (a greedy row's is the
    argmax, an unfiltered row's `filtered` is `scaled`). Inactive rows do not
    count: a slot keeps its last request's temperature and top-k until the
    next admit, and nobody reads its id."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    draws = active & (temps > 0.0)

    def draw():
        f32 = logits.astype(jnp.float32)
        scaled = f32 / jnp.maximum(temps, 1e-6)[:, None]

        def top_k_filtered():
            # per-row kth threshold: value at rank (top_k - 1) descending;
            # top_k == 0 disables the filter for that row
            v = logits.shape[-1]
            sorted_desc = -jnp.sort(-scaled, axis=-1)
            idx = jnp.clip(topks - 1, 0, v - 1)[:, None]
            kth = jnp.take_along_axis(sorted_desc, idx, axis=1)
            return jnp.where(
                (topks[:, None] > 0) & (scaled < kth), NEG_INF, scaled)

        filtered = jax.lax.cond(
            jnp.any(draws & (topks > 0)), top_k_filtered, lambda: scaled)
        sampled = jax.random.categorical(rng, filtered, axis=-1).astype(
            jnp.int32)
        return jnp.where(temps > 0.0, sampled, greedy)

    return jax.lax.cond(jnp.any(draws), draw, lambda: greedy)


# an admit's first token: the same function as a program of its own (called
# eagerly, a `lax.cond` is traced and compiled again at every call). Its
# first call in the process is booked (`FirstCall`), then the name holds the
# bare jitted callable
_sample_first = FirstCall(jax.jit(_sample_per_slot), "sample_first",
                          globals(), "_sample_first")


@dataclasses.dataclass
class _Dispatched:
    """A decode step that was dispatched and whose tokens the host has not
    read: the device arrays, their copies to the host under way."""
    toks: Any  # [slots] sampled tokens: the next step's input as it is
    load: list  # the program's expert load ([] from a dense model)
    reqs: Dict[int, "_Request"]  # slot -> the request the step advanced


@dataclasses.dataclass
class _Request:
    tokens: List[int]
    sampling: SamplingParams
    future: Optional[Future]
    stream_q: Optional[queue.Queue]  # token stream, None-terminated
    out: List[int] = dataclasses.field(default_factory=list)
    slot: int = -1
    t_submit: float = dataclasses.field(default_factory=time.monotonic)
    # the cache's own record of what it holds for the request; the
    # scheduler carries it and never reads it
    kv: Any = None


class _TokenStream:
    """A streamed request's token ids as the pump emits them, and how many
    of them nobody has taken yet. The request is enqueued when the first id
    is asked for: iteration is a generator's."""

    def __init__(self, batcher, req: _Request):
        self._req = req
        self._ids = self._emitted(batcher)

    def _emitted(self, batcher):
        q = batcher._enqueue(self._req).stream_q
        while True:
            t = q.get()
            if t is None:
                return
            if isinstance(t, BaseException):
                raise t  # the admit or the step failed: not a short answer
            yield t

    def __iter__(self):
        return self._ids

    def __next__(self) -> int:
        return next(self._ids)

    def backlog(self) -> int:
        return len(self._req.stream_q.queue)  # no lock: a reading, a token


def _counted(aux: dict) -> list:
    """What a program returns of `forward_cached`'s `aux`: what the expert
    layers counted. A looped model's `exit_pdf` stays in the program: at the
    threshold 1 no token's path depends on it, and the compiler drops the
    gate with it."""
    return [value for name, value in aux.items() if name != "exit_pdf"]


def _weights_first(impl, formats, **jit_kwargs):
    """`impl(params, ...)` jitted with `formats` said of its weights' leaves
    (one `Format` for all of them, a tree of them like `params`, or None:
    each leaf as it lies) and nothing said of its other operands."""
    others = sum(p.kind is p.POSITIONAL_OR_KEYWORD for p in
                 inspect.signature(impl).parameters.values()) - 1
    return jax.jit(impl, in_shardings=(formats,) + (None,) * others,
                   **jit_kwargs)


def _as_it_is(leaf):
    return leaf


def _relaid(leaf, chosen: Format):
    """`leaf`'s values in the format `chosen`, waited for. By a program of
    its own that the persistent compile cache never keeps, and NOT by
    `jax.device_put(leaf, chosen)`: read back from that cache, a program
    whose OUTPUT has another layout than the default returns buffers that
    say the default layout over data that lie in the one asked for (JAX
    0.9.0 on a TPU v5e: `device_put`'s identity, written there by a cold
    start that took over a second to compile it, gave every warm start after
    it weights of the wrong values under the wrong label; PERF.md section 6,
    PR 66). The compile is under a second and is paid at every start."""
    kept_from = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", math.inf)
    try:
        program = jax.jit(_as_it_is, out_shardings=chosen).lower(leaf).compile()
    finally:
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", kept_from)
    there = jax.block_until_ready(program(leaf))
    if there.format.layout.major_to_minor != chosen.layout.major_to_minor:
        raise RuntimeError(
            f"a leaf {leaf.shape} asked into {chosen.layout} came back in "
            f"{there.format.layout}")
    return there


class _LaidOutProgram:
    """A program `impl(params, ...)` that says in what layout ON THE DEVICE
    it reads each leaf of its weights. `compile_for` compiles it once, ahead
    of its first call, with `Layout.AUTO` asked for every leaf of `params`:
    the compiler chooses the tiling the program's products read (a
    projection kept `[in, heads, D]` tiled by head, where the default tiling
    has it copied into that one every call), by the shapes it is given and
    nothing else; the other operands keep what they have. `formats` is what
    it chose: a tree of `Format`s like `params`, the layout None for a leaf
    the program does not read. A CALL runs that executable, so the weights
    must lie in `formats` (`_relaid(leaf, format)`: the caller's to see
    to; logical shapes, dtypes and values are what they were).
    Everything else asked of it (`.lower`, ...) is asked of a `jit` that
    carries `formats` for `params`: lowered with plain shapes, it compiles
    the program that runs, which is how the benchmark's readers and
    `tests/test_chip_compile.py` map device operations to scopes. On a bare
    instance (no engine, no weights) the first `.lower` compiles for the
    shapes it is given."""

    def __init__(self, impl, **jit_kwargs):
        self._impl, self._jit_kwargs = impl, jit_kwargs
        self.formats = self._run = self._jit = None

    def compile_for(self, params, *others):
        """Compile for weights like `params` (arrays or shapes; a leaf's
        sharding is kept) and `others`; returns `formats`."""
        like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=getattr(a, "sharding", None)), params)
        self._run = _weights_first(
            self._impl, Format(Layout.AUTO), **self._jit_kwargs).lower(
                like, *others).compile()
        self.formats = self._run.input_formats[0][0]
        self._jit = _weights_first(
            self._impl, self.formats, **self._jit_kwargs)
        return self.formats

    def lower(self, *args):
        if self._jit is None:
            self.compile_for(*args)
        return self._jit.lower(*args)

    def __call__(self, *args):
        return self._run(*args)

    def _cache_size(self) -> int:
        """Executables compiled to RUN: the one, and whatever the `jit` was
        called for (nothing, on the pump's path)."""
        return (self._run is not None) + self._jit._cache_size()


class PrefillPrograms:
    """A prompt through the model, one compiled program per length bucket:
    all a prefill replica runs (`models/disagg_prefill.py`), and where the
    scheduler below gets its prefill."""

    def __init__(self, cfg: TransformerConfig, params, max_len: int):
        """Alone (a prefill replica) there is no decode program to say in
        what layout the weights are read: `params` stay as they lie, in the
        default layouts, and the programs are compiled for those."""
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self._jit_programs()

    def _jit_programs(self) -> None:
        self._prefill_jits: Dict[int, Any] = {}
        # what the programs are told of the weights' layouts on the device:
        # nothing, until a decode program has chosen
        # (`ContinuousBatcher._compile_decode`)
        self._formats = None
        # `moe_grouped_path`, `prefill_attention_path`, ...: jitted program
        # -> the implementation(s) each choice of `traced.TOLD` fell on
        # while it was traced (`_traced_with`). `engine_stats()` carries them
        for told in traced.TOLD.values():
            setattr(self, told, {})

    def _traced_with(self, program: str, seen: dict) -> None:
        """Books what `program`'s trace chose (`traced.booked`'s `seen`)
        under each choice's attribute: a program that fell back says so in
        one look. New dicts, not updates in place: a reader may be copying
        the old one."""
        for choice, paths in seen.items():
            told = traced.TOLD[choice]
            setattr(self, told, {**getattr(self, told),
                                 program: "+".join(sorted(paths))})

    def _prefill_impl(self, params, tokens, length):
        """[1, S] prompt -> (last_logits [V], row_k, row_v [L, S, kvH, D])
        against a standalone single-row cache. Next comes what else the
        model keeps a sequence (`_row_of`): a stateful attention's state
        [L, ...] as the prompt's TRUE last position left it; a layer
        pattern's window layers' ring rows (ring_k, ring_v [window layers,
        window, kvH, D]: the last `window` positions of the prompt's TRUE
        length), or its matrix states, convolution windows (at the TRUE
        last position) and latent rows; a sparse model's then what its
        expert layers counted (`forward_cached`'s `aux`), the experts' load
        [E] from the prompt's real positions first (a dense model's callers
        unpack three)."""
        s = tokens.shape[1]
        row_cache = init_cache(self.cfg, 1, s)
        positions = jnp.arange(s)[None, :]
        kv_mask = jnp.arange(s)[None, :] < length
        with traced.booked() as seen:
            logits, row_cache, aux = forward_cached(
                self.cfg, params, tokens, positions, row_cache, kv_mask,
                kv_mask)
        self._traced_with(f"prefill_{s}", seen)
        last = jnp.take_along_axis(
            logits, (length - 1)[:, None, None].repeat(
                logits.shape[-1], -1), axis=1)[:, 0]
        return last[0], *self._row_of(row_cache), *_counted(aux)

    @staticmethod
    def _row_of(row_cache: KVCache) -> tuple:
        """A one-sequence cache as a prefill program returns it: (row_k,
        row_v), then whatever else the model keeps a sequence, in the
        cache's own order (`KEPT`): a stateful attention's state; a layer
        pattern's ring, or its matrix states, convolution windows and latent
        rows."""
        return row_cache.k[:, 0], row_cache.v[:, 0], *(
            getattr(row_cache, name)[:, 0] for name in KEPT
            if getattr(row_cache, name) is not None)

    @staticmethod
    def _bucket(n: int) -> int:
        b = 16
        while b < n:
            b *= 2
        return b

    def _prefill_program(self, bucket: int):
        """The bucket's prefill program, compiled at its first prompt, for
        the weights as they lie (`_formats`)."""
        pf = self._prefill_jits.get(bucket)
        if pf is None:
            pf = self._prefill_jits[bucket] = FirstCall(
                _weights_first(self._prefill_impl, self._formats),
                f"prefill_{bucket}", self._prefill_jits, bucket)
        return pf

    def _prefill(self, tokens: Sequence[int]):
        """One prompt through its bucket's program: what `_prefill_impl`
        returns, the rows of bucket length."""
        bucket = min(self._bucket(len(tokens)), self.max_len)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, : len(tokens)] = tokens
        return self._prefill_program(bucket)(
            self.params, jnp.asarray(toks),
            jnp.asarray([len(tokens)], np.int32))


class ContinuousBatcher(PrefillPrograms):
    """Iteration-level scheduler, and the fixed-slot KV cache under it."""

    def __init__(self, cfg: TransformerConfig, params, max_len: int = 512,
                 slots: int = 8, seed: int = 0):
        self.slots = slots
        super().__init__(cfg, params, max_len)
        # before the cache is there: a leaf that moves is twice on the
        # device while it moves. The decode program's ONE
        # `ray_tpu.setup.program` phase is this: trace_s, lower_s and
        # compile_s its compile ahead of the first step, first_run_s the
        # weights' re-lay
        with program_phase("decode") as attrs:
            relaid = self._lay_weights()
            attrs.update(weights_relaid=sorted(relaid["weights_relaid"]),
                         weights_relaid_bytes=relaid["weights_relaid_bytes"])
        self._waiting: "queue.Queue[_Request]" = queue.Queue()
        # scheduler state (_active/_free/_host_len/...) is confined to
        # the pump thread; only _waiting and stats cross threads
        self._active: Dict[int, _Request] = {}
        self._free = list(range(slots))
        self._inflight: Optional[_Dispatched] = None
        self._wake = threading.Event()
        self._shutdown = False
        self._rng = jax.random.key(seed)
        with setup_phase("ray_tpu.setup.engine.cache") as attrs:
            self.cache = jax.block_until_ready(self._empty_cache())
            attrs["bytes"] = sum(
                leaf.nbytes for leaf in jax.tree.leaves(self.cache))
        # per-slot host-side state (no device sync on the emit path)
        self._temps = np.zeros(slots, np.float32)
        self._topks = np.zeros(slots, np.int32)
        self._last_tok = np.zeros(slots, np.int32)
        # rows in the slot once every dispatched step has run: where the
        # next step writes
        self._host_len = np.zeros(slots, np.int64)
        # tokens the slot's request may still be given here, by its
        # `max_tokens` and by the slot's rows: the count that ends it
        self._left = np.zeros(slots, np.int64)
        # what a step takes that changes at an admit or a retire only, as
        # it was last uploaded: name -> (host value, device array)
        self._uploaded: Dict[str, tuple] = {}
        # stats (observable by tests/metrics). `weights_relaid`: {leaf of
        # `params`: the `major_to_minor` it was put in} of the leaves the
        # decode program reads in another layout than they were given in
        # (`_lay_weights`; none where the compiler chooses the default, as
        # on the CPU), `weights_relaid_bytes`: their bytes; `steps_ahead`:
        # steps dispatched while the step before them was unread;
        # `steps_sampled` / `steps_sorted`: steps in which an active row had
        # a temperature / a temperature and a top-k, so that sampling drew /
        # sorted the vocabulary; `tokens_discarded`: slot-steps thrown away
        # behind a stop token; `kv_rows_held` / `kv_rows_read`: the cache
        # rows the steps' sequences held, over all layers, and the rows the
        # steps' attention read for them (`_kv_rows`); `pump_step_s`: wall
        # seconds of the pump's passes (`engine.step`), `pump_sync_s`: of
        # them, the two waits for the device (`sample_sync`,
        # `first_token_sync`), `pump_cpu_s`: the pump thread's own CPU
        # seconds in them, all three booked together (`_book`). step - sync
        # - cpu is the time the pump wanted to run and did not: the GIL, or
        # a core it did not get
        self.stats = {"admitted": 0, "finished": 0, "failed": 0,
                      "steps": 0, "max_active": 0, "tokens_out": 0,
                      "last_admit_step": -1, "steps_ahead": 0,
                      "steps_sampled": 0, "steps_sorted": 0,
                      "tokens_discarded": 0, "kv_rows_held": 0,
                      "kv_rows_read": 0, "pump_step_s": 0.0,
                      "pump_sync_s": 0.0, "pump_cpu_s": 0.0, **relaid}
        # of the passes since the last booking (`_book`): their wall time
        # and their waits for the device, the thread's CPU clock when the
        # first of them began, and the passes ever made
        self._step_s = self._sync_s = 0.0
        self._cpu_at: Optional[float] = None
        self._passes = 0
        if cfg.stateful:
            # prefills whose state went into a slot with their rows, and
            # slots whose state was cleared when their request left
            self.stats.update(state_installs=0, state_resets=0)
            # bytes of state the decode steps read and wrote back: every
            # state of a step's active sequences once each way (`cfg.kept`);
            # a free slot's state is kept as it is
            self.stats["state_bytes_rewritten"] = 0
            self._state_bytes = 2 * sum(
                kept.layers * math.prod(kept.shape)
                * jnp.dtype(kept.dtype or cfg.dtype).itemsize
                for kept in cfg.kept(max_len) if kept.rows is None)
        # A list while someone wants to know which expert each row was given
        # (a program's `expert_choice`, from a router that gives one, or the
        # k of a layer pattern's): every admit and every step read appends
        # ({slot: request}, choices [layers, rows] or [layers, rows, k]);
        # rows are a prefill's positions or a step's slots. None: nothing
        # is kept.
        self.route_log: Optional[list] = None
        if cfg.num_experts:
            # what the experts received from real rows (prompt positions,
            # active slots) and how many such rows there were, so that
            # assignments / (rows x layers) reads experts_per_token exactly
            # unless an assignment was dropped
            self.stats.update(moe_expert_load=[0] * cfg.router_outputs,
                              moe_assignments=0, moe_rows=0)
        if cfg.experts_held:
            # of those assignments, the ones to experts held here (the rest
            # are the other chips' of the layer), and the held experts that
            # had at least one real row, summed over layers and decode steps:
            # what the steps' grouped matmuls had to read; the rows the
            # programs GATHERED for their grouped matmuls
            # (`transformer.rows_gathered`: pad rows' and free slots'
            # choices among them), against which the held assignments are
            # the rows that met a weight, and how many of their calls held
            # more than `held_rows_cap` and took the whole layout: a capped
            # program counts both; without a cap the layout is static, every
            # assignment of the rows the program ran, and never gives way
            self.stats.update(moe_assignments_held=0, moe_experts_reached=0,
                              moe_rows_gathered=0, moe_calls_whole_layout=0)
        if cfg.zero_experts:
            # of the real rows' choices, those on zero-compute outputs (their
            # weight times the input, no matmul) and on routed experts that
            # are not held here; and, summed over the programs, the most
            # routed experts one real row chose in one layer
            # (`aux["routed_most"]`)
            self.stats.update(moe_assignments_zero=0, moe_assignments_absent=0,
                              moe_routed_most=0)
        self._thread = threading.Thread(
            target=self._pump, daemon=True, name="cb-pump")
        self._thread.start()

    # -- public API -----------------------------------------------------
    def submit(self, tokens: Sequence[int],
               sampling: Optional[SamplingParams] = None) -> Future:
        """Thread-safe: enqueue one request; resolves to List[int]."""
        return self._enqueue(_Request(
            list(tokens) or [0], sampling or SamplingParams(), Future(),
            None)).future

    def submit_stream(self, tokens: Sequence[int],
                      sampling: Optional[SamplingParams] = None):
        """Yields token ids as they are emitted; `.backlog()` of what is
        returned: the ids the pump has emitted and nobody has taken yet."""
        return _TokenStream(self, _Request(
            list(tokens) or [0], sampling or SamplingParams(), None,
            queue.Queue()))

    def _enqueue(self, req: _Request) -> _Request:
        if self._shutdown:
            raise RuntimeError(f"{type(self).__name__} was shut down")
        self._check_len(req)
        self._waiting.put(req)
        self._wake.set()
        return req

    def shutdown(self) -> None:
        self._shutdown = True
        self._wake.set()
        self._thread.join(timeout=10.0)
        # outstanding work can never run now: resolve it with an error
        # instead of hanging its callers
        err = RuntimeError(f"{type(self).__name__} was shut down")
        leftovers = list(self._active.values())
        while not self._waiting.empty():
            try:
                leftovers.append(self._waiting.get_nowait())
            except queue.Empty:
                break
        for req in leftovers:
            if req.future is not None and not req.future.done():
                req.future.set_exception(err)
            if req.stream_q is not None:
                req.stream_q.put(None)

    def _check_len(self, req: _Request) -> None:
        if len(req.tokens) >= self.max_len:
            raise ValueError(
                f"prompt length {len(req.tokens)} >= max_len "
                f"{self.max_len}")

    # -- device programs ------------------------------------------------
    def _jit_programs(self) -> None:
        """The programs as the pump runs them. Install and the decode step
        are given the cache to keep (donated): each changes a few rows of
        it in place, `self.cache` is replaced by what they return, and no
        one may hold the cache that went in.

        The decode step says in what layout on the device it reads the
        weights (`_LaidOutProgram`): `Layout.AUTO` is asked of every leaf of
        `params` and of nothing else (the cache, the tokens and the rest
        keep what they have), once, when the program is compiled: at the
        engine's build, ahead of the first step and before the cache is
        allocated (`_compile_decode`, and `_lay_weights` puts the leaves
        that differ there), or on a bare instance at the first `.lower`.
        From then on `_decode_jit` and every prefill program carry the
        chosen formats for `params`, so `.lower(<plain shapes>)` of either
        compiles the program that runs. Where a prefill would read a leaf
        in another tiling, the decode program's choice holds: a sequence
        is one prefill and hundreds of steps."""
        super()._jit_programs()
        self._decode_jit = _LaidOutProgram(
            self._decode_impl, donate_argnums=(2,))
        self._install_jit = FirstCall(
            jax.jit(self._install_impl, donate_argnums=(0,)), "install",
            self.__dict__, "_install_jit")
        self._reset_state_jit = FirstCall(
            jax.jit(self._reset_state_impl, donate_argnums=(0,)),
            "reset_state", self.__dict__, "_reset_state_jit")

    def _step_shapes(self) -> tuple:
        """What `_decode` gives a step behind the weights, as shapes."""
        def per_slot(dtype):
            return jax.ShapeDtypeStruct((self.slots,), dtype)

        return (per_slot(jnp.int32), jax.eval_shape(self._empty_cache),
                jax.eval_shape(lambda: jax.random.key(0)),
                per_slot(jnp.float32), per_slot(jnp.int32),
                per_slot(jnp.bool_))

    def _compile_decode(self, params):
        """Compile the decode step for weights like `params` (arrays or
        shapes) in the layouts it chooses for them; the prefill programs
        made from here on are compiled for the same."""
        self._formats = self._decode_jit.compile_for(
            params, *self._step_shapes())

    def _lay_weights(self) -> dict:
        """Compile the decode step and put every leaf of `params` that it
        reads in another layout than the leaf lies in there (`_relaid`), one
        leaf at a time, the buffer it was given in deleted before the next
        moves: never two copies of a stack. `self.params` is the tree that
        results: the structure, shapes, dtypes and values it was given, and
        the ONE tree a process should hold (a holder of the tree that was
        given holds deleted leaves where one moved). Returns what `stats`
        says of it."""
        self._compile_decode(self.params)
        moved = {}

        def lay(path, leaf, chosen):
            if chosen.layout is None or not isinstance(leaf, jax.Array) \
                    or leaf.format == chosen:
                return leaf
            there = moved[jax.tree_util.keystr(
                path, simple=True, separator="/")] = _relaid(leaf, chosen)
            leaf.delete()
            return there

        self.params = jax.tree_util.tree_map_with_path(
            lay, self.params, self._formats)
        return {"weights_relaid": {
                    name: list(leaf.format.layout.major_to_minor)
                    for name, leaf in moved.items()},
                "weights_relaid_bytes": sum(
                    leaf.nbytes for leaf in moved.values())}

    def _install_impl(self, cache: KVCache, row_k, row_v, slot, length,
                      *kept):
        """Scatter a prefilled row into its slot of the big cache (the
        row is padded to max_len, so the whole slot — including stale
        data from its previous occupant — is overwritten), and with it
        whatever else the prefill returned for the sequence, `kept` in the
        cache's own order (`KEPT`: a state [L, ...], a ring pair [window
        layers, window, kvH, D], matrix states, convolution windows, latent
        rows; None where the model keeps none): each replaces the slot's
        whole part of its stack."""
        k = jax.lax.dynamic_update_slice(
            cache.k, row_k[:, None], (0, slot, 0, 0, 0))
        v = jax.lax.dynamic_update_slice(
            cache.v, row_v[:, None], (0, slot, 0, 0, 0))
        lengths = cache.lengths.at[slot].set(length)
        return KVCache(k, v, lengths, *self._slot_fields(cache, slot, kept))

    @staticmethod
    def _slot_fields(cache: KVCache, slot, rows=None) -> list:
        """The one walk over what a cache keeps a slot behind `k` / `v`: its
        stacks in `KEPT`'s order ([layers, slots, ...] each, None where the
        model keeps none) with `slot`'s part replaced. By a prefill's `rows`
        ([layers, ...] each, in the same order, None or missing: left as it
        is): the whole part, rows of a bucket shorter than the slot followed
        by zeros, so that nothing of the last occupant is left. Without
        `rows`, a slot given back: what every step reads and rewrites
        (`decoding.STATES`) returns to a new sequence's zeros; rows stay,
        masked by the slot's length until the next install overwrites them."""
        out = []
        for i, name in enumerate(KEPT):
            stack = getattr(cache, name)
            row = rows[i] if rows is not None and i < len(rows) else None
            if stack is not None and row is not None:
                if name not in STATES and row.shape[1] < stack.shape[2]:
                    row = jnp.pad(row, ((0, 0), (0, stack.shape[2]
                                                 - row.shape[1]))
                                  + ((0, 0),) * (row.ndim - 2))
                stack = stack.at[:, slot].set(row.astype(stack.dtype))
            elif stack is not None and rows is None and name in STATES:
                stack = stack.at[:, slot].set(0)
            out.append(stack)
        return out

    def _reset_state_impl(self, cache: KVCache, slot):
        """`slot`'s states back to a new sequence's (zeros)."""
        return cache._replace(
            **dict(zip(KEPT, self._slot_fields(cache, slot))))

    def _decode_impl(self, params, toks, cache, rng, temps, topks,
                     active_mask, *, access=_write_stack):
        """One token for every slot. `access` is where the layers' K/V
        rows live (`forward_cached`): the slots' stack here."""
        positions = cache.lengths[:, None]
        kv_mask = jnp.arange(self.max_len)[None, :] <= \
            cache.lengths[:, None]
        # what each slot holds once its token is written: the same prefix as
        # `kv_mask`, stated as a count; a slot that takes no part holds none
        rows = jnp.where(active_mask, cache.lengths + 1, 0)
        with traced.booked() as seen:
            logits, cache, aux = forward_cached(
                self.cfg, params, toks[:, None], positions, cache, kv_mask,
                active_mask[:, None], access, rows)
        self._traced_with("decode", seen)
        with jax.named_scope("sample"):
            nxt = _sample_per_slot(
                logits[:, 0], rng, temps, topks, active_mask)
        # only ACTIVE slots advance (their state too: `forward_cached` was
        # given the mask); free rows stay put so a later install never
        # races a drifting length past max_len
        new_len = jnp.where(active_mask, cache.lengths + 1, cache.lengths)
        return nxt, cache._replace(lengths=new_len), *_counted(aux)

    def _pad_row(self, row_k, row_v):
        """A prefilled row [L, S, kvH, D] out to max_len, as install takes
        it (keys and values each by their own row's shape)."""
        pad = self.max_len - row_k.shape[1]
        if pad <= 0:
            return row_k, row_v
        return tuple(jnp.concatenate([row, jnp.zeros(
            row.shape[:1] + (pad,) + row.shape[2:], row.dtype)], axis=1)
            for row in (row_k, row_v))

    # -- the cache: fixed slots ------------------------------------------
    # The six methods the scheduler reaches K/V rows through. It calls them
    # for every request and every step and never asks which cache answers.
    def _empty_cache(self):
        """The device cache with nothing in it: at construction, and again
        after a step that raised."""
        return init_cache(self.cfg, self.slots, self.max_len)

    def _prefill_into(self, req: _Request, slot: int):
        """Put the prompt's K/V (and state) into `slot`. Returns (logits at
        its last position [V], what the program's expert layers counted,
        the rows that were computed). What it takes for the request it
        takes before it can fail or leaves with the request, for `_release`
        to give back."""
        with device_span(spans.ENGINE_PREFILL_DISPATCH):
            last_logits, row_k, row_v, *rest = self._prefill(req.tokens)
            state, load = self._row_state(rest)
            self._fetch_ahead(load)
        with device_span(spans.ENGINE_INSTALL_DISPATCH):
            self.cache = self._install_jit(
                self.cache, *self._pad_row(row_k, row_v), slot,
                len(req.tokens), *state)
        return last_logits, load, len(req.tokens)

    def _row_state(self, rest: list):
        """What a prefill program returned after its rows, split into what
        install takes behind them (one entry a field of `KEPT` up to the
        last the model keeps, None where it keeps none: `cfg.keeps` says
        which the program returned; nothing for a model that keeps rows
        alone) and what the expert layers counted; a state is counted as
        installed."""
        names = [name for name in KEPT if name in self.cfg.keeps]
        given = dict(zip(names, rest))
        if self.cfg.stateful:
            self.stats["state_installs"] += 1
        last = max((KEPT.index(name) + 1 for name in names), default=0)
        return [given.get(name) for name in KEPT[:last]], rest[len(names):]

    def _decode(self, toks, rng, temps, topks, active_mask):
        """One decode step over the cache, which the step keeps. Returns
        (sampled tokens [slots], the program's expert load)."""
        toks, self.cache, *load = self._decode_jit(
            self.params, toks, self.cache, rng, temps, topks, active_mask)
        return toks, load

    def _kv_rows(self, lens: np.ndarray):
        """(held, read): the cache rows a decode step
        must read for sequences of `lens` rows (the step's own among them),
        summed over the layers that keep rows (`cfg.kept`: K and V one row;
        a ring holding its window at most, a latent layer one row a
        position, a state none); and what the step's attention copies for
        them: a slot's K and V rows in whole granules
        (`ops.attention.decode_rows_copied`, which `decode_attention` copies
        by; whole blocks before PR 65), a latent layer's in whole blocks
        (`latent_decode_attention`); a free slot nothing. Before PR
        35 a step read `slots x max_len` a full layer and `slots x window`
        a window layer whatever was held."""
        itemsize = jnp.dtype(self.cfg.dtype).itemsize
        held = read = 0
        for kept in self.cfg.kept(self.max_len):
            if kept.rows is None:  # a state: no rows
                continue
            rows = np.minimum(lens, kept.rows)  # a ring holds its window
            held += kept.layers * int(rows.sum())
            row_bytes = math.prod(kept.shape) * itemsize
            if kept.fields == ("latent",):
                block = decode_block(kept.rows, row_bytes)
                copied = -(-rows // block) * block
            else:
                copied = decode_rows_copied(rows, kept.rows, row_bytes)
            read += kept.layers * int(copied.sum())
        return held, read

    def _release(self, req: _Request) -> None:
        """Give back what the cache holds for a request that leaves its
        slot or never got one. A slot's rows are overwritten by the next
        install and masked until then: nothing. A stateful model's slot
        (`_slot_fields`: whatever its layers read and rewrite every step) goes
        back to a new sequence's zeros at once (behind whatever step is in
        flight: the device runs them in order). No result depends on it: a
        step keeps a free slot's state as it is and the next install writes
        over it; a slot nobody holds then holds nobody's data, and
        `state_resets` beside `state_installs` shows every slot that was
        taken was given back."""
        self._reset_state(req.slot)

    def _reset_state(self, slot: int) -> None:
        if self.cfg.stateful and slot >= 0:
            self.cache = self._reset_state_jit(self.cache, slot)
            self.stats["state_resets"] += 1

    def _make_room(self) -> None:
        """Before a step: every slot it advances (`_next_slots`) can take
        one more row. A cache that has to take a slot back for that reads
        the step in flight first (`_drain`): the request it requeues must
        hold every token computed for it. A slot is `max_len` rows long
        and its request is counted out at the last: nothing."""

    def _refused_for_now(self, req: _Request, e: Exception) -> bool:
        """Whether a failed admit is the cache saying "not yet": the
        request then keeps its place at the head of the queue. A free slot
        is all a request needs here: never."""
        return False

    # -- scheduler ------------------------------------------------------
    def _admit(self) -> bool:
        admitted = False
        while self._free and not self._waiting.empty():
            try:
                req = self._waiting.get_nowait()
            except queue.Empty:
                break
            slot = self._free.pop()
            try:
                self._admit_one(req, slot)
            except Exception as e:  # noqa: BLE001 — e.g. compile OOM
                # the slot and what the cache took go back
                self._free.append(slot)
                self._release(req)
                if self._refused_for_now(req, e):
                    # to the FRONT (FIFO position kept — a tail requeue
                    # would let every later small request leapfrog a big
                    # one forever, its future never resolving), and no
                    # more admits this step: retiring sequences make room
                    # and the pump runs _admit every step
                    with self._waiting.mutex:
                        self._waiting.queue.appendleft(req)
                        self._waiting.not_empty.notify()
                    break
                # THIS request fails; others and the pump survive
                self._fail(req, e)
                continue
            admitted = True
        self.stats["max_active"] = max(self.stats["max_active"],
                                       len(self._active))
        return admitted

    def _admit_one(self, req: _Request, slot: int) -> None:
        # the prompt's bucket; a cache that finds part of the prompt already
        # there (`PagedBatcher`) runs the remainder's
        bucket = min(self._bucket(len(req.tokens)), self.max_len)
        with device_span(
                spans.ENGINE_ADMIT, bucket=bucket,
                prompt_len=len(req.tokens),
                queued_ms=(time.monotonic() - req.t_submit) * 1e3):
            last_logits, load, rows = self._prefill_into(req, slot)
            t_sync = time.perf_counter()
            with device_span(spans.ENGINE_FIRST_TOKEN_SYNC):
                self._rng, k = jax.random.split(self._rng)
                first = _sample_first(
                    last_logits[None], k,
                    jnp.asarray([req.sampling.temperature], np.float32),
                    jnp.asarray([req.sampling.top_k], np.int32),
                    np.ones(1, bool))
                first_tok = int(np.asarray(first)[0])
                self._count_experts(load, rows)
                self._log_routes(load, {slot: req})
            self._sync_s += time.perf_counter() - t_sync
            # inside the span: an admit is over when its first token is out
            req.slot = slot
            self.stats["last_admit_step"] = self.stats["steps"]
            self._temps[slot] = req.sampling.temperature
            self._topks[slot] = req.sampling.top_k
            self._host_len[slot] = len(req.tokens)
            # the first token and one a step, a step a row: a prompt of
            # `max_len` (a preempted request come back) gets the first alone
            self._left[slot] = min(
                req.sampling.max_tokens - len(req.out),
                self.max_len + 1 - len(req.tokens))
            self._last_tok[slot] = first_tok
            self._active[slot] = req
            self.stats["admitted"] += 1
            self._emit(req, first_tok)

    @staticmethod
    def _fetch_ahead(arrays: list) -> None:
        """Start the arrays' copies to the host with the program's dispatch:
        a step's tokens are then on their way while the next step runs, and
        reading the expert load after them costs no second round trip."""
        for array in arrays:
            array.copy_to_host_async()

    def _count_experts(self, load: list, rows: int,
                       step: bool = False) -> None:
        """Add a program's expert load (`[load]`; `[]` from a dense model's
        program) to the counters. Called where the program's tokens have
        just been copied to the host, so the load is ready (`_fetch_ahead`)
        and this is no sync point of its own. `step`: a decode step's, whose
        reached experts (`[load, choices, counted]` from a held share:
        `pattern.sparse_mlp`'s reached and, from a capped layout, rows
        gathered and whole-layout calls) are what its grouped matmuls read;
        a prefill reaches them all."""
        if not load:
            return
        loads, load = load, np.asarray(load[0])
        # a new list, not an update in place: `engine_stats` copies the
        # dict from another thread and must see one state
        self.stats["moe_expert_load"] = [
            a + b for a, b in zip(self.stats["moe_expert_load"],
                                  load.tolist())]
        self.stats["moe_assignments"] += int(load.sum())
        self.stats["moe_rows"] += rows
        if self.cfg.experts_held:
            first, count = self.cfg.experts_held
            self.stats["moe_assignments_held"] += int(
                load[first:first + count].sum())
            counted = np.asarray(loads[2])
            if counted.ndim:
                reached, gathered, whole = counted.tolist()
            else:  # no cap: k rows a row of the program, a sparse layer
                ran = self.slots if step else min(self._bucket(rows),
                                                  self.max_len)
                reached, gathered, whole = int(counted), ran \
                    * self.cfg.experts_per_token * self.cfg.sparse_layers, 0
            if step:
                self.stats["moe_experts_reached"] += reached
            self.stats["moe_rows_gathered"] += gathered
            self.stats["moe_calls_whole_layout"] += whole
        if self.cfg.zero_experts:
            first, count = self.cfg.experts_held or (0, self.cfg.num_experts)
            on_zero = int(load[self.cfg.num_experts:].sum())
            self.stats["moe_assignments_zero"] += on_zero
            self.stats["moe_assignments_absent"] += int(
                load.sum() - on_zero - load[first:first + count].sum())
            self.stats["moe_routed_most"] += int(np.asarray(loads[3]))

    def _log_routes(self, counted: list, reqs: Dict[int, _Request]) -> None:
        """Keep a program's `expert_choice` (`counted[1]`, behind the load)
        for whoever set `route_log`; called where `_count_experts` is."""
        if self.route_log is not None and len(counted) > 1:
            self.route_log.append((dict(reqs), np.asarray(counted[1])))

    def _emit(self, req: _Request, tok: int) -> None:
        """Deliver one sampled token; free the slot when the request is
        done: at a stop token, or counted out (`_left`: max_tokens, or the
        slot's last row — the NEXT decode would write at position
        `max_len`, matching Generator.generate's lengths >= max_len
        stop)."""
        stop = req.sampling.stop_token_id
        if stop is not None and tok == stop:
            done = True
        else:
            req.out.append(int(tok))
            if req.stream_q is not None:
                req.stream_q.put(int(tok))
            self.stats["tokens_out"] += 1
            self._left[req.slot] -= 1
            done = self._left[req.slot] <= 0
        if done:
            self._retire(req)

    def _vacate(self, req: _Request) -> None:
        """The request leaves its slot (done, failed or preempted)."""
        if req.slot >= 0:
            self._release(req)
            self._active.pop(req.slot, None)
            self._free.append(req.slot)
            req.slot = -1

    def _retire(self, req: _Request) -> None:
        self._vacate(req)
        self.stats["finished"] += 1
        if req.future is not None and not req.future.done():
            req.future.set_result(list(req.out))
        if req.stream_q is not None:
            req.stream_q.put(None)

    def _fail(self, req: _Request, e: BaseException) -> None:
        """Resolve a request with the device-side failure: its caller —
        future or stream — sees the exception, and it is counted."""
        self.stats["failed"] += 1
        if req.future is not None and not req.future.done():
            req.future.set_exception(e)
        if req.stream_q is not None:
            req.stream_q.put(e)

    def _pump(self) -> None:
        while not self._shutdown:
            if not self._active and self._inflight is None \
                    and self._waiting.empty():
                self._book()
                with device_span(spans.ENGINE_IDLE):
                    self._wake.wait(timeout=0.1)
                self._wake.clear()
                continue
            try:
                self._timed_step()
            except Exception as e:  # noqa: BLE001 — fail active requests
                # a step in flight that is whole (`_step` leaves none that
                # follows a failed read) was dispatched before the one that
                # raised: its tokens go out first, so the requests fail
                # holding what they would hold had no step run ahead
                try:
                    self._drain()
                except Exception:  # noqa: BLE001 — the device is gone: `e`
                    pass
                for req in list(self._active.values()):
                    self._fail(req, e)
                    self._vacate(req)
                # the step was given the cache to keep and may have
                # consumed it before it raised. Every slot is free now, so
                # an empty cache is the right state; the old one goes
                # first, two do not fit beside the weights
                self.cache = None
                self.cache = self._empty_cache()
                import logging

                logging.getLogger(__name__).exception(
                    "continuous-batching step failed")

    def _timed_step(self) -> None:
        """One `_step` under its span, with the pass's wall time and its
        waits for the device added to what `_book` will book. The span
        carries the pump's three clocks as last booked, so that a trace
        holds the counters of its own window."""
        st = self.stats
        if self._cpu_at is None:
            self._cpu_at = time.thread_time()
        wall = time.perf_counter()
        # around the call, not inside it: the step's device arrays are
        # released when its frame goes, and that is step time
        with device_span(spans.ENGINE_STEP, step=st["steps"],
                         pump_step_s=st["pump_step_s"],
                         pump_sync_s=st["pump_sync_s"],
                         pump_cpu_s=st["pump_cpu_s"]):
            self._step()
        self._step_s += time.perf_counter() - wall
        self._passes += 1
        if self._passes % BOOK_EVERY == 0:
            self._book()

    def _book(self) -> None:
        """Book the passes since the last booking: their wall time, their
        waits for the device and the thread's CPU time over them, all three
        at once (new floats, not updates in place: `engine_stats` copies
        the dict from another thread), so that they cover the same passes
        whenever they are read. Every `BOOK_EVERY` passes and when the pump
        goes idle, not every pass: reading the thread's CPU clock is a system
        call (0.3 us on a plain kernel, 5.6 us on the chip machine's)."""
        if self._cpu_at is None:
            return
        st = self.stats
        st["pump_cpu_s"] += time.thread_time() - self._cpu_at
        st["pump_step_s"] += self._step_s
        st["pump_sync_s"] += self._sync_s
        self._step_s = self._sync_s = 0.0
        self._cpu_at = None  # read again when the next pass begins

    def _next_slots(self) -> List[int]:
        """The slots the next step advances, in order of admission: the
        active ones whose request may be given a token beyond the one in
        flight for it. The others end, by count, when the step in flight
        is read."""
        pending = self._inflight.reqs if self._inflight else ()
        return [s for s in self._active if self._left[s] > (s in pending)]

    def _on_device(self, name: str, host: np.ndarray):
        """`host` as a device array, uploaded when it differs from what
        was uploaded under `name` last."""
        last = self._uploaded.get(name)
        if last is None or not np.array_equal(last[0], host):
            kept = host.copy()  # `host` is written in place at an admit
            last = self._uploaded[name] = (kept, jnp.asarray(kept))
        return last[1]

    def _step(self) -> None:
        """One pass of the pump: dispatch the next decode step, THEN read
        and emit the one before it. First, on a drained loop, whatever is
        due that is not counting."""
        ending = len(self._active) - len(self._next_slots())
        if not self._waiting.empty() and (self._free or ending):
            # an admit is due, now or once the step in flight is read and
            # ends a slot: the request joins the step it joins with no
            # step ahead
            self._drain()
            self._admit()
        self._make_room()
        slots = self._next_slots()
        if not slots:
            # every active slot ends with the step in flight, or the cache
            # took its last slot back
            self._drain()
            return
        ahead = int(self._inflight is not None)
        self._host_len[slots] += 1
        # what the step's sampling does beyond the argmax, by the same rows
        # the program looks at (`_sample_per_slot`): the draw, and the sort
        draws = self._temps[slots] > 0
        sampled = int(draws.any())
        sorts = int((draws & (self._topks[slots] > 0)).any())
        # `rows`: the positions the step's sequences hold, its own among
        # them: what its attention has to read in a full layer;
        # `window_rows`: the rows a window layer's ring holds of them (a
        # layer pattern alone)
        lens = self._host_len[slots]
        held, read = self._kv_rows(lens)
        ring = {"window_rows": int(np.minimum(lens, self.cfg.window).sum())} \
            if self.cfg.window else {}
        with device_span(spans.ENGINE_DECODE_DISPATCH, active=len(slots),
                         ahead=ahead, rows=int(lens.sum()), **ring):
            active_mask = np.zeros(self.slots, bool)
            active_mask[slots] = True
            self._rng, k = jax.random.split(self._rng)
            toks, load = self._decode(
                # the step before's tokens where they are; after a drain
                # the host has them all, an admit's first token among them
                self._inflight.toks if ahead else jnp.array(self._last_tok),
                k, self._on_device("temps", self._temps),
                self._on_device("topks", self._topks),
                self._on_device("active_mask", active_mask))
            self._fetch_ahead([toks, *load])
        newer = _Dispatched(toks, load, {s: self._active[s] for s in slots})
        self.stats["steps"] += 1
        self.stats["steps_ahead"] += ahead
        self.stats["steps_sampled"] += sampled
        self.stats["steps_sorted"] += sorts
        self.stats["kv_rows_held"] += held
        self.stats["kv_rows_read"] += read
        if self.cfg.stateful:
            self.stats["state_bytes_rewritten"] += \
                len(slots) * self._state_bytes
        # nothing is in flight while the step before is read: if that
        # fails, the failure path must not emit `newer` behind the hole
        self._drain()
        self._inflight = newer

    def _drain(self) -> bool:
        """Read and emit the step in flight, if there is one: after it the
        scheduler's state holds every token computed, as if no step ran
        ahead. Returns whether there was one."""
        step, self._inflight = self._inflight, None
        if step is not None:
            self._read(step)
        return step is not None

    def _read(self, step: _Dispatched) -> None:
        t_sync = time.perf_counter()
        with device_span(spans.ENGINE_SAMPLE_SYNC):
            toks_np = np.asarray(step.toks)
            self._count_experts(step.load, len(step.reqs), step=True)
            self._log_routes(step.load, step.reqs)
        self._sync_s += time.perf_counter() - t_sync
        with device_span(spans.ENGINE_EMIT):
            for slot, req in step.reqs.items():
                if self._active.get(slot) is not req:
                    # it ended at a stop token while this step was in
                    # flight: retired as if the step had not run
                    self.stats["tokens_discarded"] += 1
                    continue
                self._last_tok[slot] = tok = int(toks_np[slot])
                self._emit(req, tok)
