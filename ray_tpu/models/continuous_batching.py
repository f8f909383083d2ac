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
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from concurrent.futures import Future
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.decoding import (
    KVCache,
    SamplingParams,
    forward_cached,
    init_cache,
)
from ray_tpu.models.transformer import TransformerConfig
from ray_tpu.observability import schema as spans
from ray_tpu.observability.tracing import device_span
from ray_tpu.ops.attention import NEG_INF


def _sample_per_slot(logits, rng, temps, topks):
    """Vectorized sampling: per-row temperature (0 = greedy) and top-k
    (0 = unfiltered). logits [B, V] -> ids [B]."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    f32 = logits.astype(jnp.float32)
    scaled = f32 / jnp.maximum(temps, 1e-6)[:, None]
    # per-row kth threshold: value at rank (top_k - 1) descending;
    # top_k == 0 disables the filter for that row
    v = logits.shape[-1]
    sorted_desc = -jnp.sort(-scaled, axis=-1)
    idx = jnp.clip(topks - 1, 0, v - 1)[:, None]
    kth = jnp.take_along_axis(sorted_desc, idx, axis=1)
    filtered = jnp.where(
        (topks[:, None] > 0) & (scaled < kth), NEG_INF, scaled)
    sampled = jax.random.categorical(rng, filtered, axis=-1).astype(
        jnp.int32)
    return jnp.where(temps > 0.0, sampled, greedy)


@dataclasses.dataclass
class _Request:
    tokens: List[int]
    sampling: SamplingParams
    future: Optional[Future]
    stream_q: Optional[queue.Queue]  # token stream, None-terminated
    out: List[int] = dataclasses.field(default_factory=list)
    slot: int = -1
    t_submit: float = dataclasses.field(default_factory=time.monotonic)


class ContinuousBatcher:
    """Iteration-level scheduler over a fixed-slot KV cache."""

    def __init__(self, cfg: TransformerConfig, params, max_len: int = 512,
                 slots: int = 8, seed: int = 0):
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.slots = slots
        self._waiting: "queue.Queue[_Request]" = queue.Queue()
        # scheduler state (_active/_free/_host_len/...) is confined to
        # the pump thread; only _waiting and stats cross threads
        self._active: Dict[int, _Request] = {}
        self._free = list(range(slots))
        self._wake = threading.Event()
        self._shutdown = False
        self._rng = jax.random.key(seed)
        self.cache = init_cache(cfg, slots, max_len)
        # per-slot host-side state (no device sync on the emit path)
        self._temps = np.zeros(slots, np.float32)
        self._topks = np.zeros(slots, np.int32)
        self._last_tok = np.zeros(slots, np.int32)
        self._host_len = np.zeros(slots, np.int64)
        # stats (observable by tests/metrics)
        self.stats = {"admitted": 0, "finished": 0, "failed": 0,
                      "steps": 0, "max_active": 0, "tokens_out": 0,
                      "last_admit_step": -1}
        if cfg.num_experts:
            # what the experts received from real rows (prompt positions,
            # active slots) and how many such rows there were, so that
            # assignments / (rows x layers) reads experts_per_token exactly
            # unless an assignment was dropped
            self.stats.update(moe_expert_load=[0] * cfg.num_experts,
                              moe_assignments=0, moe_rows=0)
        self._jit_programs()
        self._thread = threading.Thread(
            target=self._pump, daemon=True, name="cb-pump")
        self._thread.start()

    # -- public API -----------------------------------------------------
    def submit(self, tokens: Sequence[int],
               sampling: Optional[SamplingParams] = None) -> Future:
        """Thread-safe: enqueue one request; resolves to List[int]."""
        if self._shutdown:
            raise RuntimeError("ContinuousBatcher was shut down")
        fut: Future = Future()
        req = _Request(list(tokens) or [0], sampling or SamplingParams(),
                       fut, None)
        self._check_len(req)
        self._waiting.put(req)
        self._wake.set()
        return fut

    def submit_stream(self, tokens: Sequence[int],
                      sampling: Optional[SamplingParams] = None):
        """Yields token ids as they are emitted."""
        if self._shutdown:
            raise RuntimeError("ContinuousBatcher was shut down")
        q: queue.Queue = queue.Queue()
        req = _Request(list(tokens) or [0], sampling or SamplingParams(),
                       None, q)
        self._check_len(req)
        self._waiting.put(req)
        self._wake.set()
        while True:
            t = q.get()
            if t is None:
                return
            if isinstance(t, BaseException):
                raise t  # the admit or the step failed: not a short answer
            yield t

    def shutdown(self) -> None:
        self._shutdown = True
        self._wake.set()
        self._thread.join(timeout=10.0)
        # outstanding work can never run now: resolve it with an error
        # instead of hanging its callers
        err = RuntimeError("ContinuousBatcher was shut down")
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
        one may hold the cache that went in."""
        self._prefill_jits: Dict[int, Any] = {}
        self._decode_jit = jax.jit(self._decode_impl, donate_argnums=(2,))
        self._install_jit = jax.jit(self._install_impl,
                                    donate_argnums=(0,))

    def _prefill_impl(self, params, tokens, length):
        """[1, S] prompt -> (last_logits [V], row_k, row_v [L, S, kvH, D])
        against a standalone single-row cache; a sparse model's program
        returns a fourth value, the experts' load [E] from the prompt's
        real positions (a dense model's callers unpack three)."""
        s = tokens.shape[1]
        row_cache = init_cache(self.cfg, 1, s)
        positions = jnp.arange(s)[None, :]
        kv_mask = jnp.arange(s)[None, :] < length
        logits, row_cache, aux = forward_cached(
            self.cfg, params, tokens, positions, row_cache, kv_mask, kv_mask)
        last = jnp.take_along_axis(
            logits, (length - 1)[:, None, None].repeat(
                logits.shape[-1], -1), axis=1)[:, 0]
        return (last[0], row_cache.k[:, 0], row_cache.v[:, 0],
                *aux.values())

    def _install_impl(self, cache: KVCache, row_k, row_v, slot, length):
        """Scatter a prefilled row into its slot of the big cache (the
        row is padded to max_len, so the whole slot — including stale
        data from its previous occupant — is overwritten)."""
        k = jax.lax.dynamic_update_slice(
            cache.k, row_k[:, None], (0, slot, 0, 0, 0))
        v = jax.lax.dynamic_update_slice(
            cache.v, row_v[:, None], (0, slot, 0, 0, 0))
        lengths = cache.lengths.at[slot].set(length)
        return KVCache(k, v, lengths)

    def _decode_impl(self, params, toks, cache, rng, temps, topks,
                     active_mask):
        positions = cache.lengths[:, None]
        kv_mask = jnp.arange(self.max_len)[None, :] <= \
            cache.lengths[:, None]
        logits, cache, aux = forward_cached(
            self.cfg, params, toks[:, None], positions, cache, kv_mask,
            active_mask[:, None])
        nxt = _sample_per_slot(logits[:, 0], rng, temps, topks)
        # only ACTIVE slots advance; free rows stay put so a later
        # install never races a drifting length past max_len
        new_len = jnp.where(active_mask, cache.lengths + 1, cache.lengths)
        return nxt, KVCache(cache.k, cache.v, new_len), *aux.values()

    # -- scheduler ------------------------------------------------------
    @staticmethod
    def _bucket(n: int) -> int:
        b = 16
        while b < n:
            b *= 2
        return b

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
                # the slot goes back and THIS request fails; others and
                # the pump survive
                self._free.append(slot)
                self._fail(req, e)
                continue
            admitted = True
        self.stats["max_active"] = max(self.stats["max_active"],
                                       len(self._active))
        return admitted

    def _admit_one(self, req: _Request, slot: int) -> None:
        bucket = min(self._bucket(len(req.tokens)), self.max_len)
        with device_span(
                spans.ENGINE_ADMIT, bucket=bucket,
                prompt_len=len(req.tokens),
                queued_ms=(time.monotonic() - req.t_submit) * 1e3):
            self._prefill_into(req, slot, bucket)

    def _prefill_into(self, req: _Request, slot: int, bucket: int) -> None:
        with device_span(spans.ENGINE_PREFILL_DISPATCH):
            toks = np.zeros((1, bucket), np.int32)
            toks[0, : len(req.tokens)] = req.tokens
            pf = self._prefill_jits.get(bucket)
            if pf is None:
                pf = jax.jit(self._prefill_impl)
                self._prefill_jits[bucket] = pf
            last_logits, row_k, row_v, *load = pf(
                self.params, jnp.asarray(toks),
                jnp.asarray([len(req.tokens)], np.int32))
            self._fetch_ahead(load)
        with device_span(spans.ENGINE_INSTALL_DISPATCH):
            # pad the row out to max_len before install
            pad = self.max_len - row_k.shape[1]
            if pad > 0:
                zeros = jnp.zeros(
                    row_k.shape[:1] + (pad,) + row_k.shape[2:],
                    row_k.dtype)
                row_k = jnp.concatenate([row_k, zeros], axis=1)
                row_v = jnp.concatenate([row_v, zeros], axis=1)
            self.cache = self._install_jit(
                self.cache, row_k, row_v, slot, len(req.tokens))
        with device_span(spans.ENGINE_FIRST_TOKEN_SYNC):
            self._rng, k = jax.random.split(self._rng)
            first = _sample_per_slot(
                last_logits[None], k,
                jnp.asarray([req.sampling.temperature], np.float32),
                jnp.asarray([req.sampling.top_k], np.int32))
            first_tok = int(np.asarray(first)[0])
            self._count_experts(load, len(req.tokens))
        req.slot = slot
        self.stats["last_admit_step"] = self.stats["steps"]
        self._temps[slot] = req.sampling.temperature
        self._topks[slot] = req.sampling.top_k
        self._host_len[slot] = len(req.tokens)
        self._last_tok[slot] = first_tok
        self._active[slot] = req
        self.stats["admitted"] += 1
        self._emit(req, first_tok)

    @staticmethod
    def _fetch_ahead(load: list) -> None:
        """Start the load's copy to the host with the program's dispatch, so
        that reading it after the tokens costs no second round trip."""
        for array in load:
            array.copy_to_host_async()

    def _count_experts(self, load: list, rows: int) -> None:
        """Add a program's expert load (`[load]`; `[]` from a dense model's
        program) to the counters. Called where the program's tokens have
        just been copied to the host, so the load is ready (`_fetch_ahead`)
        and this is no sync point of its own."""
        if not load:
            return
        load = np.asarray(load[0])
        # a new list, not an update in place: `engine_stats` copies the
        # dict from another thread and must see one state
        self.stats["moe_expert_load"] = [
            a + b for a, b in zip(self.stats["moe_expert_load"],
                                  load.tolist())]
        self.stats["moe_assignments"] += int(load.sum())
        self.stats["moe_rows"] += rows

    def _emit(self, req: _Request, tok: int) -> None:
        """Deliver one sampled token; free the slot when the request is
        done (stop token / max_tokens / out of cache room)."""
        stop = req.sampling.stop_token_id
        done = False
        if stop is not None and tok == stop:
            done = True
        else:
            req.out.append(int(tok))
            if req.stream_q is not None:
                req.stream_q.put(int(tok))
            self.stats["tokens_out"] += 1
            if len(req.out) >= req.sampling.max_tokens:
                done = True
        # prompt_len + emitted tokens occupy the row; the NEXT decode
        # writes at position lengths[slot], which must stay < max_len —
        # matching Generator.generate's lengths >= max_len stop
        if not done and req.slot >= 0:
            if self._host_len[req.slot] >= self.max_len:
                done = True
        if done:
            self._retire(req)

    def _retire(self, req: _Request) -> None:
        if req.slot >= 0:
            self._active.pop(req.slot, None)
            self._free.append(req.slot)
            req.slot = -1
        self.stats["finished"] += 1
        if req.future is not None and not req.future.done():
            req.future.set_result(list(req.out))
        if req.stream_q is not None:
            req.stream_q.put(None)

    def _pump(self) -> None:
        while not self._shutdown:
            if not self._active and self._waiting.empty():
                with device_span(spans.ENGINE_IDLE):
                    self._wake.wait(timeout=0.1)
                self._wake.clear()
                continue
            try:
                # around the call, not inside it: the step's device arrays
                # are released when its frame goes, and that is step time
                with device_span(spans.ENGINE_STEP, step=self.stats["steps"]):
                    self._step()
            except Exception as e:  # noqa: BLE001 — fail active requests
                for req in list(self._active.values()):
                    self._fail(req, e)
                    self._retire_silent(req)
                # the step was given the cache to keep and may have
                # consumed it before it raised. Every slot is free now, so
                # an empty cache is the right state; the old one goes
                # first, two do not fit beside the weights
                self.cache = None
                self.cache = init_cache(self.cfg, self.slots, self.max_len)
                import logging

                logging.getLogger(__name__).exception(
                    "continuous-batching step failed")

    def _fail(self, req: _Request, e: BaseException) -> None:
        """Resolve a request with the device-side failure: its caller —
        future or stream — sees the exception, and it is counted."""
        self.stats["failed"] += 1
        if req.future is not None and not req.future.done():
            req.future.set_exception(e)
        if req.stream_q is not None:
            req.stream_q.put(e)

    def _retire_silent(self, req: _Request) -> None:
        if req.slot >= 0:
            self._active.pop(req.slot, None)
            self._free.append(req.slot)
            req.slot = -1

    def _step(self) -> None:
        self._admit()
        if not self._active:
            return
        with device_span(spans.ENGINE_DECODE_DISPATCH,
                         active=len(self._active)):
            active_mask = np.zeros(self.slots, bool)
            for slot in self._active:
                active_mask[slot] = True
            self._rng, k = jax.random.split(self._rng)
            toks, self.cache, *load = self._decode_jit(
                self.params, jnp.asarray(self._last_tok), self.cache, k,
                jnp.asarray(self._temps), jnp.asarray(self._topks),
                jnp.asarray(active_mask))
            self._fetch_ahead(load)
        self.stats["steps"] += 1
        with device_span(spans.ENGINE_SAMPLE_SYNC):
            toks_np = np.asarray(toks)
            self._count_experts(load, len(self._active))
        with device_span(spans.ENGINE_EMIT):
            for slot, req in list(self._active.items()):
                self._host_len[slot] += 1
                self._last_tok[slot] = int(toks_np[slot])
                self._emit(req, int(toks_np[slot]))
