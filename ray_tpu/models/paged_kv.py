"""Block-paged KV cache with prefix reuse — the TPU-native answer to
vLLM's PagedAttention + automatic prefix caching.

Reference: the reference LLM library delegates KV management to vLLM
(python/ray/llm/_internal/serve/engines/vllm/), whose memory model is
fixed-size KV pages + a per-sequence page table + copy-on-write prefix
sharing. This module rebuilds that model under XLA's constraints:

- **One physical pool** ``[L, num_pages, page_size, kvH, D]`` for K and
  V. Page tables are ``[slots, pages_per_seq]`` int32 — every shape is
  static, so steady state runs exactly three compiled programs (prefill
  per length bucket, page install, one decode step) and never
  recompiles.
- **Decode** gathers each active slot's pages into a contiguous view
  *inside* the per-layer scan body (``pool[l][page_table]``) — the
  transient is one layer's worth, not a dense cache — attends, then
  scatters the new K/V into the slot's current write page. Inactive
  slots write to a reserved trash page (page 0), so the step needs no
  host-side branching.
- **Prefix reuse**: pages are refcounted; a finished sequence's prompt
  pages register content hashes at full-page granularity. A new prompt
  reuses the longest cached chain of FULL pages (incref — shared pages
  are never written: decode only appends to a sequence's private last
  page) and prefills just the remainder, attending over the reused
  prefix gathered into the prefill row. Freed pages stay cached (rc=0,
  on the LRU free list) until the allocator reclaims them, exactly
  vLLM's "cached-free" state.
- **Disaggregated prefill**: ``submit_prefilled`` admits a request
  whose KV row was computed elsewhere (a prefill replica shipping over
  a typed tensor channel — see models/disagg_prefill.py), installing
  pages without running local prefill.
"""

from __future__ import annotations

import dataclasses
import hashlib
import queue
import threading
from collections import OrderedDict
from concurrent.futures import Future
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.models.continuous_batching import _sample_per_slot
from ray_tpu.models.decoding import (
    SamplingParams,
    _block_cached,
    _rms_norm,
    forward_cached,
    init_cache,
    layers_to_scan,
)
from ray_tpu.models.transformer import TransformerConfig


class KVPoolExhausted(RuntimeError):
    """No free pages. A RuntimeError subclass so existing callers that
    catch the old bare RuntimeError keep working; the batcher's admit
    path catches THIS to requeue instead of failing the request."""


class PagedKV:
    """Host-side page bookkeeping: refcounts, free list, prefix map."""

    def __init__(self, num_pages: int, page_size: int):
        self.num_pages = num_pages
        self.page_size = page_size
        self.rc = np.zeros(num_pages, np.int32)
        self.rc[0] = 1  # page 0 = trash page, never allocated
        # free pages in LRU order; a freed page keeps its content (and
        # its prefix-map entry) until reallocated
        self.free: "OrderedDict[int, None]" = OrderedDict(
            (i, None) for i in range(1, num_pages))
        # prefix hash -> page id holding that page of the prefix
        self.prefix_map: Dict[str, int] = {}
        self.page_key: Dict[int, str] = {}  # inverse, for invalidation
        self.stats = {"prefix_hit_pages": 0, "alloc_pages": 0,
                      "evicted_entries": 0}

    def alloc(self) -> int:
        """Pop the least-recently-freed page, invalidating whatever
        prefix entry still pointed at its old content."""
        if not self.free:
            raise KVPoolExhausted("KV pool exhausted")
        page, _ = self.free.popitem(last=False)
        old_key = self.page_key.pop(page, None)
        if old_key is not None and self.prefix_map.get(old_key) == page:
            del self.prefix_map[old_key]
            self.stats["evicted_entries"] += 1
        self.rc[page] = 1
        self.stats["alloc_pages"] += 1
        return page

    def incref(self, page: int) -> None:
        if self.rc[page] == 0:
            self.free.pop(page, None)  # cached-free -> live again
        self.rc[page] += 1

    def decref(self, page: int) -> None:
        self.rc[page] -= 1
        if self.rc[page] == 0:
            self.free[page] = None  # to the LRU tail, content retained

    def lookup_prefix(self, keys: List[str]) -> List[int]:
        """Longest chain of cached pages matching the prefix keys."""
        pages: List[int] = []
        for key in keys:
            page = self.prefix_map.get(key)
            if page is None:
                break
            pages.append(page)
        self.stats["prefix_hit_pages"] += len(pages)
        return pages

    def register_prefix(self, keys: List[str], pages: List[int]) -> None:
        for key, page in zip(keys, pages):
            if key not in self.prefix_map:
                self.prefix_map[key] = page
                self.page_key[page] = key


def prefix_keys(tokens: Sequence[int], page_size: int) -> List[str]:
    """One content hash per FULL page of the prompt: key i covers
    tokens[:page_size*(i+1)] — a chain, so matching key i implies the
    whole prefix up to that page matches."""
    keys = []
    h = hashlib.sha1()
    full_pages = len(tokens) // page_size
    for i in range(full_pages):
        chunk = tokens[i * page_size:(i + 1) * page_size]
        h.update(np.asarray(chunk, np.int32).tobytes())
        keys.append(h.hexdigest())
    return keys


@dataclasses.dataclass
class _Request:
    tokens: List[int]
    sampling: SamplingParams
    future: Optional[Future]
    stream_q: Optional[queue.Queue]
    # disaggregated prefill: KV row + last logits computed elsewhere
    premade_row: Optional[Tuple[Any, Any, Any]] = None
    out: List[int] = dataclasses.field(default_factory=list)
    slot: int = -1
    pages: List[int] = dataclasses.field(default_factory=list)


class PagedBatcher:
    """Continuous batching over the paged pool. API mirrors
    models/continuous_batching.ContinuousBatcher (submit/submit_stream/
    shutdown + stats) so engines can swap slot-dense for paged."""

    def __init__(self, cfg: TransformerConfig, params, max_len: int = 512,
                 slots: int = 8, page_size: int = 64,
                 extra_pages: int = 0, seed: int = 0,
                 num_pages: Optional[int] = None):
        """``num_pages`` overrides the pool size: smaller than
        1 + slots*pages_per_seq overcommits memory (lazy growth +
        recompute-preemption absorb the shortfall — vLLM's model);
        ``extra_pages`` adds headroom so freed prefix pages survive
        longer in the cache."""
        if max_len % page_size != 0:
            raise ValueError("max_len must be a multiple of page_size")
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.page_size = page_size
        self.pages_per_seq = max_len // page_size
        self.slots = slots
        if num_pages is None:
            num_pages = 1 + slots * self.pages_per_seq + extra_pages
        self.kv = PagedKV(num_pages, page_size)
        shape = (cfg.layers, num_pages, page_size, cfg.kv_heads, cfg.hd)
        self.pool_k = jnp.zeros(shape, cfg.dtype)
        self.pool_v = jnp.zeros(shape, cfg.dtype)
        # per-slot host state
        self._page_table = np.zeros((slots, self.pages_per_seq), np.int32)
        self._lengths = np.zeros(slots, np.int32)
        self._temps = np.zeros(slots, np.float32)
        self._topks = np.zeros(slots, np.int32)
        self._last_tok = np.zeros(slots, np.int32)
        self._active: Dict[int, _Request] = {}
        self._free_slots = list(range(slots))
        self._waiting: "queue.Queue[_Request]" = queue.Queue()
        self._wake = threading.Event()
        self._shutdown = False
        self._rng = jax.random.key(seed)
        self.stats = {"admitted": 0, "finished": 0, "steps": 0,
                      "tokens_out": 0, "prefill_tokens": 0,
                      "prefix_hit_tokens": 0, "preempted": 0}
        self._decode_jit = jax.jit(self._decode_impl,
                                   donate_argnums=(2, 3))
        self._install_jit = jax.jit(self._install_impl,
                                    donate_argnums=(0, 1))
        self._prefill_jits: Dict[int, Any] = {}
        self._thread = threading.Thread(target=self._pump, daemon=True,
                                        name="paged-pump")
        self._thread.start()

    # -- public API -----------------------------------------------------
    def submit(self, tokens: Sequence[int],
               sampling: Optional[SamplingParams] = None) -> Future:
        return self._enqueue(tokens, sampling, stream=False)

    def submit_stream(self, tokens: Sequence[int],
                      sampling: Optional[SamplingParams] = None):
        req = self._enqueue(tokens, sampling, stream=True)
        while True:
            t = req.get()
            if t is None:
                return
            yield t

    def submit_prefilled(self, tokens: Sequence[int], row_k, row_v,
                         last_logits,
                         sampling: Optional[SamplingParams] = None
                         ) -> Future:
        """Admit a request whose prompt KV was computed by a prefill
        replica (disaggregated prefill — reference:
        llm/_internal/serve/engines/vllm/kv_transfer/). ``row_k/row_v``
        are [L, S, kvH, D] with S >= len(tokens)."""
        if self._shutdown:
            raise RuntimeError("PagedBatcher was shut down")
        fut: Future = Future()
        req = _Request(list(tokens) or [0], sampling or SamplingParams(),
                       fut, None,
                       premade_row=(jnp.asarray(row_k), jnp.asarray(row_v),
                                    jnp.asarray(last_logits)))
        self._check_len(req)
        self._waiting.put(req)
        self._wake.set()
        return fut

    def _enqueue(self, tokens, sampling, stream: bool):
        if self._shutdown:
            raise RuntimeError("PagedBatcher was shut down")
        q: Optional[queue.Queue] = queue.Queue() if stream else None
        fut: Optional[Future] = None if stream else Future()
        req = _Request(list(tokens) or [0], sampling or SamplingParams(),
                       fut, q)
        self._check_len(req)
        self._waiting.put(req)
        self._wake.set()
        return q if stream else fut

    def _check_len(self, req: _Request) -> None:
        if len(req.tokens) >= self.max_len:
            raise ValueError(
                f"prompt length {len(req.tokens)} >= max_len "
                f"{self.max_len}")

    def shutdown(self) -> None:
        self._shutdown = True
        self._wake.set()
        self._thread.join(timeout=10.0)
        err = RuntimeError("PagedBatcher was shut down")
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

    # -- device programs ------------------------------------------------
    def _prefill_impl(self, params, tokens, length, prefix_row_k,
                      prefix_row_v, prefix_len):
        """Continuation prefill: [1, S] remainder tokens at positions
        prefix_len.., attending over the reused prefix (gathered into
        the row) plus themselves. Returns (last_logits [V], row_k,
        row_v [L, max_len, kvH, D])."""
        s = tokens.shape[1]
        row = init_cache(self.cfg, 1, self.max_len)
        k = lax.dynamic_update_slice(
            row.k, prefix_row_k[:, None], (0, 0, 0, 0, 0))
        v = lax.dynamic_update_slice(
            row.v, prefix_row_v[:, None], (0, 0, 0, 0, 0))
        row = row._replace(k=k, v=v)
        positions = prefix_len + jnp.arange(s)[None, :]
        kv_mask = jnp.arange(self.max_len)[None, :] < (prefix_len + s)
        logits, row, _ = forward_cached(
            self.cfg, params, tokens, positions, row, kv_mask,
            positions < length)
        last = jnp.take_along_axis(
            logits, (length - prefix_len - 1)[:, None, None].repeat(
                logits.shape[-1], -1), axis=1)[:, 0]
        return last[0], row.k[:, 0], row.v[:, 0]

    def _install_impl(self, pool_k, pool_v, row_k, row_v, page_ids):
        """Scatter a [L, max_len] row into the pool at page_ids
        [pages_per_seq] (trash page 0 for pages not to keep)."""
        ps = self.page_size
        lk = row_k.reshape(row_k.shape[0], self.pages_per_seq, ps,
                           *row_k.shape[2:])
        lv = row_v.reshape(row_v.shape[0], self.pages_per_seq, ps,
                           *row_v.shape[2:])
        return (pool_k.at[:, page_ids].set(lk.astype(pool_k.dtype)),
                pool_v.at[:, page_ids].set(lv.astype(pool_v.dtype)))

    def _gather_row_impl(self, pool_k, pool_v, page_ids):
        """[pages_per_seq] page ids -> dense [L, max_len] row (for
        continuation prefill over a reused prefix)."""
        k = pool_k[:, page_ids]  # [L, P, ps, kvH, D]
        v = pool_v[:, page_ids]
        ln = k.shape[0]
        return (k.reshape(ln, self.max_len, *k.shape[3:]),
                v.reshape(ln, self.max_len, *v.shape[3:]))

    def _decode_impl(self, params, toks, pool_k, pool_v, page_table,
                     lengths, rng, temps, topks, active_mask):
        """One decode step for all slots over the paged pool."""
        cfg = self.cfg
        b = toks.shape[0]
        ps = self.page_size
        positions = lengths[:, None]  # [B, 1]
        t_total = self.pages_per_seq * ps
        kv_mask = jnp.arange(t_total)[None, :] <= lengths[:, None]
        # current write target per slot; inactive slots hit trash page 0
        cur_page = jnp.where(
            active_mask,
            page_table[jnp.arange(b), lengths // ps], 0)
        cur_off = jnp.where(active_mask, lengths % ps, 0)

        x = params["embed"].astype(cfg.dtype)[toks[:, None]]
        layer_tree, whole = layers_to_scan(cfg, params)

        def paged_access(pool_k, pool_v, k, v, positions):
            # one layer of the pool: the current token's K/V go to their
            # page, then attention reads a dense view of each slot's pages
            # (transient, one layer only; the pool itself stays paged)
            pool_k = pool_k.at[cur_page, cur_off].set(
                k[:, 0].astype(pool_k.dtype))
            pool_v = pool_v.at[cur_page, cur_off].set(
                v[:, 0].astype(pool_v.dtype))
            dense = (b, t_total, cfg.kv_heads, cfg.hd)
            return (pool_k, pool_v, pool_k[page_table].reshape(dense),
                    pool_v[page_table].reshape(dense))

        def body(x, layer):
            out, pk, pv, _ = _block_cached(
                cfg, x, dict(layer["p"], **whole), layer.get("l"), positions,
                layer["k"], layer["v"], kv_mask, active_mask[:, None],
                layer["i"], paged_access)
            return out, (pk, pv)

        x, (new_pool_k, new_pool_v) = lax.scan(
            body, x, dict(layer_tree, k=pool_k, v=pool_v))
        x = _rms_norm(x, params["ln_f"], cfg.norm_eps)
        unembed = params.get("unembed")
        if unembed is None:
            unembed = params["embed"].T
        logits = jnp.einsum("bsh,hv->bsv", x, unembed.astype(x.dtype))
        nxt = _sample_per_slot(logits[:, 0], rng, temps, topks)
        new_len = jnp.where(active_mask, lengths + 1, lengths)
        return nxt, new_pool_k, new_pool_v, new_len

    # -- scheduler ------------------------------------------------------
    @staticmethod
    def _bucket(n: int) -> int:
        b = 16
        while b < n:
            b *= 2
        return b

    def _admit(self) -> None:
        while self._free_slots and not self._waiting.empty():
            try:
                req = self._waiting.get_nowait()
            except queue.Empty:
                break
            slot = self._free_slots.pop()
            try:
                self._admit_one(req, slot)
            except Exception as e:  # noqa: BLE001
                self._free_slots.append(slot)
                # _admit_one grows req.pages INCREMENTALLY (reused-prefix
                # increfs first, then each fresh alloc as it happens), so
                # this decref sweep releases everything a partial admit
                # acquired — no page leaks on pool exhaustion mid-admit
                for page in req.pages:
                    self.kv.decref(page)
                req.pages = []
                never_fits = (len(req.tokens) // self.page_size + 1
                              > self.kv.num_pages - 1)  # page 0 = trash
                if isinstance(e, KVPoolExhausted) and not never_fits:
                    # transient: active sequences hold the pool. Requeue
                    # at the FRONT (FIFO position kept — a tail requeue
                    # would let every later small request leapfrog a big
                    # one forever, its future never resolving) and stop
                    # admitting; retired sequences free pages and the
                    # pump re-runs _admit every step. (A request bigger
                    # than the whole pool still fails: requeueing it
                    # would spin forever.)
                    with self._waiting.mutex:
                        self._waiting.queue.appendleft(req)
                        self._waiting.not_empty.notify()
                    break
                if req.future is not None and not req.future.done():
                    req.future.set_exception(e)
                if req.stream_q is not None:
                    req.stream_q.put(None)

    def _padded_page_ids(self, pages: List[int]) -> np.ndarray:
        ids = np.zeros(self.pages_per_seq, np.int32)
        ids[:len(pages)] = pages
        return ids

    def _admit_one(self, req: _Request, slot: int) -> None:
        n = len(req.tokens)
        keys = prefix_keys(req.tokens, self.page_size)
        if req.premade_row is not None:
            reused: List[int] = []  # KV arrived whole from the prefiller
        else:
            reused = self.kv.lookup_prefix(keys)
            # reuse must leave at least one token to prefill (the last
            # logits come from the prefill forward)
            while reused and len(reused) * self.page_size >= n:
                self.kv.stats["prefix_hit_pages"] -= 1
                reused.pop()
        # every acquisition lands in req.pages IMMEDIATELY so the
        # _admit cleanup path can decref exactly what was taken when an
        # alloc below raises mid-admit (incref'd reused-prefix pages and
        # partial fresh allocations both leaked before)
        req.pages = []
        for page in reused:
            self.kv.incref(page)
            req.pages.append(page)
        prefix_len = len(reused) * self.page_size
        self.stats["prefix_hit_tokens"] += prefix_len
        # LAZY allocation: only the pages the sequence occupies right
        # now (prompt + the first decode write at position n) — growth
        # happens per step in _grow_pages; this is what lets the pool be
        # smaller than slots × pages_per_seq (vLLM's overcommit)
        n_pages_now = n // self.page_size + 1
        for _ in range(n_pages_now - len(reused)):
            req.pages.append(self.kv.alloc())
        page_ids = self._padded_page_ids(req.pages)

        if req.premade_row is not None:
            row_k, row_v, last_logits = req.premade_row
            pad = self.max_len - row_k.shape[1]
            if pad > 0:
                z = jnp.zeros(row_k.shape[:1] + (pad,) + row_k.shape[2:],
                              row_k.dtype)
                row_k = jnp.concatenate([row_k, z], axis=1)
                row_v = jnp.concatenate([row_v, z], axis=1)
            self.pool_k, self.pool_v = self._install_jit(
                self.pool_k, self.pool_v, row_k, row_v,
                jnp.asarray(page_ids))
        else:
            remainder = req.tokens[prefix_len:]
            bucket = min(self._bucket(len(remainder)),
                         self.max_len - prefix_len)
            bucket = max(bucket, len(remainder))
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :len(remainder)] = remainder
            prefix_k, prefix_v = self._gather_row_impl(
                self.pool_k, self.pool_v, jnp.asarray(page_ids))
            pf = self._prefill_jits.get(bucket)
            if pf is None:
                pf = jax.jit(self._prefill_impl)
                self._prefill_jits[bucket] = pf
            last_logits, row_k, row_v = pf(
                self.params, jnp.asarray(toks),
                jnp.asarray([n], np.int32), prefix_k, prefix_v,
                jnp.asarray(prefix_len, np.int32))
            self.stats["prefill_tokens"] += len(remainder)
            self.pool_k, self.pool_v = self._install_jit(
                self.pool_k, self.pool_v, row_k, row_v,
                jnp.asarray(page_ids))

        self._rng, k = jax.random.split(self._rng)
        first = _sample_per_slot(
            last_logits[None], k,
            jnp.asarray([req.sampling.temperature], np.float32),
            jnp.asarray([req.sampling.top_k], np.int32))
        req.slot = slot
        self._page_table[slot] = page_ids
        self._lengths[slot] = n
        self._temps[slot] = req.sampling.temperature
        self._topks[slot] = req.sampling.top_k
        self._last_tok[slot] = int(np.asarray(first)[0])
        self._active[slot] = req
        self.stats["admitted"] += 1
        self._emit(req, self._last_tok[slot])

    def _emit(self, req: _Request, tok: int) -> None:
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
        if not done and req.slot >= 0 and \
                self._lengths[req.slot] >= self.max_len - 1:
            done = True
        if done:
            self._retire(req)

    def _retire(self, req: _Request) -> None:
        if req.slot >= 0:
            # register this prompt's full pages for future prefix hits
            keys = prefix_keys(req.tokens, self.page_size)
            self.kv.register_prefix(keys, req.pages[:len(keys)])
            for page in req.pages:
                self.kv.decref(page)
            req.pages = []
            self._active.pop(req.slot, None)
            self._free_slots.append(req.slot)
            req.slot = -1
        self.stats["finished"] += 1
        if req.future is not None and not req.future.done():
            req.future.set_result(list(req.out))
        if req.stream_q is not None:
            req.stream_q.put(None)

    def _pump(self) -> None:
        while not self._shutdown:
            if not self._active and self._waiting.empty():
                self._wake.wait(timeout=0.1)
                self._wake.clear()
                continue
            try:
                self._step()
            except Exception as e:  # noqa: BLE001
                for req in list(self._active.values()):
                    if req.future is not None and not req.future.done():
                        req.future.set_exception(e)
                    if req.stream_q is not None:
                        req.stream_q.put(None)
                    if req.slot >= 0:
                        for page in req.pages:
                            self.kv.decref(page)
                        req.pages = []
                        self._active.pop(req.slot, None)
                        self._free_slots.append(req.slot)
                        req.slot = -1
                import logging

                logging.getLogger(__name__).exception(
                    "paged decode step failed")

    def _grow_pages(self) -> None:
        """Per-step lazy growth: every active slot must own the page its
        next decode write lands in. Pool exhausted → preempt the most
        recently admitted slot (free its pages, requeue it — it
        re-prefills from prompt+generated when room returns), matching
        vLLM's recompute-preemption policy."""
        for slot in sorted(self._active):
            req = self._active[slot]
            need = int(self._lengths[slot]) // self.page_size
            while need >= len(req.pages):
                try:
                    page = self.kv.alloc()
                except RuntimeError:
                    # prefer preempting a DIFFERENT slot; if this is the
                    # only active one it preempts itself and returns
                    candidates = [s for s in self._active if s != slot]
                    victim = candidates[-1] if candidates else slot
                    self._preempt(victim)
                    if victim == slot:
                        return
                    continue
                req.pages.append(page)
                self._page_table[slot, len(req.pages) - 1] = page

    def _preempt(self, slot: int) -> None:
        req = self._active.pop(slot)
        for page in req.pages:
            self.kv.decref(page)
        req.pages = []
        # recompute-preemption: when a slot frees up the request
        # re-prefills over prompt + everything generated so far and
        # resumes sampling from there. Already-emitted tokens stay
        # emitted (req.out keeps the max_tokens accounting).
        req.tokens = list(req.tokens) + list(req.out)
        req.premade_row = None  # its KV is gone; must re-prefill
        req.slot = -1
        self._free_slots.append(slot)
        self.stats["preempted"] += 1
        self._waiting.put(req)

    def _step(self) -> None:
        self._admit()
        if not self._active:
            return
        self._grow_pages()
        if not self._active:
            return
        active_mask = np.zeros(self.slots, bool)
        for slot in self._active:
            active_mask[slot] = True
        self._rng, k = jax.random.split(self._rng)
        toks, self.pool_k, self.pool_v, new_len = self._decode_jit(
            self.params, jnp.asarray(self._last_tok), self.pool_k,
            self.pool_v, jnp.asarray(self._page_table),
            jnp.asarray(self._lengths), k, jnp.asarray(self._temps),
            jnp.asarray(self._topks), jnp.asarray(active_mask))
        self.stats["steps"] += 1
        # np.array (copy): asarray of a jax Array is a read-only view,
        # and _admit_one writes per-slot lengths in place
        self._lengths = np.array(new_len)
        toks_np = np.asarray(toks)
        for slot, req in list(self._active.items()):
            self._last_tok[slot] = int(toks_np[slot])
            self._emit(req, int(toks_np[slot]))

    def decode_cache_size(self) -> int:
        """Compiled-program count for the decode step (steady-state
        no-recompile assertion hook)."""
        return int(self._decode_jit._cache_size())
