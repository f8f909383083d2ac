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
- **Decode** is ``forward_cached``'s layer loop carrying the pools: each
  layer scatters the new K/V into the slot's current write page, then
  gathers each slot's pages into a contiguous view
  (``pool[l, page_table]``) — the transient is one layer's worth, not a
  dense cache — and attends. Inactive
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

This module keeps the cache and schedules nothing. ``PagedBatcher`` is
``models/continuous_batching.ContinuousBatcher`` — its queue, admission
loop, step, emit, retire, failure path, spans and counters, all
inherited — with the six methods overridden through which that scheduler
reaches K/V rows: an empty pool, a prompt into pages, a decode step over
the pools, pages given back, room made before a step (growth or
preemption), and "pool exhausted" told apart from "failed".
"""

from __future__ import annotations

import dataclasses
import hashlib
from collections import OrderedDict
from concurrent.futures import Future
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.models.continuous_batching import (
    ContinuousBatcher, _Request, _counted)
from ray_tpu.models.decoding import (
    KVCache,
    SamplingParams,
    forward_cached,
    init_cache,
    init_state,
)
from ray_tpu.models.families import only_kv_rows
from ray_tpu.models.transformer import TransformerConfig
from ray_tpu.observability import schema as spans
from ray_tpu.observability.tracing import device_span


class KVPoolExhausted(RuntimeError):
    """No free pages. A RuntimeError subclass so existing callers that
    catch the old bare RuntimeError keep working; the batcher's admit
    path catches THIS to requeue instead of failing the request."""


class PagedKV:
    """Host-side page bookkeeping: refcounts, free list, prefix map."""

    def __init__(self, num_pages: int, page_size: int):
        self.num_pages = num_pages
        self.page_size = page_size
        self.stats = {"prefix_hit_pages": 0, "alloc_pages": 0,
                      "evicted_entries": 0}
        self.clear()

    def clear(self) -> None:
        """Every page free and no prefix known: the state of a new pool,
        and of one whose device arrays were lost."""
        self.rc = np.zeros(self.num_pages, np.int32)
        self.rc[0] = 1  # page 0 = trash page, never allocated
        # free pages in LRU order; a freed page keeps its content (and
        # its prefix-map entry) until reallocated
        self.free: "OrderedDict[int, None]" = OrderedDict(
            (i, None) for i in range(1, self.num_pages))
        # prefix hash -> page id holding that page of the prefix
        self.prefix_map: Dict[str, int] = {}
        self.page_key: Dict[int, str] = {}  # inverse, for invalidation

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
class _Held:
    """What the pool holds for one request (`_Request.kv`)."""
    pages: List[int] = dataclasses.field(default_factory=list)
    # (row_k, row_v, last_logits) computed by a prefill replica
    premade_row: Optional[tuple] = None


class PagedBatcher(ContinuousBatcher):
    """The paged cache under `ContinuousBatcher`'s scheduler: same API
    (submit / submit_stream / shutdown, stats, spans) plus
    `submit_prefilled`; what it overrides is where K/V rows are kept."""

    def __init__(self, cfg: TransformerConfig, params, max_len: int = 512,
                 slots: int = 8, page_size: int = 64,
                 extra_pages: int = 0, seed: int = 0,
                 num_pages: Optional[int] = None):
        """``num_pages`` overrides the pool size: smaller than
        1 + slots*pages_per_seq overcommits memory (lazy growth +
        recompute-preemption absorb the shortfall — vLLM's model);
        ``extra_pages`` adds headroom so freed prefix pages survive
        longer in the cache."""
        # one `state` a slot rides beside the pages, prompts prefilled whole
        only_kv_rows(cfg, "pages hold no ring, matrix state, convolution "
                     "window or latent row (prefix reuse and preemption "
                     "would have to rebuild them): serve it from "
                     "ContinuousBatcher", also=("state",))
        if max_len % page_size != 0:
            raise ValueError("max_len must be a multiple of page_size")
        self.page_size = page_size
        self.pages_per_seq = max_len // page_size
        if num_pages is None:
            num_pages = 1 + slots * self.pages_per_seq + extra_pages
        self.kv = PagedKV(num_pages, page_size)
        self._page_table = np.zeros((slots, self.pages_per_seq), np.int32)
        super().__init__(cfg, params, max_len, slots, seed)
        self.stats.update(prefill_tokens=0, prefix_hit_tokens=0, preempted=0)

    def submit_prefilled(self, tokens: Sequence[int], row_k, row_v,
                         last_logits,
                         sampling: Optional[SamplingParams] = None
                         ) -> Future:
        """Admit a request whose prompt KV was computed by a prefill
        replica (disaggregated prefill — reference:
        llm/_internal/serve/engines/vllm/kv_transfer/). ``row_k/row_v``
        are [L, S, kvH, D] with S >= len(tokens)."""
        only_kv_rows(self.cfg, "a premade row brings none: submit the prompt")
        return self._enqueue(_Request(
            list(tokens) or [0], sampling or SamplingParams(), Future(),
            None, kv=_Held(premade_row=(
                jnp.asarray(row_k), jnp.asarray(row_v),
                jnp.asarray(last_logits))))).future

    # -- device programs ------------------------------------------------
    # `cache` is KVCache(pool_k, pool_v [L, num_pages, page_size, kvH, D],
    # lengths [slots]); install and decode are given it to keep, as the
    # scheduler jits them (`_jit_programs`)
    def _prefill_impl(self, params, tokens, length, prefix_row_k,
                      prefix_row_v, prefix_len):
        """Continuation prefill: [1, S] remainder tokens at positions
        prefix_len.., attending over the reused prefix (gathered into
        the row) plus themselves. Returns (last_logits [V], row_k,
        row_v [L, max_len, kvH, D]), then a stateful model's row state and
        what a sparse model's expert layers counted over the remainder's
        real positions, as the scheduler's own prefill does. A stateful
        model's prompts are prefilled whole (`_prefill_into`), so the state
        a remainder starts from is a new sequence's."""
        s = tokens.shape[1]
        row = init_cache(self.cfg, 1, self.max_len)
        k = lax.dynamic_update_slice(
            row.k, prefix_row_k[:, None], (0, 0, 0, 0, 0))
        v = lax.dynamic_update_slice(
            row.v, prefix_row_v[:, None], (0, 0, 0, 0, 0))
        row = row._replace(k=k, v=v)
        positions = prefix_len + jnp.arange(s)[None, :]
        kv_mask = jnp.arange(self.max_len)[None, :] < (prefix_len + s)
        logits, row, aux = forward_cached(
            self.cfg, params, tokens, positions, row, kv_mask,
            positions < length)
        last = jnp.take_along_axis(
            logits, (length - prefix_len - 1)[:, None, None].repeat(
                logits.shape[-1], -1), axis=1)[:, 0]
        return last[0], *self._row_of(row), *_counted(aux)

    def _install_impl(self, cache: KVCache, row_k, row_v, page_ids, slot,
                      length, *kept):
        """Scatter a [L, max_len] row into the pool at page_ids
        [pages_per_seq] (trash page 0 for pages not to keep). A stateful
        model's row state (`kept`, as `ContinuousBatcher._install_impl`'s)
        goes to the slot, not to a page: the state is kept per sequence,
        [L, slots, ...], outside the pool."""
        paged = (row_k.shape[0], self.pages_per_seq, self.page_size,
                 *row_k.shape[2:])
        return KVCache(
            cache.k.at[:, page_ids].set(
                row_k.reshape(paged).astype(cache.k.dtype)),
            cache.v.at[:, page_ids].set(
                row_v.reshape(paged).astype(cache.v.dtype)),
            cache.lengths.at[slot].set(length),
            *self._slot_fields(cache, slot, kept))

    def _gather_row(self, page_ids):
        """[pages_per_seq] page ids -> dense [L, max_len] row (for
        continuation prefill over a reused prefix)."""
        k, v = self.cache.k[:, page_ids], self.cache.v[:, page_ids]
        dense = (k.shape[0], self.max_len, *k.shape[3:])  # [L, P, ps, ..]
        return k.reshape(dense), v.reshape(dense)

    def _step_shapes(self):
        return super()._step_shapes() + (jax.ShapeDtypeStruct(
            self._page_table.shape, jnp.int32),)

    def _decode_impl(self, params, toks, cache, rng, temps, topks,
                     active_mask, page_table):
        """The scheduler's decode step over the pools: the token's K/V go
        to [layer, its slot's current page, offset], and attention reads a
        dense view of each slot's pages (transient, one layer only; the
        pools stay paged). Inactive slots write to the trash page 0, so
        the step needs no host-side branching."""
        slots = jnp.arange(toks.shape[0])
        ps = self.page_size
        cur_page = jnp.where(
            active_mask, page_table[slots, cache.lengths // ps], 0)
        cur_off = jnp.where(active_mask, cache.lengths % ps, 0)
        dense = (toks.shape[0], self.max_len, self.cfg.kv_heads, self.cfg.hd)

        def write_pages(layer):
            def access(pool_k, pool_v, k, v, positions):
                pool_k = pool_k.at[layer, cur_page, cur_off].set(
                    k[:, 0].astype(pool_k.dtype))
                pool_v = pool_v.at[layer, cur_page, cur_off].set(
                    v[:, 0].astype(pool_v.dtype))
                return (pool_k, pool_v,
                        (pool_k[layer, page_table].reshape(dense),
                         pool_v[layer, page_table].reshape(dense)))

            return access

        return super()._decode_impl(params, toks, cache, rng, temps, topks,
                                    active_mask, access=write_pages)

    # -- the cache: refcounted pages ------------------------------------
    def _empty_cache(self):
        # with the pools' content goes every page held and every prefix
        # cached in them
        self.kv.clear()
        self._page_table[:] = 0
        shape = (self.cfg.full_layers, self.kv.num_pages, self.page_size,
                 self.cfg.kv_heads, self.cfg.hd)
        return KVCache(jnp.zeros(shape, self.cfg.dtype),
                       jnp.zeros(shape, self.cfg.dtype),
                       jnp.zeros((self.slots,), jnp.int32),
                       **init_state(self.cfg, self.slots))

    def _prefill_into(self, req: _Request, slot: int):
        n = len(req.tokens)
        held = req.kv = req.kv or _Held()
        reused: List[int] = []  # a premade row's KV arrived whole
        # a stateful attention reads position t-1, and no page keeps the
        # state at its boundary: such a prompt is prefilled whole
        if held.premade_row is None and not self.cfg.stateful:
            reused = self.kv.lookup_prefix(
                prefix_keys(req.tokens, self.page_size))
            # reuse must leave at least one token to prefill (the last
            # logits come from the prefill forward)
            while reused and len(reused) * self.page_size >= n:
                self.kv.stats["prefix_hit_pages"] -= 1
                reused.pop()
        # every acquisition lands in held.pages IMMEDIATELY so `_release`
        # can decref exactly what was taken when an alloc below raises
        # mid-admit (incref'd reused-prefix pages and partial fresh
        # allocations both leaked before)
        for page in reused:
            self.kv.incref(page)
            held.pages.append(page)
        prefix_len = len(reused) * self.page_size
        self.stats["prefix_hit_tokens"] += prefix_len
        # LAZY allocation: only the pages the sequence occupies right
        # now (prompt + the first decode write at position n) — growth
        # happens per step in _grow_pages; this is what lets the pool be
        # smaller than slots × pages_per_seq (vLLM's overcommit)
        for _ in range(self._pages_to_admit(n) - len(reused)):
            held.pages.append(self.kv.alloc())
        page_ids = np.zeros(self.pages_per_seq, np.int32)
        page_ids[:len(held.pages)] = held.pages

        state, load, rows = [], [], 0  # a premade row was computed elsewhere
        with device_span(spans.ENGINE_PREFILL_DISPATCH):
            if held.premade_row is not None:
                row_k, row_v, last_logits = held.premade_row
                row_k, row_v = self._pad_row(row_k, row_v)
            else:
                remainder = req.tokens[prefix_len:]
                rows = len(remainder)
                bucket = min(self._bucket(rows), self.max_len - prefix_len)
                toks = np.zeros((1, bucket), np.int32)
                toks[0, :rows] = remainder
                last_logits, row_k, row_v, *rest = \
                    self._prefill_program(bucket)(
                        self.params, jnp.asarray(toks),
                        jnp.asarray([n], np.int32),
                        *self._gather_row(jnp.asarray(page_ids)),
                        jnp.asarray(prefix_len, np.int32))
                state, load = self._row_state(rest)
                self._fetch_ahead(load)
                self.stats["prefill_tokens"] += rows
        with device_span(spans.ENGINE_INSTALL_DISPATCH):
            self.cache = self._install_jit(
                self.cache, row_k, row_v, jnp.asarray(page_ids), slot, n,
                *state)
        self._page_table[slot] = page_ids
        return last_logits, load, rows

    def _decode(self, toks, rng, temps, topks, active_mask):
        toks, self.cache, *load = self._decode_jit(
            self.params, toks, self.cache, rng, temps, topks, active_mask,
            # a copy: growth writes the table while this step is in flight
            self._on_device("page_table", self._page_table))
        return toks, load

    def _kv_rows(self, lens):
        """Pages are gathered into a dense view of every slot before a
        step's attention reads them: all of it is read, whatever is held."""
        held, _ = super()._kv_rows(lens)
        return held, self.cfg.full_layers * self.slots * self.max_len

    def _pages_to_admit(self, n: int) -> int:
        """Pages an n-token prompt takes at admission: its own and the one
        its first decode step writes to. A preempted request that comes
        back with `max_len` tokens fills the row and has no such step
        (`_emit` retires it with its first token)."""
        return min(n // self.page_size + 1, self.pages_per_seq)

    def _release(self, req: _Request) -> None:
        for page in req.kv.pages:
            self.kv.decref(page)
        req.kv.pages = []
        self._reset_state(req.slot)

    def _retire(self, req: _Request) -> None:
        if req.slot >= 0:
            # a FINISHED prompt's full pages serve later prefix hits; they
            # stay cached when `_release` frees them
            keys = prefix_keys(req.tokens, self.page_size)
            self.kv.register_prefix(keys, req.kv.pages[:len(keys)])
        super()._retire(req)

    def _refused_for_now(self, req: _Request, e: Exception) -> bool:
        # active sequences hold the pool and will give pages back. (A
        # request bigger than the whole pool still fails: requeueing it
        # would spin forever.)
        fits = self._pages_to_admit(len(req.tokens)) \
            <= self.kv.num_pages - 1  # page 0 = trash
        return isinstance(e, KVPoolExhausted) and fits

    def _make_room(self) -> None:
        self._grow_pages()

    def _grow_pages(self) -> None:
        """Per-step lazy growth: every slot the step advances must own the
        page its decode write lands in. Pool exhausted → preempt the most
        recently admitted slot (free its pages, requeue it — it
        re-prefills from prompt+generated when room returns), matching
        vLLM's recompute-preemption policy. On a drained loop: the victim's
        `out` then holds every token computed for it, and a request that
        the step in flight ended has given its pages back."""
        for slot in sorted(self._next_slots()):
            if slot not in self._active:
                continue  # taken back for an earlier slot's page
            pages = self._active[slot].kv.pages
            need = int(self._host_len[slot]) // self.page_size
            while need >= len(pages):
                try:
                    page = self.kv.alloc()
                except RuntimeError:
                    if self._drain():
                        return self._grow_pages()
                    # prefer preempting a DIFFERENT slot; if this is the
                    # only active one it preempts itself and returns
                    candidates = [s for s in self._active if s != slot]
                    victim = candidates[-1] if candidates else slot
                    self._preempt(victim)
                    if victim == slot:
                        return
                    continue
                pages.append(page)
                self._page_table[slot, len(pages) - 1] = page

    def _preempt(self, slot: int) -> None:
        req = self._active[slot]
        # what it emitted in this slot: its first token and one a step
        # (after an earlier preemption `tokens` holds the rest of `out`)
        emitted = int(self._host_len[slot]) - len(req.tokens) + 1
        self._vacate(req)
        # recompute-preemption: when a slot frees up the request
        # re-prefills over prompt + everything generated so far and
        # resumes sampling from there. Already-emitted tokens stay
        # emitted (req.out keeps the max_tokens accounting).
        req.tokens = list(req.tokens) + req.out[-emitted:]
        req.kv.premade_row = None  # its KV is gone; must re-prefill
        self.stats["preempted"] += 1
        self._waiting.put(req)

    def decode_cache_size(self) -> int:
        """Compiled-program count for the decode step (steady-state
        no-recompile assertion hook)."""
        return int(self._decode_jit._cache_size())
