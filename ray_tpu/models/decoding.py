"""Autoregressive decoding with a KV cache for the flagship transformer.

The reference LLM library delegates generation to vLLM
(python/ray/llm/_internal/serve/engines/vllm/); here the engine is
JAX-native over ray_tpu.models.transformer — the TPU-first shape:

- prefill: ONE jitted forward over the whole (right-padded) prompt
  batch writing K/V for every layer into a preallocated cache
  [L, B, max_len, kvH, D] (static shapes — no per-token recompiles),
- decode: ONE jitted single-token step per emitted token; the layer
  stack is a `lax.scan` over the stacked params so the compiled program
  is independent of depth. The cache rides in the scan's CARRY: a layer
  writes this call's K/V rows into the stack in place, and on the chip
  attends with a kernel that is given the stack, the layer and how many
  rows each slot holds (`ops.attention.decode_attention`): it reads
  those rows where they lie, so no layer is ever cut or copied out of
  the stack and a row no sequence holds is never read. The jitted steps
  donate the cache: a caller keeps the cache a step returns and never
  the one it passed in,
- sampling (greedy / temperature / top-k) happens on-device; only the
  emitted token ids cross back to host.

Left-padding-free: prompts are right-padded, per-sequence lengths track
the true positions, and attention masks cache slots >= the sequence's
current length.

A family may keep more than these rows, and states what (`TransformerConfig.
kept`): e.g. window layers keep a RING beside the slots, `KVCache.ring_k` /
`ring_v` [window layers, B, window, kvH, D]: position p lives at row p mod
window, a decode step overwrites the row of the position that has just left
the window, and a prefill leaves the last `window` positions of the prompt's
TRUE length. All of it rides in the same carry, is written in place and is
donated together; a field a family does not keep is None and no program has
an operand for it.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import families
from ray_tpu.models.transformer import (
    TransformerConfig, _gated, _lora_xa, _qk_norm, _qkv, _rms_norm, _rope,
    exit_pdf, moe_dropless, pass_end,
)
from ray_tpu.ops import attention as attention_ops
from ray_tpu.ops.attention import NEG_INF
from ray_tpu.ops import traced


class KVCache(NamedTuple):
    k: jax.Array  # [L, B, max_len, kvH, D]
    v: jax.Array  # [L, B, max_len, kvH, D]
    lengths: jax.Array  # [B] — tokens currently in cache per sequence
    # What a sequence keeps between steps besides its K/V rows, [L, B, ...]:
    # one position of an attention that looks one token back
    # (`cfg.stateful`, models/zaya.py). Read and rewritten every step, not
    # appended to; None for a model whose sequences are their rows, and then
    # no program has an operand for it.
    state: Optional[jax.Array] = None
    # The window layers' rows of a layer pattern, [window layers, B, window,
    # kvH, D]: position p at row p mod window (models/laguna.py). `k` / `v`
    # are then the full layers' alone. None for every other model.
    ring_k: Optional[jax.Array] = None
    ring_v: Optional[jax.Array] = None
    # A pattern of "kda" and "mla" layers (models/kimi_linear.py). `mat`
    # [kda layers, B, heads, D, D] float32: a linear-attention head's matrix
    # state, keys x values; `conv` [kda layers, B, (taps - 1) * 3 * heads * D]:
    # the last inputs of its three convolutions; both read and rewritten
    # every step, as `state`. `latent` [latent layers, B, max_len,
    # latent_row]: a latent-attention SUBLAYER's one row a position (the
    # normed, scaled latent, then the shared key part, rotated where the
    # configuration rotates it; zeros up to whole lanes: `cfg.latent_row`),
    # appended as `k` / `v` are but with no heads. One layer an "mla" layer,
    # two a "scmoe" double layer (models/longcat.py), in the layers' order:
    # `cfg.latent_layers`. `k` / `v` then have no layer at all.
    mat: Optional[jax.Array] = None
    conv: Optional[jax.Array] = None
    latent: Optional[jax.Array] = None


# A cache's per-slot fields, [layers, B, ...] each (`TransformerConfig.kept`,
# its family's statement, says which a configuration has, and their shapes).
# ROWS are appended a position at a time and masked by `lengths`: a slot's
# next occupant overwrites them whole. STATES (`Kept.rows` None) are read and
# rewritten by every step: they start from zeros.
ROWS = ("k", "v", "ring_k", "ring_v", "latent")
STATES = ("state", "mat", "conv")


def init_state(cfg: TransformerConfig, batch: int, dtype=None) -> dict:
    """{field: zeros} of `KVCache`'s STATES for `batch` new sequences, by
    what the configuration's layers keep (`cfg.kept`); {} for a model whose
    sequences keep nothing but their rows."""
    return {name: jnp.zeros((kept.layers, batch, *kept.shape_of(name)),
                            kept.dtype or dtype or cfg.dtype)
            for kept in cfg.kept() if kept.rows is None
            for name in kept.fields}


def init_cache(cfg: TransformerConfig, batch: int, max_len: int,
               dtype=None) -> KVCache:
    dtype = dtype or cfg.dtype
    rows = {name: jnp.zeros(
        (kept.layers, batch, kept.rows, *kept.shape_of(name)),
        kept.dtype or dtype)
            for kept in cfg.kept(max_len) if kept.rows is not None
            for name in kept.fields}
    for name in ("k", "v"):  # a family without K/V rows: no layer of them
        rows.setdefault(name, jnp.zeros(
            (0, batch, max_len, cfg.kv_heads, cfg.hd), dtype))
    return KVCache(lengths=jnp.zeros((batch,), jnp.int32), **rows,
                   **init_state(cfg, batch, dtype))


@jax.named_scope("attend_cached")
def _attend_cached(q, k_cache, v_cache, q_pos, kv_len_mask, sink=None,
                   sm_scale=None):
    """q [B,S,H,D] against the full cache [B,max_len,kvH,D] (the values may
    have a width of their own). `sink` [H]: a learned logit a query head
    that joins the softmax's denominator and carries no value
    (`ops.attention.softmax_with_sink`); `sm_scale`: the factor on the
    logits where it is not 1 / sqrt(D) (keys cached wider than they are).

    kv_len_mask [B, max_len] marks valid cache slots; q_pos [B,S] are the
    global positions of the queries (causal: key position <= q position).

    Attends by KV-head group on the cache's stored dtype and must never
    repeat or upcast the cache: a decode step reads every cache row of
    every layer, so any cache-sized temporary sets its time. With
    `jnp.repeat(cache, rep, axis=2).astype(float32)` the compiler wrote and
    re-read a float32 [16,2048,8,4,128] array (537 MB) for K and another
    for V in every layer, 2.6 GB of HBM traffic where the bf16 cache layer
    is 134 MB: 67% of a 72.5 ms decode step on the v5e (PERF.md, PR 24).
    The products of two bf16 values are exact in float32, so float32
    accumulation (`preferred_element_type`) gives the same logits; the
    probabilities stay float32 and V is upcast inside the fusion.

    Its sibling rule is the caller's (`_write_stack`): never copy a layer
    of the cache out of its stack to get here. Who gets here: a prefill
    behind a prefix or into a longer cache (S > 1, over the indexed layer),
    `PagedBatcher`'s gathered view, and off the chip, or at a shape a kernel
    does not take, a decode step and a prefill from position 0. On the chip
    a decode step over a stack reads only the rows held and a prefill from
    position 0 never writes its [S, S] logits (`attend_held`): here they
    cross HBM three times in float32, 30% of a 2,048-token prefill of 32
    heads (PERF.md, PR 53).
    """
    b, s, h, d = q.shape
    t, kvh = k_cache.shape[1], k_cache.shape[2]
    q5 = q.reshape(b, s, kvh, h // kvh, d)  # heads of one KV group adjoin
    logits = jnp.einsum("bsgrd,btgd->bgrst", q5, k_cache,
                        preferred_element_type=jnp.float32)
    logits = logits / (d ** 0.5) if sm_scale is None else logits * sm_scale
    key_pos = jnp.arange(t)
    causal = q_pos[:, None, None, :, None] >= key_pos
    mask = kv_len_mask[:, None, None, None, :] & causal
    logits = jnp.where(mask, logits, NEG_INF)
    probs = attention_ops.softmax_with_sink(
        logits, None if sink is None else sink.astype(jnp.float32).reshape(
            1, kvh, h // kvh, 1, 1))
    out = jnp.einsum("bgrst,btgd->bsgrd", probs, v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s, h, v_cache.shape[-1]).astype(q.dtype)


def _write_layer(k_cache, v_cache, k, v, positions):
    """`_attention_cached`'s plain cache access: `k_cache` / `v_cache` are ONE
    layer [B, max_len, kvH, D]; fresh K/V [B, S, kvH, D] go to each
    sequence's `positions` [B, S], and the layer is what attention reads."""
    bidx = jnp.arange(k.shape[0])[:, None]
    k_cache = k_cache.at[bidx, positions].set(k.astype(k_cache.dtype))
    v_cache = v_cache.at[bidx, positions].set(v.astype(v_cache.dtype))
    return k_cache, v_cache, (k_cache, v_cache)


class StackLayer(NamedTuple):
    """Where a layer's rows lie in a cache that is a stack: the stacks
    [N, B, T, kvH, D] and the layer's index, NOT a view cut out of them.
    Keys wider than the values lie in pieces of the values' width
    (`ops.attention.key_pieces`)."""
    k: jax.Array
    v: jax.Array
    layer: Any  # int32 scalar

    def view(self):
        """The layer as dense [B, T, kvH, D] arrays, read by index: what a
        prefill into a longer cache and a step off the chip attend over."""
        return (attention_ops.whole_keys(lax.dynamic_index_in_dim(
            self.k, self.layer, keepdims=False), self.v.shape[3]),
                lax.dynamic_index_in_dim(self.v, self.layer, keepdims=False))


class FreshRows(NamedTuple):
    """A prefill from position 0, as its cache access says it: the fresh
    K/V [B, S, kvH, D] of positions 0..S-1 are every row there is, and the
    queries they are attended by are the same S positions."""
    k: jax.Array
    v: jax.Array


# What the fresh rows of a prefill were attended with while the body ran,
# "flash" or "dense" (`attend_fresh`): this choice's share of
# `traced.booked()`.
fresh_rows_attended = functools.partial(traced.booked, "fresh_rows")


def attend_fresh(q, fresh: FreshRows, sink=None, sm_scale=None):
    """A prefill from position 0 through the flash forward kernel where it
    takes the shape (`flash_attention_takes`), else None: the caller then
    runs the spelling it has (`_attend_cached`; a latent prefill's
    `kimi_linear._attend_expanded`). ONE rule and one booking for every
    prefill's fresh rows: which of the two a program was traced with is
    booked here, "flash" or "dense" (`fresh_rows_attended`). No caller has
    a sink over fresh rows, and the kernel has none."""
    flash = sink is None and attention_ops.flash_attention_takes(q, *fresh)
    traced.book("fresh_rows", "flash" if flash else "dense")
    if not flash:
        return None
    # values of a width of their own: the forward reads a KV head where it
    # lies, by index; else the heads are repeated
    kv = fresh if fresh.k.shape[-1] != fresh.v.shape[-1] else \
        attention_ops.gqa_expand(*fresh, q.shape[2])
    return attention_ops.flash_attention(q, *kv, causal=True,
                                         sm_scale=sm_scale)


def attend_held(q, held, q_pos, kv_len_mask, rows=None, sink=None,
                sm_scale=None):
    """q [B, S, H, D] against what a cache access returned as `held`: a
    dense (k, v) [B, T, kvH, D] pair, a `StackLayer` or `FreshRows`
    (`sink`, `sm_scale`: `_attend_cached`'s). One
    token a sequence (S == 1) over a stack whose caller states `rows` [B],
    how many rows each slot holds (a prefix; 0: the slot takes no part),
    goes to the kernel that reads those rows in the stack and nothing else
    (`ops.attention.decode_attention`; on a TPU, as `flash_attention`).

    A prefill from position 0 (`FreshRows`) goes to `flash_attention` where
    its forward kernel takes the shape (`attend_fresh`): the causal
    rule is the whole mask there. A real query at position i sees keys 0..i,
    all real, so `kv_len_mask` has nothing left to hide; a pad row sees other
    keys than under the mask, and is as unused as before: its K/V lie past
    the sequence's length, `row_mask` keeps it out of the experts' counts and
    out of a state. The kernel multiplies the operands as they arrive and
    casts the probabilities to their dtype before the weighted sum, which at
    default precision is what the MXU makes of `_attend_cached`'s float32
    ones.

    Everything else is `_attend_cached` over the dense rows under
    `kv_len_mask` and the causal rule. What decides is in the arguments:
    no option, no model's name."""
    if isinstance(held, FreshRows):
        with jax.named_scope("attend_cached"):
            out = attend_fresh(q, held, sink, sm_scale)
        if out is not None:
            return out
    if isinstance(held, StackLayer):
        step = rows is not None and q.shape[1] == 1
        kernel = step and attention_ops.decode_attention_takes(held.k, held.v)
        if step:
            traced.book("held_rows", "kernel" if kernel else "dense")
        if kernel:
            with jax.named_scope("attend_cached"):
                return attention_ops.decode_attention(
                    q[:, 0], held.k, held.v, held.layer, rows, sink=sink,
                    sm_scale=sm_scale)[:, None]
        held = held.view()
    return _attend_cached(q, *held, q_pos, kv_len_mask, sink, sm_scale)


def _write_stack(layer):
    """Cache access for a layer scan that CARRIES the whole stack
    [L, B, max_len, kvH, D]: the rows are scattered into the stack at
    [layer, sequence, position], in place, and what attention gets is the
    written stack and the layer (`StackLayer`), not a view: `attend_held`
    reads the held rows where they lie (the new token's own among them),
    and with the stack donated the program holds no temporary of a layer's
    size (`tests/test_chip_compile.py`). XLA left to index the layer fused
    the index into one full-length pass in three configurations and copied
    the layer out in the fourth (PERF.md, PR 35).

    `positions` [B, S] are each sequence's S CONSECUTIVE positions, as
    every engine writes a cache. The one thing read off the shapes rests
    on that: S rows into a cache of S rows are the whole layer, positions
    0..S-1, written as one slice (2,048 row scatters into the carried stack
    cost a 2,048-token prefill 2 ms more than the parent's, PERF.md, PR 26)
    and handed to attention as what they are, `FreshRows`."""

    def access(k_cache, v_cache, k, v, positions):
        k, v = k.astype(k_cache.dtype), v.astype(v_cache.dtype)
        fresh = k
        # keys wider than the values are kept in pieces of the values' width
        k = attention_ops.key_pieces(k, k_cache.shape[-1])
        if k.shape[1] == k_cache.shape[2]:
            # a batcher's prefill into a row cache of its bucket's length:
            # the fresh K/V ARE the layer, no row is scattered or read back
            return (lax.dynamic_update_index_in_dim(k_cache, k, layer, 0),
                    lax.dynamic_update_index_in_dim(v_cache, v, layer, 0),
                    FreshRows(fresh, v))
        bidx = jnp.arange(k.shape[0])[:, None]
        k_cache = k_cache.at[layer, bidx, positions].set(k)
        v_cache = v_cache.at[layer, bidx, positions].set(v)
        return k_cache, v_cache, StackLayer(k_cache, v_cache, layer)

    return access


def _attention_cached(cfg: TransformerConfig, x, p, lora, positions,
                      k_cache, v_cache, kv_len_mask, access=_write_layer,
                      rows=None):
    """The attention half of a decoder block against cached K/V. Returns
    (x, k_cache, v_cache): the residual stream after attention and the
    caches with this call's K/V written at `positions`.

    The cache is touched in one place, by `access(k_cache, v_cache, k, v,
    positions) -> (k_cache, v_cache, held)`: it writes the fresh, rotated
    K/V wherever its cache keeps them and returns the caches with what
    attention reads (`attend_held`): a dense (k, v) [B, max_len, kvH, D]
    pair, the stack and the layer, or the fresh rows of a prefill from
    position 0. One layer's cache (the default), the carried stack
    (`_write_stack`) and `PagedBatcher`'s page pool each bring their own;
    the block around it has this one spelling. `rows` [B]
    is a decode step's statement of the rows each slot holds."""
    scale = cfg.lora_alpha / cfg.lora_rank if cfg.lora_rank else 0.0
    y = _rms_norm(x, p["ln_attn"], cfg.norm_eps)
    q, k, v = _qkv(y, p, lora, scale, lambda name: _lora_xa(y, lora[name]))
    if cfg.qk_norm:
        q, k = _qk_norm(cfg, q, k, p)
    q = _rope(q, positions, cfg.rope_theta)
    k = _rope(k, positions, cfg.rope_theta)

    k_cache, v_cache, held = access(k_cache, v_cache, k, v, positions)
    attn = attend_held(q, held, positions, kv_len_mask, rows)
    attn = jnp.einsum("bsnd,ndh->bsh", attn, p["wo"].astype(attn.dtype))
    if cfg.sandwich:
        attn = _rms_norm(attn, p["ln_attn_post"], cfg.norm_eps)
    return x + attn, k_cache, v_cache


def layers_to_scan(cfg: TransformerConfig, params):
    """(tree, whole): the per-layer tree a layer scan runs over, and the
    leaves that stay whole. The tree holds the stacked blocks (and
    adapters) and each layer's index `tree["i"]`, by which the body
    reaches what is not scanned: the KV cache it carries, and a sparse
    model's expert weights. Those are not scanned: the body merges `whole`
    into its layer's parameters and hands `_block_cached` the index, so
    that the grouped matmuls read the stack in place
    (`transformer._grouped_matmul`)."""
    blocks, whole = params["blocks"], {}
    if cfg.num_experts:
        whole = {n: blocks[n] for n in ("wi_gate", "wi_up", "wo_mlp")}
    tree = {"p": {n: a for n, a in blocks.items() if n not in whole},
            "i": jnp.arange(cfg.layers)}
    if params.get("lora") is not None:
        tree["l"] = params["lora"]
    return tree, whole


def _block_cached(cfg: TransformerConfig, x, p, lora, positions,
                  k_cache, v_cache, kv_len_mask, row_mask, layer=None,
                  access=_write_layer, state=None, route=None, rows=None):
    """One decoder block against cached K/V. Returns ((x, k_cache, v_cache,
    state, route), counted): the caches with this call's K/V written at
    `positions` by `access` (`_attention_cached`), and what the expert layer
    counted, a tuple: () from a dense layer (which does not read
    `row_mask`); the assignments each expert received from the rows
    `row_mask` [B,S] marks as real; and behind them, from a router that
    chooses one expert a token, every row's choice. With `layer`, `p`'s
    expert weights are the whole stacks (`layers_to_scan`).

    `state` and `route` are None but for the sublayers that carry them
    (the family's, `families.SUBLAYERS`): the stateful attention's state
    stack, which it reads and rewrites at `layer` as `row_mask` says, and the
    router's representation, which goes from this layer to the next."""
    if cfg.attention == "cca":
        x, k_cache, v_cache, state = families.of(cfg).attention_cached(
            cfg, x, p, positions, k_cache, v_cache, state, kv_len_mask,
            row_mask, layer, access, rows)
    else:
        x, k_cache, v_cache = _attention_cached(
            cfg, x, p, lora, positions, k_cache, v_cache, kv_len_mask, access,
            rows)
    y = _rms_norm(x, p["ln_mlp"], cfg.norm_eps)
    if cfg.num_experts:
        routing, chosen = None, ()
        if cfg.router == "zaya_mlp":
            routing, route = families.of(cfg).router(
                cfg, y.reshape(-1, y.shape[-1]), p, route)
            chosen = (routing[1][:, 0],)
        out, load = moe_dropless(cfg, y, p, row_mask, layer, routing)
        return (x + out, k_cache, v_cache, state, route), (load, *chosen)
    scale = cfg.lora_alpha / cfg.lora_rank if cfg.lora_rank else 0.0
    with jax.named_scope("mlp"):
        act = _gated(y, p, lora, scale, lambda name: _lora_xa(y, lora[name]))
        out = jnp.einsum("bsm,mh->bsh", act, p["wo_mlp"].astype(act.dtype))
        if cfg.sandwich:
            out = _rms_norm(out, p["ln_mlp_post"], cfg.norm_eps)
    return (x + out, k_cache, v_cache, state, route), ()


def forward_cached(cfg: TransformerConfig, params, tokens, positions,
                   cache: KVCache, kv_len_mask, row_mask,
                   access=_write_stack, rows=None):
    """Forward [B,S] tokens through all layers, reading+writing the cache.
    The one layer loop over a cache: every engine's prefill and decode
    program is this function under its own masks.

    Returns (logits [B,S,V], new_cache, aux). The layer stack is a lax.scan
    over the stacked params (one compiled block body) that CARRIES
    `cache.k` / `cache.v` beside the residual stream: each layer writes
    this call's rows into the stack in place and attends against its own
    layer of it (`_write_stack`, `attend_held`). A jitted caller that loops
    donates `cache` and keeps `new_cache`: then the step changes B x S rows
    of each layer and copies nothing, where a scan over the cache's layers
    copied every layer out of the stack and back (42% of a decode step,
    PERF.md, PR 26). Without donation the stack is copied once a call.
    `positions` [B,S] are each sequence's S consecutive positions.

    `access(layer)` is the layer's cache access (`_attention_cached`), and
    `cache.k` / `cache.v` are whatever it indexes: the stack
    [L, B, max_len, kvH, D] for the default, the page pools
    [L, pages, page_size, kvH, D] for `PagedBatcher`'s. The loop only
    carries them.

    `aux` is {} for a dense model; for a sparse one {"expert_load": int32
    [E]}, the assignments each expert received summed over the layers, from
    the rows `row_mask` [B,S] marks as real (a prompt's positions below its
    length, a decode step's active slots): pad rows and free slots are
    computed, not counted. A router that gives a token ONE expert adds
    {"expert_choice": int32 [L, B*S]}, every row's expert in every layer: a
    near-tie that rounding flips there is a whole other expert, so a
    comparison with a reference has to know the route that was taken. A
    looped model (`cfg.loop_steps` > 1) gives {"exit_pdf": float32 [passes,
    B, S]}, the probability that a token leaves the loop at each pass
    (`transformer.exit_pdf` of the exit gate's outputs); at the threshold 1
    the logits are the last pass's whatever it says.

    `rows` [B] is a decode step's (S == 1) own statement of how many rows
    each sequence holds once its token is written, 0 for a slot that takes
    no part: with it the step's attention reads those rows and no others
    (`attend_held`). The values are the device's (`cache.lengths`), so one
    program serves every mix of lengths. A prefill leaves it None.

    `row_mask` also tells a stateful attention (`cache.state`, carried and
    written in place beside `cache.k` / `cache.v`) which of this call's
    positions is each sequence's last: the state it leaves is that
    position's, and a sequence with no real row keeps the state it had.

    A layer pattern (`cfg.layer_kinds`, `families.PATTERNS`) has a loop of
    its own over what its kinds of layers keep (this carry plus a ring;
    matrix states, convolution windows and latent rows):
    `pattern.forward_cached` over the one layer its family states, or the
    family's own `forward_cached` (LongCat's scan of double layers).
    """
    if cfg.layer_kinds:
        run = getattr(families.of(cfg), "forward_cached", None)
        if run is None:  # `pattern` imports this module
            from ray_tpu.models.pattern import forward_cached as run
        return run(cfg, params, tokens, positions, cache, kv_len_mask,
                   row_mask, access, rows)
    x = params["embed"].astype(cfg.dtype)[tokens]
    layer_tree, whole = layers_to_scan(cfg, params)
    route = None
    if cfg.router == "zaya_mlp":  # the first layer's router adds nothing
        route = jnp.zeros((tokens.size, cfg.router_hidden), jnp.float32)

    def layers(carry, first=None):
        """The ONE scan over the stacked layers. `first`: the cache layer of
        this pass's layer 0 where the model runs its layers more than once
        (None: a layer's rows are cache layer `i`, the program as it was)."""
        def body(carry, layer):
            x, k_cache, v_cache, state, route = carry
            at = layer["i"] if first is None else first + layer["i"]
            return _block_cached(
                cfg, x, dict(layer["p"], **whole), layer.get("l"), positions,
                k_cache, v_cache, kv_len_mask, row_mask, layer["i"],
                access(at), state, route, rows)

        return lax.scan(body, carry, layer_tree)

    if cfg.loop_steps > 1:
        # A looped model: the same stacked parameters every pass, read in
        # place (the inner scan's body is compiled once and the outer loop is
        # not unrolled); pass t's layer i writes and attends cache layer
        # `t * layers + i` of the carried stacks, and the final norm's output
        # is the next pass's input
        def a_pass(carry, t):
            (x, k_cache, v_cache, _, _), _ = layers(
                (*carry, None, None), t * cfg.layers)
            x, lam = pass_end(cfg, params, x)
            return (x, k_cache, v_cache), lam

        (x, new_k, new_v), lam = lax.scan(
            a_pass, (x, cache.k, cache.v), jnp.arange(cfg.loop_steps))
        # threshold 1 (`transformer.check`): every token leaves at the last
        return (lm_head(cfg, params, x, normed=True),
                KVCache(new_k, new_v, cache.lengths),
                {"exit_pdf": exit_pdf(lam)})
    (x, new_k, new_v, new_state, _), counted = layers(
        (x, cache.k, cache.v, cache.state, route))
    aux = dict(zip(("expert_load", "expert_choice"), counted))
    if aux:
        aux["expert_load"] = aux["expert_load"].sum(0)
    return (lm_head(cfg, params, x),
            KVCache(new_k, new_v, cache.lengths, new_state), aux)


def lm_head(cfg: TransformerConfig, params, x, normed: bool = False):
    """Every cached forward's end: the stream x [B, S, h] through the final
    norm (`normed`: a looped model's last pass has applied it) and the
    output matrix (the embedding's transpose where they are tied) -> logits
    [B, S, V]."""
    if not normed:
        x = _rms_norm(x, params["ln_f"], cfg.norm_eps)
    with jax.named_scope("lm_head"):
        unembed = params.get("unembed")
        if unembed is None:
            unembed = params["embed"].T
        return jnp.einsum("bsh,hv->bsv", x, unembed.astype(x.dtype))


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Reference surface: vLLM SamplingParams (the subset that matters)."""

    max_tokens: int = 64
    temperature: float = 0.0  # 0 = greedy
    top_k: int = 0  # 0 = no top-k filter
    stop_token_id: Optional[int] = None


def _sample(logits, rng, temperature: float, top_k: int):
    """logits [B,V] → token ids [B]."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits.astype(jnp.float32) / temperature
    if top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, NEG_INF, logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


class Generator:
    """Compiled prefill + decode loop over one parameter set.

    Built once per (batch, max_len) shape bucket; generate() runs
    prompts → completions without recompiling.
    """

    def __init__(self, cfg: TransformerConfig, params, max_len: int = 512):
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        # both take a cache to keep: the caller holds only what they return
        self._prefill = jax.jit(self._prefill_impl, donate_argnums=(3,))
        self._decode = jax.jit(
            self._decode_impl, static_argnames=("temperature", "top_k"),
            donate_argnums=(2,))

    def _prefill_impl(self, params, tokens, lengths, cache):
        b, s = tokens.shape
        positions = jnp.arange(s)[None, :].repeat(b, 0)
        kv_mask = jnp.arange(self.max_len)[None, :] < lengths[:, None]
        logits, cache, _ = forward_cached(
            self.cfg, params, tokens, positions, cache, kv_mask,
            kv_mask[:, :s])
        # logits at each prompt's LAST real token
        last = jnp.take_along_axis(
            logits, (lengths - 1)[:, None, None].repeat(
                logits.shape[-1], -1), axis=1)[:, 0]
        return last, cache._replace(lengths=lengths)

    def _decode_impl(self, params, tok, cache, rng, *, temperature, top_k):
        b = tok.shape[0]
        positions = cache.lengths[:, None]  # next slot per sequence
        kv_mask = jnp.arange(self.max_len)[None, :] <= cache.lengths[:, None]
        logits, cache, _ = forward_cached(
            self.cfg, params, tok[:, None], positions, cache, kv_mask,
            jnp.ones((b, 1), bool), rows=cache.lengths + 1)
        nxt = _sample(logits[:, 0], rng, temperature, top_k)
        return nxt, cache._replace(lengths=cache.lengths + 1)

    def _decode_loop(self, prompts, sampling: SamplingParams, seed: int):
        """Prefill `prompts`, then yield (tokens [B], full [B]) for each of
        at most `max_tokens` steps: the tokens sampled at this step and
        which sequences have no cache row left for another. The consumer
        decides who has stopped and closes the loop when all have."""
        import numpy as np

        lens = np.array([len(p) for p in prompts], np.int32)
        s = int(lens.max())
        if s >= self.max_len:
            # JAX silently drops out-of-bounds cache scatters — without
            # this check an over-long prompt would "generate" garbage
            raise ValueError(
                f"prompt length {s} >= max_len "
                f"{self.max_len}; raise Generator(max_len=...)")
        toks = np.zeros((len(prompts), s), np.int32)
        for i, p in enumerate(prompts):
            toks[i, : len(p)] = p
        cache = init_cache(self.cfg, len(prompts), self.max_len)
        last_logits, cache = self._prefill(
            self.params, jnp.asarray(toks), jnp.asarray(lens), cache)
        rng = jax.random.key(seed)
        rng, k0 = jax.random.split(rng)
        tok = _sample(last_logits, k0, sampling.temperature, sampling.top_k)
        for _ in range(sampling.max_tokens):
            yield np.asarray(tok), np.asarray(cache.lengths) >= self.max_len
            rng, k = jax.random.split(rng)
            tok, cache = self._decode(
                self.params, tok, cache, k,
                temperature=sampling.temperature, top_k=sampling.top_k)

    def generate(self, prompts, sampling: Optional[SamplingParams] = None,
                 seed: int = 0):
        """prompts: list of int32 token-id lists → list of completions
        (token-id lists, stop token excluded)."""
        sampling = sampling or SamplingParams()
        outs = [[] for _ in prompts]
        done = [False] * len(prompts)
        for tok, full in self._decode_loop(prompts, sampling, seed):
            for i, t in enumerate(tok.tolist()):
                if done[i]:
                    continue
                if t == sampling.stop_token_id:
                    done[i] = True
                    continue
                outs[i].append(t)
                # a sequence whose next KV slot is out of room stops alone —
                # cache rows are per-sequence, so others keep decoding
                done[i] = bool(full[i])
            if all(done):
                break
        return outs

    def generate_stream(self, prompt, sampling: Optional[SamplingParams] = None,
                        seed: int = 0):
        """Single-prompt streaming: yields one token id at a time (the
        Serve LLM deployment's token-stream path)."""
        sampling = sampling or SamplingParams()
        for tok, full in self._decode_loop([list(prompt) or [0]], sampling,
                                           seed):
            t = int(tok[0])
            if t == sampling.stop_token_id:
                return
            yield t
            if full[0]:
                return
