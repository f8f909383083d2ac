"""A layer pattern: window layers beside full ones (poolside/Laguna-S-2.1,
`model_type` laguna; XiaomiMiMo/MiMo-V2-Flash, `model_type` mimo_v2_flash:
"What the second model states", below). Imported only where a configuration
has one
(`families.PATTERNS`); the cache's slots, the grouped attention, the expert
matmuls, sampling and the scheduler are the other models'
(`decoding._attend_cached` / `_write_stack`, `transformer.moe_dropless`),
and what the patterns share is `pattern.py`'s.

**Layers.** One leading layer of full attention with a dense SwiGLU MLP,
then periods of `layer_kinds` (window, window, window, full), every one with
sparse experts. The two kinds differ in their number of query heads
(`heads` full, `window_heads` window; the same `kv_heads`), so `wq` / `wo`
cannot share a stack: parameters are stacked BY KIND, `blocks["full"]`
[full layers, ...], `blocks["window"]` [window layers, ...], `blocks["sparse"]`
[sparse layers, ...] (its expert stacks hold the `experts_held` alone and are
read in place by `_grouped_matmul(layer=...)`), `blocks["dense"]` the leading
MLP alone. The loop is `pattern.forward_cached`'s over `layer` here: the
leading layer, then ONE `lax.scan` over periods whose body unrolls a period's
layers.

**Attention of kind K** on the normed stream y: `q = y Wq_K` [n_K, D], `k`,
`v` [kv_heads, D]; RoPE by kind (full: `rope_theta` over the first
`partial_rotary` of a head, YaRN's frequencies, cos and sin times its
attention factor; window: `window_rope_theta`, plain, the whole head);
causal softmax attention, a window layer over the last `window` positions
(`i - j < window`: the token itself counts); `head_gate`: `o_r <- sigmoid(y
Wg_K)_r o_r` before `Wo_K`.

**Two kinds of rows in one carry.** A full layer's rows are slots of
`max_len` (`KVCache.k` / `.v`, written by `_write_stack`). A window layer
keeps `window` rows a sequence in a RING (`KVCache.ring_k` / `.ring_v`):
position p at row p mod window. A decode step (S = 1) writes row `len mod
window`, which held the position that has just left the window, and attends
over the rows that hold a position (`p - ((p - r) mod window) >= 0`); every
key is already rotated by its own position, so the ring's order does not
matter. A call with S > 1 is a prefill FROM POSITION 0 (every engine's):
attention runs over the fresh rows in a BAND (query blocks of `window`
against their own block and the one before: S x 2 window logits a head, not
S x S), and the ring it leaves is the last `window` positions of each
sequence's TRUE length (`row_mask`), zero where there is none, the whole
ring overwritten.

**Experts.** `moe_router` over all `num_experts` (renormalised top-k times
`routed_scale`), `moe_dropless` with `experts_held` for the experts whose
weights are here, and the shared expert (`moe.shared`), a dense SwiGLU that
every token runs, added ungated. The chips that share a layer each hold a
share of its experts; what the absent ones would add is left out here, and
nothing stands in for the other chip or for the exchange.

**What the second model states** (every field off or 0 is the first's).
`lead_kind` "": `layer_kinds` is EVERY layer's kind in order (full, 4 window,
full, then 5 window + full: no whole periods), the first a full layer with
the dense MLP; the loop is read off the list (`pattern.cut`: a run of one
kind is ONE scan, a single layer is unrolled), where periods are one scan
over periods. `window_kv_heads`: a KV-head count BY KIND. `value_dim`: values
narrower than the keys (192 beside 128): the keys and queries are then
carried in whole pieces of the values' width (`key_row`: 256, zeros behind
the 192, `sm_scale` stays 1 / sqrt(192)), because the cache keeps a head's
key as adjoining pieces of one lane tile (`ops.attention.key_pieces`; PERF.md
section 6, PR 58 says what the compiler refused). `window_partial_rotary`:
a window layer rotates a share of the head as a full one does. `value_scale`:
`v <- scale v`. `window_sink`: one learned logit a query head of a window
layer (`blocks["window"]["sink"]`) that joins the softmax's denominator and
carries no value: in the band, the ring's step and the dense spelling the
same one term (`ops.attention.softmax_with_sink`, the decode kernel's
starting state). `router_score` "sigmoid": `kimi_linear.router` (a stored
selection bias). No gate, no shared expert: `head_gate`,
`shared_expert_hidden` off.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.models.decoding import (
    StackLayer, _attend_cached, _write_stack, attend_held,
)
from ray_tpu.models.families import Kept
from ray_tpu.models.kimi_linear import router as sigmoid_router
from ray_tpu.models.pattern import (  # noqa: F401 (the family's three)
    EXPERT_LEAVES, _swiglu, _take, init_params, long_prompt, mlp_leaves,
    num_params, param_axes, rows_at_a_time, sparse_mlp,
)
from ray_tpu.models.transformer import (
    TransformerConfig, _rms_norm, _rope, moe_router,
)
from ray_tpu.ops.attention import NEG_INF, key_pieces, softmax_with_sink

RUN_MAX = 6  # the longest unit `runs` looks for: 5 window layers and a full

# -- the family (`families.py`) ---------------------------------------------------
FIELDS = frozenset({
    "layer_kinds", "window", "window_heads", "rope_yarn", "partial_rotary",
    "head_gate", "dense_mlp_hidden", "shared_expert_hidden", "experts_held",
    "lead_kind", "window_kv_heads", "value_dim", "window_sink", "value_scale",
    "window_partial_rotary", "router_score"})


def check(cfg: TransformerConfig) -> None:
    if cfg.lead_kind == "":  # the list form: every layer named
        if cfg.layers != len(cfg.layer_kinds) or cfg.layers < 2 \
                or cfg.layer_kinds[0] != "full":
            raise ValueError(
                f"with lead_kind '' layer_kinds names every one of the "
                f"{cfg.layers} layers, the first a full one (it has the "
                f"dense MLP): {cfg.layer_kinds!r}")
    elif cfg.lead_kind != "full" or cfg.layers < 2 \
            or (cfg.layers - 1) % len(cfg.layer_kinds):
        raise ValueError(
            f"layers {cfg.layers} is not one leading layer and whole "
            f"periods of {cfg.layer_kinds!r}")
    if not (cfg.window > 0 and cfg.window_heads and cfg.num_experts
            and cfg.dense_mlp_hidden):
        raise ValueError("a pattern of window and full layers needs window, "
                         "window_heads, num_experts and dense_mlp_hidden")
    if cfg.window_heads % kv_heads(cfg, "window") \
            or cfg.heads % kv_heads(cfg, "full"):
        raise ValueError("both kinds' query heads are whole groups of "
                         "kv_heads (a window layer's: window_kv_heads)")
    if cfg.rope_yarn is not None and len(cfg.rope_yarn) != 5:
        raise ValueError("rope_yarn is (factor, original positions, "
                         "beta_fast, beta_slow, attention_factor)")
    if cfg.value_dim > cfg.hd:
        raise ValueError("value_dim is at most the keys' head_dim")


def sparse_layers(cfg: TransformerConfig) -> int:
    """The layers that route: all but the first, in both forms."""
    return cfg.layers - 1


def kv_heads(cfg: TransformerConfig, kind: str) -> int:
    """A layer of `kind`'s KV heads."""
    return cfg.window_kv_heads or cfg.kv_heads if kind == "window" \
        else cfg.kv_heads


def value_dim(cfg: TransformerConfig) -> int:
    return cfg.value_dim or cfg.hd


def key_row(cfg: TransformerConfig) -> int:
    """The width queries and keys are carried and cached in: `hd`, or,
    beside narrower values, `hd` in whole pieces of the values' width (of a
    lane tile at most): 192 beside 128 lies in 256, zeros behind it."""
    piece = min(value_dim(cfg), 128)
    return -(-cfg.hd // piece) * piece


def kept(cfg: TransformerConfig, max_len: int):
    """A full layer's K/V rows in slots of `max_len`, a window layer's in a
    ring of `window` beside them; each kind's rows by its own KV heads, and
    keys wider than the values in pieces of the values' width
    (`ops.attention.key_pieces`)."""
    dv, pieces = value_dim(cfg), key_row(cfg) // value_dim(cfg)

    def rows(fields, kind, n):
        kvh = kv_heads(cfg, kind)
        if pieces == 1:
            return Kept(fields, cfg.layers_of(kind), n, (kvh, cfg.hd))
        return Kept(fields, cfg.layers_of(kind), n, (kvh * pieces, dv),
                    shapes=((kvh * pieces, dv), (kvh, dv)))

    return (rows(("k", "v"), "full", max_len),
            rows(("ring_k", "ring_v"), "window", cfg.window))


# -- parameters --------------------------------------------------------------

def leaves(cfg: TransformerConfig) -> dict:
    """{(group, ..., name): (shape, init, logical axes)} of every parameter
    leaf; `init` a fan-in, None (a norm's weight: ones) or the name of one of
    `special`'s."""
    h, d, dv = cfg.hidden, cfg.hd, value_dim(cfg)
    out = {("embed",): ((cfg.vocab_size, h), h, ("vocab", "embed")),
           ("unembed",): ((h, cfg.vocab_size), h, ("embed", "vocab")),
           ("ln_f",): ((h,), None, ("norm",))}
    for kind, n, nh in (("full", cfg.full_layers, cfg.heads),
                        ("window", cfg.window_layers, cfg.window_heads)):
        at, nkv = ("blocks", kind), kv_heads(cfg, kind)
        out[at + ("wq",)] = ((n, h, nh, d), h,
                             ("layers", "embed", "heads", "head_dim"))
        out[at + ("wk",)] = ((n, h, nkv, d), h,
                             ("layers", "embed", "kv_heads", "head_dim"))
        out[at + ("wv",)] = ((n, h, nkv, dv), h,
                             ("layers", "embed", "kv_heads", "head_dim"))
        out[at + ("wo",)] = ((n, nh, dv, h), nh * dv,
                             ("layers", "heads", "head_dim", "embed"))
        if cfg.head_gate:
            out[at + ("wg",)] = ((n, h, nh), h, ("layers", "embed", "heads"))
        out[at + ("ln_attn",)] = ((n, h), None, ("layers", "norm"))
        if kind == "window" and cfg.window_sink:
            out[at + ("sink",)] = ((n, nh), "sink", ("layers", "heads"))
    out.update(mlp_leaves(cfg))
    return out


def special(cfg: TransformerConfig, key, shape, init: str):
    """The leaves a draw over a fan-in does not fit: the sinks normal about
    zero (a logit among logits whose spread is about one with these weights:
    a trained one is stored, zeros or a far value would leave the term
    unexercised); the sigmoid router's selection bias normal of 0.01, as
    the other families draw it."""
    std = {"sink": 1.0, "router_bias": 0.01}[init]
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(
        cfg.param_dtype)


# -- rotary embeddings by kind -------------------------------------------------

@functools.lru_cache(maxsize=None)
def rope_table(cfg: TransformerConfig, kind: str):
    """(rotated dimensions, their rot/2 inverse frequencies, the factor on
    cos and sin) of a layer of `kind`: a full layer's `rope_theta` over
    `partial_rotary` of the head, a window layer's `window_rope_theta` over
    `window_partial_rotary`; with `rope_yarn` (a full layer's alone) the
    frequencies are YaRN's (a dimension that turns
    more than `beta_fast` times within the original positions keeps its
    frequency, one that turns less than `beta_slow` times has it divided by
    `factor`, a linear ramp between) and cos and sin carry its attention
    factor."""
    theta, part, yarn = (cfg.rope_theta, cfg.partial_rotary, cfg.rope_yarn) \
        if kind == "full" else \
        (cfg.window_rope_theta, cfg.window_partial_rotary, None)
    rot = int(cfg.hd * part)
    inv = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    if yarn is None:
        return rot, inv.astype(np.float32), 1.0
    factor, original, beta_fast, beta_slow, attention_factor = yarn

    def dimension_of(turns):
        return rot * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(dimension_of(beta_fast)), 0)
    high = min(math.ceil(dimension_of(beta_slow)), rot - 1)
    if low == high:
        high += 0.001
    keep = 1.0 - np.clip((np.arange(rot // 2) - low) / (high - low), 0, 1)
    inv = inv / factor * (1 - keep) + inv * keep
    return rot, inv.astype(np.float32), float(attention_factor)


def full_rope_table(cfg: TransformerConfig):
    return rope_table(cfg, "full")


def rope(cfg: TransformerConfig, kind: str, x, positions):
    """x [B, S, heads, D] rotated by `kind`'s rule (rotate-half layout), and
    carried `key_row` wide: zeros behind the head where that is wider."""
    pad = key_row(cfg) - cfg.hd
    if kind == "window" and cfg.window_partial_rotary == 1.0 and not pad:
        return _rope(x, positions, cfg.window_rope_theta)
    rot, inv, scale = rope_table(cfg, kind)
    ang = positions[..., None].astype(jnp.float32) * inv  # [B, S, rot/2]
    cos = (jnp.cos(ang) * scale)[:, :, None, :]
    sin = (jnp.sin(ang) * scale)[:, :, None, :]
    x1, x2 = jnp.split(x[..., :rot].astype(jnp.float32), 2, axis=-1)
    turned = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    parts = [turned.astype(x.dtype), x[..., rot:]]
    if pad:
        parts.append(jnp.zeros((*x.shape[:-1], pad), x.dtype))
    return jnp.concatenate(parts, axis=-1)


# -- attention -----------------------------------------------------------------

def _attend_band(q, k, v, window: int, sink=None, sm_scale=None):
    """A prefill's window attention over its own fresh rows: q [B, S, H, D],
    k [B, S, kvH, D], v [B, S, kvH, Dv] at positions 0..S-1, query i against
    keys j with `0 <= i - j < window`. In blocks of `window` queries against
    their own block of keys and the one before: [H, S, 2 window] float32
    logits, where the full mask would take [H, S, S]. `sink` [H], `sm_scale`:
    `decoding._attend_cached`'s."""
    b, s, h, d = q.shape
    kvh, dv = k.shape[2], v.shape[3]
    if s <= window:  # every earlier position is inside the window
        pos = jnp.broadcast_to(jnp.arange(s), (b, s))
        return _attend_cached(q, k, v, pos, jnp.ones((b, s), bool), sink,
                              sm_scale)
    pad = -s % window
    if pad:  # pad keys lie behind every real query
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
    nb = (s + pad) // window
    q6 = q.reshape(b, nb, window, kvh, h // kvh, d)

    def with_before(rows):  # [B, nb, 2 window, kvH, D]
        blocks = rows.reshape(b, nb, window, kvh, rows.shape[-1])
        before = jnp.concatenate(
            [jnp.zeros_like(blocks[:, :1]), blocks[:, :-1]], axis=1)
        return jnp.concatenate([before, blocks], axis=2)

    k2, v2 = with_before(k), with_before(v)
    logits = jnp.einsum("bnqgrd,bntgd->bngrqt", q6, k2,
                        preferred_element_type=jnp.float32)
    logits = logits / (d ** 0.5) if sm_scale is None else logits * sm_scale
    # query a of a block is key a + window of its 2 window keys
    a, t = jnp.arange(window)[:, None], jnp.arange(2 * window)[None, :]
    mask = (t > a) & (t <= a + window)
    first = (jnp.arange(nb) == 0)[:, None, None]  # no block before block 0
    mask = jnp.where(first, mask & (t >= window), mask)  # [nb, w, 2w]
    logits = jnp.where(mask[None, :, None, None], logits, NEG_INF)
    probs = softmax_with_sink(
        logits, None if sink is None else sink.astype(jnp.float32).reshape(
            1, 1, kvh, h // kvh, 1, 1))
    out = jnp.einsum("bngrqt,bntgd->bnqgrd", probs, v2,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, nb * window, h, dv)[:, :s].astype(q.dtype)


def _ring_positions(last, window: int):
    """[B, window]: the position each ring row holds when a sequence's newest
    position is `last` [B]; negative where the row holds none yet."""
    last = last[:, None]
    return last - (last - jnp.arange(window)) % window


def _ring_attention(cfg: TransformerConfig, q, k, v, positions, row_mask,
                    ring_k, ring_v, layer, rows=None, sink=None,
                    sm_scale=None):
    """A window layer's cache access and attention. Returns (ring_k, ring_v,
    attention [B, S, H, Dv]); the rings are the stacks [window layers, B,
    window, kvH, D], written at `layer` in place (keys wider than the values
    in pieces, `ops.attention.key_pieces`). `rows` [B]: the positions
    a decode step's sequences hold (`forward_cached`); a ring holds the
    last `window` of them. `sink` [H], `sm_scale`: `_attend_cached`'s."""
    w = cfg.window
    b, s = q.shape[:2]
    k, v = k.astype(ring_k.dtype), v.astype(ring_v.dtype)
    if s == 1:  # a decode step: one row in, the rows the ring holds read once
        pos = positions[:, 0]
        bidx = jnp.arange(b)
        ring_k = ring_k.at[layer, bidx, pos % w].set(
            key_pieces(k[:, 0], ring_k.shape[-1]))
        ring_v = ring_v.at[layer, bidx, pos % w].set(v[:, 0])
        # while a sequence is shorter than the window its rows are the prefix
        # 0..pos, after that the whole ring; every row held is in the past,
        # so the causal rule has nothing to do
        attn = attend_held(
            q, StackLayer(ring_k, ring_v, layer), jnp.full((b, 1), w),
            _ring_positions(pos, w) >= 0,
            None if rows is None else jnp.minimum(rows, w), sink, sm_scale)
        return ring_k, ring_v, attn
    # a prefill from position 0: attention over the fresh rows, and the ring
    # as the sequence's TRUE last position leaves it
    attn = _attend_band(q, k, v, w, sink, sm_scale)
    held = _ring_positions(row_mask.sum(1).astype(jnp.int32) - 1, w)
    at = jnp.clip(held, 0, s - 1)[:, :, None, None]

    def kept(fresh):
        return jnp.where((held >= 0)[:, :, None, None],
                         jnp.take_along_axis(fresh, at, axis=1), 0)

    return (lax.dynamic_update_index_in_dim(
        ring_k, key_pieces(kept(k), ring_k.shape[-1]), layer, 0),
            lax.dynamic_update_index_in_dim(ring_v, kept(v), layer, 0), attn)


def attention(cfg: TransformerConfig, kind: str, x, p, positions, k_cache,
              v_cache, kv_len_mask, row_mask, layer, rows=None):
    """The attention half of a layer of `kind` ("full": `k_cache` / `v_cache`
    are the slots' stacks, written by `_write_stack`; "window": the ring
    stacks), `layer` its index within its kind. Returns (x, k_cache,
    v_cache). `rows`: `forward_cached`'s."""
    with jax.named_scope(f"attn.{kind}"):
        y = _rms_norm(x, p["ln_attn"], cfg.norm_eps)
        q = jnp.einsum("bsh,hnd->bsnd", y, p["wq"].astype(y.dtype))
        k = jnp.einsum("bsh,hnd->bsnd", y, p["wk"].astype(y.dtype))
        if cfg.value_scale == 1.0:
            v = jnp.einsum("bsh,hnd->bsnd", y, p["wv"].astype(y.dtype))
        else:  # the factor on the float32 sums: one rounding, as without it
            v = (jnp.einsum("bsh,hnd->bsnd", y, p["wv"].astype(y.dtype),
                            preferred_element_type=jnp.float32)
                 * cfg.value_scale).astype(y.dtype)
        q, k = rope(cfg, kind, q, positions), rope(cfg, kind, k, positions)
        # logits over the head's own width where it is carried wider
        sm_scale = None if key_row(cfg) == cfg.hd else cfg.hd ** -0.5
        if kind == "full":
            k_cache, v_cache, held = _write_stack(layer)(
                k_cache, v_cache, k, v, positions)
            attn = attend_held(q, held, positions, kv_len_mask, rows,
                               sm_scale=sm_scale)
        else:
            k_cache, v_cache, attn = _ring_attention(
                cfg, q, k, v, positions, row_mask, k_cache, v_cache, layer,
                rows, p.get("sink"), sm_scale)
        if cfg.head_gate:
            gate = jax.nn.sigmoid(jnp.einsum(
                "bsh,hn->bsn", y, p["wg"].astype(y.dtype),
                preferred_element_type=jnp.float32))
            attn = (attn * gate[..., None]).astype(attn.dtype)
        out = jnp.einsum("bsnd,ndh->bsh", attn, p["wo"].astype(attn.dtype))
    return x + out, k_cache, v_cache


# -- one layer (`pattern.forward_cached` walks them) ------------------------------

CARRIED = ("k", "v", "ring_k", "ring_v")  # beside the stream, in `layer`'s carry


def layer(cfg: TransformerConfig, call, kind: str, i, n, carry):
    """`pattern.forward_cached`'s one layer: attention of `kind` at layer
    `i` of its kind over the full layers' stacks or the window layers'
    rings, then the leading layer's dense MLP (`n` None; a long prompt's a
    piece at a time) or sparse layer `n`'s experts."""
    x, k, v, ring_k, ring_v = carry
    p = _take(call.blocks[kind], i)
    if kind == "full":
        x, k, v = attention(
            cfg, kind, x, p, call.positions, k, v, call.kv_len_mask,
            call.row_mask, i, call.rows)
    else:
        x, ring_k, ring_v = attention(
            cfg, kind, x, p, call.positions, ring_k, ring_v,
            call.kv_len_mask, call.row_mask, i, call.rows)
    counted = None
    if n is None:
        dense = call.blocks["dense"]

        def lead_mlp(rows):
            return _swiglu(_rms_norm(rows, dense["ln_mlp"], cfg.norm_eps),
                           dense["wi_gate"], dense["wi_up"], dense["wo_mlp"])

        with jax.named_scope("mlp"):
            x = x + (rows_at_a_time(lambda rows: (lead_mlp(rows), ()), x)[0]
                     if long_prompt(x) else lead_mlp(x))
    else:
        x, *counted = sparse_mlp(
            cfg, x, call.sparse(n), call.row_mask, n,
            sigmoid_router if cfg.router_score == "sigmoid" else moe_router)
    return (x, k, v, ring_k, ring_v), counted
