"""Plain reference of the Laguna-S-2.1 block (poolside/Laguna-S-2.1,
``model_type`` laguna): ``jax.numpy``, float32, no cache, no ring, no batching,
no sort, no grouped matmul, nothing from ``ray_tpu.models``.

Written from the keys of the model's ``config.json`` (the layer equations of
ISSUE 34's Tentpole); what the keys do not fix is listed under ``assumed`` in
``configs/laguna-s-2.1-serve-ep2-d5.json``, each item with its reason. This
sandbox has no network: where the published text differs from an item there,
the published text wins, and the difference is to be written down HERE (none
is known). One whole sequence at a time, every layer following
``layer_types[l]``, ``num_attention_heads_per_layer[l]`` and
``mlp_layer_types[l]`` of the configuration file, not the program's periods.
With y the RMS-normed stream (eps ``rms_norm_eps``, no bias anywhere):

1. Attention of layer l, n = ``num_attention_heads_per_layer[l]`` query heads,
   8 KV heads of 128: ``q = y Wq`` [n, 128], ``k = y Wk``, ``v = y Wv`` [8,
   128]; rotary embedding by the layer's type (below); ``s_ij = q_i . k_j /
   sqrt(128)`` for ``j <= i`` and, in a ``sliding_attention`` layer, ``i - j <
   sliding_window`` (the token itself counts); ``o = softmax_j(s) v``, query
   head r reading KV head ``r // (n / 8)``; ``gating`` per-head: ``g =
   sigmoid(y Wg)`` [n], ``o_r <- g_r o_r``; ``x <- x + concat(o) Wo``.
2. Rotary embedding, rotate-half layout. ``full_attention``: over the first
   ``partial_rotary_factor`` of a head's dimensions, ``rope_theta`` 5e5 with
   YaRN's frequencies (``factor``, ``original_max_position_embeddings``,
   ``beta_fast``, ``beta_slow``: a dimension that turns more than beta_fast
   times within the original positions keeps its frequency, one that turns
   less than beta_slow times has it divided by the factor, a linear ramp
   between), cos and sin times ``attention_factor``; the other dimensions pass.
   ``sliding_attention``: ``rope_theta`` 1e4, plain, the whole head.
3. ``mlp_layer_types[l]`` dense (layer 0): ``x <- x + (silu(y Wg) * (y Wu))
   Wd``, width ``intermediate_size``. sparse: ``p = softmax(y Wr)`` over all
   the published experts in float32; ``(w, e) = top-k(p)``,
   ``num_experts_per_tok``; ``w <- moe_routed_scaling_factor * w / sum(w)``
   (``norm_topk_prob``); ``x <- x + sum over j with e_j HELD of w_j
   SwiGLU_{e_j}(y) + SwiGLU_shared(y)``, each ``moe_intermediate_size`` wide.
4. Final RMSNorm, logits through ``unembed`` over the held rows.

**The share.** The configuration is one chip's share of a layer that two
chips hold (``deployment``): the router has all its published outputs, the
parameter tree holds the experts ``experts_held_first ..`` of every sparse
layer (as many as its expert stacks have) and the first ``vocab_size`` rows
of the vocabulary. What the absent experts would add is left out, here as in
the program, and that partial result goes on to the next layer.
``routed_part(..., first, count)`` is one share's part alone, so that a test
can add the shares up to the uncut layer.

It reads the program's parameter tree (``blocks["full" | "window"]``: ``wq
[L_kind, hidden, n, 128]``, ``wk``, ``wv``, ``wo [L_kind, n, 128, hidden]``,
``wg [L_kind, hidden, n]``, ``ln_attn``; ``blocks["dense"]``; ``blocks
["sparse"]``: ``router [Ls, hidden, E]``, ``wi_gate``, ``wi_up [Ls, held,
hidden, m]``, ``wo_mlp [Ls, held, m, hidden]``, ``shared_gate``, ``shared_up``,
``shared_down``, ``ln_mlp``), because the comparison needs the same weights.
Every matmul runs under ``default_matmul_precision("highest")``;
``precision="bfloat16"`` instead computes every matmul on bfloat16 operands
with a bfloat16 accumulator (``reference_zaya._mm``; all but the routed
experts', which stay at the highest): the nearest precision below the system's
bfloat16 products with float32 sums, which the check's limits must refuse. ``drop`` names a part to leave out or swap, which they
must refuse too: "gate", "shared", "window" (full attention in a window
layer), "rope" (each kind rotated by the other kind's rule), "scale" (no
``moe_routed_scaling_factor``). Attention runs one KV group at a time and the
experts one at a time, so that 2,048 + 8 positions fit beside the engine.

**Routes.** Ten of 256 experts a token: the 10th and 11th probabilities lie
close, the system's bfloat16 stream flips them in a few pairs of every
thousand, and a flipped expert (a weight of 0.25 on another expert's output)
moves single logits by a third of their spread (my chip runs, PR 34), which
says nothing about the rest of the system's arithmetic. So ``logits(follow=
...)`` is given the sets the system took (the programs' ``expert_choice``)
and takes the system's set wherever ITS OWN probabilities call it a tie
(``ROUTE_TIE_MARGIN``); a set further off is ``refused``: the reference keeps
its own there, and the check fails on the count.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import (  # noqa: F401 — shared, model-free pieces
    _f32, compare_logits, compare_tokens, rms_norm)
from benchmarks.reference_zaya import _mm as _mm_whole

EXPERT_LEAVES = ("wi_gate", "wi_up", "wo_mlp")
# The reference takes the system's set of k experts where every expert of it
# has, by the reference's OWN probabilities, at least (1 - this) of the
# reference's k-th probability: the set is then first within rounding.
# Between its two readings (my chip runs, PR 34, published widths, 5 layers,
# 1,207 tokens = 4,828 pairs a check): the system's sets differ from the
# reference's own in 850-930 pairs (the 10th and 11th of 256 probabilities lie
# close, and the router reads a stream that carries the layers' bfloat16
# roundings), with the largest gap 0.065-0.094 in eleven checks and one pair
# just over 0.10 in a twelfth (the five largest of a check lie within 0.03
# of each other: the tail is thin); a reference that routes from other inputs
# differs by more than the margin in hundreds of pairs (full attention in a
# window layer) to all of them (the gate dropped, the RoPE swapped): section
# 6, PR 34 has the counts.
ROUTE_TIE_MARGIN = 0.2
ROWS = 64  # precision="bfloat16": rows of a matmul computed together


def _mm(a, b, precision):
    """``reference_zaya._mm``, a block of ``ROWS`` rows at a time where the
    accumulator is bfloat16: that path keeps every partial sum of 8 products
    ([K / 8, rows, N] bfloat16: 11 GB for the 1,208 rows of the dense MLP at
    once)."""
    rows = math.prod(a.shape[:-1])
    if precision == "highest" or rows <= ROWS:
        return _mm_whole(a, b, precision)
    flat = a.reshape(rows, a.shape[-1])
    flat = jnp.pad(flat, ((0, -rows % ROWS), (0, 0)))
    out = jax.lax.map(lambda block: _mm_whole(block, b, precision),
                      flat.reshape(-1, ROWS, a.shape[-1]))
    return out.reshape(-1, b.shape[-1])[:rows].reshape(
        *a.shape[:-1], b.shape[-1])


def inverse_frequencies(rope: dict, head_dim: int):
    """(rotated dimensions, inverse frequencies [rot / 2], factor on cos and
    sin) of one entry of the configuration's ``rope_parameters``."""
    rot = int(head_dim * float(rope.get("partial_rotary_factor", 1)))
    base = float(rope["rope_theta"])
    inv = 1.0 / base ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    if rope["rope_type"] == "default":
        return rot, tuple(inv.tolist()), 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    factor, original = float(rope["factor"]), float(
        rope["original_max_position_embeddings"])

    def dimension_turning(times):  # the dimension that turns `times` times
        return rot * math.log(original / (times * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(dimension_turning(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(dimension_turning(float(rope["beta_slow"]))),
               rot - 1)
    ramp = np.clip((np.arange(rot // 2) - low) / max(high - low, 0.001), 0, 1)
    inv = inv / factor * ramp + inv * (1 - ramp)
    scale = float(rope.get("attention_factor") or 0.1 * math.log(factor) + 1)
    return rot, tuple(inv.tolist()), scale


def rotary(x, positions, table):
    """x [B, S, heads, D]: the first ``rot`` dimensions rotated (rotate-half:
    dimension i pairs with i + rot / 2), the rest pass."""
    rot, inv, scale = table
    angles = positions[:, :, None].astype(jnp.float32) * jnp.asarray(
        inv, jnp.float32)  # [B, S, rot / 2]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, :, None, :] * scale
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, :, None, :] * scale
    turned, rest = x[..., :rot], x[..., rot:]
    half = jnp.concatenate([-turned[..., rot // 2:], turned[..., : rot // 2]],
                           axis=-1)
    return jnp.concatenate([turned * cos + half * sin, rest], axis=-1)


def attention(y, layer, positions, *, kv_heads, window, table,
              precision="highest", drop=()):
    """Step 1 on the normed stream y [B, S, hidden] -> [B, S, hidden];
    ``window`` 0 is a full layer."""
    b, s, hidden = y.shape
    n, d = layer["wq"].shape[-2:]
    rep = n // kv_heads
    mm = functools.partial(_mm, precision=precision)
    q = mm(y, layer["wq"].reshape(hidden, -1)).reshape(b, s, n, d)
    k = mm(y, layer["wk"].reshape(hidden, -1)).reshape(b, s, kv_heads, d)
    v = mm(y, layer["wv"].reshape(hidden, -1)).reshape(b, s, kv_heads, d)
    q, k = rotary(q, positions, table), rotary(k, positions, table)
    back = positions[:, :, None] - positions[:, None, :]  # i - j
    seen = back >= 0
    if window and "window" not in drop:
        seen = seen & (back < window)

    def one_group(g):  # the `rep` query heads that read KV head g
        qg = jax.lax.dynamic_slice_in_dim(q, g * rep, rep, axis=2)
        kg = jax.lax.dynamic_index_in_dim(k, g, axis=2, keepdims=False)
        vg = jax.lax.dynamic_index_in_dim(v, g, axis=2, keepdims=False)
        scores = jnp.einsum("bqrd,bkd->brqk", qg, kg) / (d ** 0.5)
        probs = jax.nn.softmax(
            jnp.where(seen[:, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("brqk,bkd->bqrd", probs, vg)

    out = jax.lax.map(one_group, jnp.arange(kv_heads))  # [G, B, S, rep, D]
    out = jnp.moveaxis(out, 0, 2).reshape(b, s, n, d)
    if "gate" not in drop:
        out = out * jax.nn.sigmoid(mm(y, layer["wg"]))[..., None]
    return mm(out.reshape(b, s, -1), layer["wo"].reshape(-1, hidden))


def swiglu(y, gate, up, down, precision="highest"):
    mm = functools.partial(_mm, precision=precision)
    return mm(jax.nn.silu(mm(y, gate)) * mm(y, up), down)


def router_weights(y, router, *, top_k, norm_topk_prob, scale, follow=None,
                   precision="highest"):
    """y [T, hidden] -> (w [T, E] float32, zero outside each token's k
    experts; chosen [T, k]; gap [T]). ``follow`` [T, k] is the set the
    system took: it is taken here too where the reference's own
    probabilities call it a TIE, every expert of it within
    ``ROUTE_TIE_MARGIN`` (as a share of the reference's k-th probability)
    of that k-th probability; ``gap`` is how far below it the set's lowest
    lies (0 where the sets agree), or -1 where the set was refused and the
    reference keeps its own."""
    probs = jax.nn.softmax(_mm(y, router, precision), axis=-1)
    values, chosen = jax.lax.top_k(probs, top_k)
    gap = jnp.zeros(probs.shape[:1], jnp.float32)
    if follow is not None:
        theirs = jnp.take_along_axis(probs, follow, axis=-1)
        kth = values[:, -1]
        gap = jnp.maximum(kth - jnp.min(theirs, axis=-1), 0.0) / kth
        accept = (gap <= ROUTE_TIE_MARGIN)[:, None]
        chosen = jnp.where(accept, follow, chosen)
        values = jnp.where(accept, theirs, values)
        gap = jnp.where(accept[:, 0], gap, -1.0)
    if norm_topk_prob:
        values = values / jnp.sum(values, axis=-1, keepdims=True)
    values = values * scale
    one_hot = jax.nn.one_hot(chosen, probs.shape[-1], dtype=jnp.float32)
    return jnp.sum(one_hot * values[..., None], axis=1), chosen, gap


def routed_part(y, w, stacks, at, first, count, precision="highest"):
    """``sum over the experts first .. first + count - 1 of w[:, e] *
    SwiGLU_e(y)`` for y [T, hidden]: one share's part of the routed result.
    ``stacks`` are the three expert stacks with every leading axis joined
    ([groups, hidden, m] / [groups, m, hidden]) and ``at`` the index of the
    share's first expert in them. One expert upcast at a time."""
    def one_expert(total, c):
        gate, up, down = (jax.lax.dynamic_index_in_dim(
            stacks[name], at + c, keepdims=False).astype(jnp.float32)
            for name in EXPERT_LEAVES)
        weight = jax.lax.dynamic_index_in_dim(w, first + c, axis=1)
        return total + weight * swiglu(y, gate, up, down, precision), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(y), jnp.arange(count))
    return out


def sparse_mlp(y, small, stacks, at, *, count, top_k, norm_topk_prob, scale,
               first, follow=None, precision="highest", drop=()):
    """Step 3's sparse layer on y [T, hidden] -> ([T, hidden], chosen [T,
    k], gap [T])."""
    w, chosen, gap = router_weights(
        y, small["router"], top_k=top_k, norm_topk_prob=norm_topk_prob,
        scale=1.0 if "scale" in drop else scale, follow=follow,
        precision=precision)
    # the routed experts stay at the highest precision: a bfloat16
    # accumulator over 128 experts x 3 matmuls x 384 partial sums a layer is
    # 600,000 dependent steps; what it reads is then a LOWER bound on what
    # that precision does to the logits
    out = routed_part(y, w, stacks, at, first, count)
    if "shared" not in drop:
        out = out + swiglu(y, small["shared_gate"], small["shared_up"],
                           small["shared_down"], precision)
    return out, chosen, gap


@functools.partial(jax.jit, static_argnames=(
    "kv_heads", "window", "table", "eps", "precision", "drop"))
def attention_block(x, layer, positions, *, kv_heads, window, table, eps,
                    precision="highest", drop=()):
    with jax.default_matmul_precision("highest"):
        layer = _f32(layer)
        return x + attention(
            rms_norm(x, layer["ln_attn"], eps), layer, positions,
            kv_heads=kv_heads, window=window, table=table,
            precision=precision, drop=drop)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def dense_block(x, layer, *, eps, precision="highest"):
    with jax.default_matmul_precision("highest"):
        layer = _f32(layer)
        return x + swiglu(rms_norm(x, layer["ln_mlp"], eps), layer["wi_gate"],
                          layer["wi_up"], layer["wo_mlp"], precision)


@functools.partial(jax.jit, static_argnames=(
    "count", "top_k", "norm_topk_prob", "scale", "first", "eps", "precision",
    "drop"))
def sparse_block(x, small, experts, layer, follow, *, count, top_k,
                 norm_topk_prob, scale, first, eps, precision="highest",
                 drop=()):
    """``experts`` are the WHOLE stacks [Ls, count, ...] (never a layer's
    copy: 2.4 GB at the published widths) and ``layer`` the sparse layer."""
    with jax.default_matmul_precision("highest"):
        b, s, h = x.shape
        small = _f32(small)
        stacks = {n: a.reshape(-1, *a.shape[2:]) for n, a in experts.items()}
        y = rms_norm(x, small["ln_mlp"], eps).reshape(b * s, h)
        out, chosen, gap = sparse_mlp(
            y, small, stacks, layer * count, count=count, top_k=top_k,
            norm_topk_prob=norm_topk_prob, scale=scale, first=first,
            follow=follow, precision=precision, drop=drop)
        return x + out.reshape(b, s, h), chosen, gap


@functools.partial(jax.jit, static_argnames=("eps", "pieces", "precision"))
def head(x, ln_f, unembed, *, eps, pieces=1, precision="highest"):
    """Logits over the held rows, the vocabulary in ``pieces`` so that one
    float32 piece of the head is live."""
    with jax.default_matmul_precision("highest"):
        x = rms_norm(x, _f32(ln_f), eps)
        cols = unembed.reshape(unembed.shape[0], pieces, -1)
        out = jax.lax.map(
            lambda i: _mm(x, _f32(jax.lax.dynamic_index_in_dim(
                cols, i, axis=1, keepdims=False)), precision),
            jnp.arange(pieces))  # [pieces, B, S, V / pieces]
        return jnp.moveaxis(out, 0, -2).reshape(*x.shape[:-1], -1)


@functools.lru_cache(maxsize=None)
def _take():
    return jax.jit(lambda tree, i: jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), tree))


def logits(params, tokens, config: dict, last: int = 0, follow=None,
           precision: str = "highest", drop=()):
    """Full forward of ``tokens`` [1, S] -> (float32 logits [1, S, vocab] or
    of the last ``last`` positions, routes): ``routes`` has ``chosen``
    [sparse layers, S, k] (every layer's routing over ALL the published
    experts) and, with ``follow`` [sparse layers, S, k] (the sets the system
    took), how many (layer, token) pairs differed from the reference's own
    and were ``followed`` as ties, the largest gap among them, and how many
    were ``refused``."""
    tokens = jnp.asarray(tokens, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    x = jnp.asarray(params["embed"][tokens], jnp.float32)
    blocks, take = params["blocks"], _take()
    eps, d = float(config["rms_norm_eps"]), config["head_dim"]
    tables = {kind: inverse_frequencies(rope, d)
              for kind, rope in config["rope_parameters"].items()}
    if "rope" in drop:  # each kind by the other kind's rule
        tables = dict(zip(tables, reversed(list(tables.values()))))
    sparse = {n: a for n, a in blocks["sparse"].items()
              if n not in EXPERT_LEAVES}
    experts = {n: blocks["sparse"][n] for n in EXPERT_LEAVES}
    seen = {"full_attention": 0, "sliding_attention": 0, "sparse": 0}
    routing, gaps = [], []
    for l in range(config["num_hidden_layers"]):
        kind = config["layer_types"][l]
        stack = blocks["full" if kind == "full_attention" else "window"]
        layer = take(stack, seen[kind])
        heads = config["num_attention_heads_per_layer"][l]
        if layer["wq"].shape[-2] != heads:
            raise ValueError(f"layer {l} ({kind}) has {heads} query heads in "
                             f"the configuration, {layer['wq'].shape[-2]} in "
                             f"the parameters")
        seen[kind] += 1
        x = attention_block(
            x, layer, positions, kv_heads=config["num_key_value_heads"],
            window=config["sliding_window"] if kind == "sliding_attention"
            else 0, table=tables[kind], eps=eps, precision=precision,
            drop=tuple(drop))
        if config["mlp_layer_types"][l] == "dense":
            x = dense_block(x, blocks["dense"], eps=eps, precision=precision)
        else:
            told = None if follow is None else jnp.asarray(
                follow[seen["sparse"]], jnp.int32)
            x, chosen, gap = sparse_block(
                x, take(sparse, seen["sparse"]), experts, seen["sparse"], told,
                count=experts["wi_gate"].shape[1],
                top_k=config["num_experts_per_tok"],
                norm_topk_prob=bool(config["norm_topk_prob"]),
                scale=float(config["moe_routed_scaling_factor"]),
                first=int(config.get("experts_held_first", 0)), eps=eps,
                precision=precision, drop=tuple(drop))
            routing.append(chosen)
            gaps.append(gap)
            seen["sparse"] += 1
        x.block_until_ready()  # one float32 layer at a time (reference.py)
    if last:
        x = x[:, -last:]
    vocab = params["unembed"].shape[-1]
    out = head(x, params["ln_f"], params["unembed"], eps=eps,
               pieces=8 if vocab % 8 == 0 and vocab > 32768 else 1,
               precision=precision)
    return out, _routes(jnp.stack(routing), jnp.stack(gaps))


def _routes(chosen, gaps) -> dict:
    gaps = np.asarray(gaps)
    return {"chosen": np.asarray(chosen), "pairs": int(gaps.size),
            "followed": int((gaps > 0).sum()),
            "max_followed_gap": float(gaps.max(initial=0.0)),
            # the next largest too: how thin the tail is under the margin
            "largest_gaps": [round(float(g), 5) for g in
                             np.sort(gaps[gaps > 0])[::-1][:5]],
            "refused": int((gaps < 0).sum()), "margin": ROUTE_TIE_MARGIN}
