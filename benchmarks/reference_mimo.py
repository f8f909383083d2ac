"""Plain reference of the MiMo-V2-Flash block (XiaomiMiMo/MiMo-V2-Flash,
``model_type`` mimo_v2_flash): ``jax.numpy``, float32, no cache, no ring, no
band, no batching, no sort, no grouped matmul, no kernel, nothing from
``ray_tpu.models``.

Written from the keys of the model's ``config.json`` (the layer equations of
ISSUE 58's Tentpole); what the keys do not fix is listed under ``assumed`` in
``configs/mimo-v2-flash-serve-ep16-d11.json``, each item with its reason. This
sandbox has no network: where the published text differs from an item there,
the published text wins, and the difference is to be written down HERE (none
is known). One whole sequence at a time, every layer following
``hybrid_layer_pattern[l]`` (0 full, 1 window) and ``moe_layer_freq[l]`` (0
dense, 1 sparse) of the configuration file, not the program's runs. With y the
RMS-normed stream (eps ``layernorm_epsilon``, no bias anywhere):

1. Attention of layer l, kind K by ``hybrid_layer_pattern[l]``: ``q = y Wq``
   [``num_attention_heads``, ``head_dim`` 192]; ``k = y Wk`` [kv_K, 192]; ``v =
   y Wv`` [kv_K, ``v_head_dim`` 128], kv_full = ``num_key_value_heads``,
   kv_window = ``swa_num_key_value_heads``; rotary embedding (below) on q and k;
   ``v <- attention_value_scale v``; ``s_ij = q_i . k_j / sqrt(192)`` for ``j
   <= i`` and, in a window layer, ``i - j < sliding_window`` (the token itself
   counts); query head r reads KV head ``r // (heads / kv_K)``. A full layer
   (``add_full_attention_sink_bias`` false): ``p = softmax_j(s)``. A window
   layer (``add_swa_attention_sink_bias`` true) with one learned scalar ``b_r``
   a query head: ``p_ij = exp(s_ij - m_i) / (exp(b_r - m_i) + sum_j' exp(s_ij'
   - m_i))``, ``m_i = max(b_r, max_j s_ij)``: the sink takes a share of the
   probability and adds nothing. ``o_i = sum_j p_ij v_j`` [heads, 128]; ``x <-
   x + concat(o) Wo``.
2. Rotary embedding, rotate-half layout, over the FIRST ``int(head_dim *
   partial_rotary_factor)`` = 64 dimensions of a head, theta ``rope_theta`` in
   a full layer and ``swa_rope_theta`` in a window layer, no scaling; the other
   128 dimensions pass.
3. ``moe_layer_freq[l]`` 0 (layer 0): ``x <- x + (silu(y Wg) * (y Wu)) Wd``,
   width ``intermediate_size``. 1: ``sc = sigmoid(y Wr)`` over all the
   published experts in float32; the ``num_experts_per_tok`` largest of ``sc +
   bias`` (``topk_method`` noaux_tc: a stored bias; ``n_group`` 1: no
   grouping); ``w`` = ``sc`` of the chosen, without the bias, ``w <- w /
   sum(w)`` (``norm_topk_prob``), times ``routed_scaling_factor`` (null: 1);
   ``x <- x + sum over j with e_j HELD of w_j SwiGLU_{e_j}(y)``, each
   ``moe_intermediate_size`` wide. No shared expert (``n_shared_experts``
   null).
4. Final RMSNorm, logits through ``unembed`` over the held rows.

**The share.** The configuration is one chip's share of a layer that sixteen
chips hold (``deployment``): the router has all its published outputs, the
parameter tree holds the experts ``experts_held_first ..`` of every sparse
layer (as many as its expert stacks have) and the first ``vocab_size`` rows of
the vocabulary. What the absent experts would add is left out, here as in the
program, and that partial result goes on to the next layer. ``routed_part(...,
first, count)`` is one share's part alone, so that a test can add the shares
up to the uncut layer.

It reads the program's parameter tree (``blocks["full" | "window"]``: ``wq
[L_kind, hidden, 64, 192]``, ``wk [L_kind, hidden, kv_K, 192]``, ``wv [L_kind,
hidden, kv_K, 128]``, ``wo [L_kind, 64, 128, hidden]``, ``ln_attn``, the window
kind's ``sink [L_window, 64]``; ``blocks["dense"]``; ``blocks["sparse"]``:
``router [Ls, hidden, E]``, ``router_bias [Ls, E]``, ``wi_gate``, ``wi_up [Ls,
held, hidden, m]``, ``wo_mlp [Ls, held, m, hidden]``, ``ln_mlp``), because the
comparison needs the same weights. Every matmul runs under
``default_matmul_precision("highest")``; ``precision="bfloat16"`` instead
computes every matmul on bfloat16 operands with a bfloat16 accumulator
(``reference_zaya._mm``; all but the routed experts', which stay at the
highest: ``reference_laguna`` says why): the nearest precision below the
system's bfloat16 products with float32 sums, which the check's limits must
refuse. ``drop`` names a part to leave out or swap, which they must refuse too:
"sink" (a window layer's softmax over its keys alone), "window" (full attention
in a window layer), "value_scale", "rope" (each kind rotated by the other
kind's theta), "bias" (no selection bias). Attention runs one
query head at a time and the experts one at a time, so that 4,800 + 8
positions fit beside the engine.

**Routes.** Eight of 256 experts a token: the 8th and 9th selection scores lie
close, and the system's bfloat16 stream flips them now and then.
``logits(follow=...)`` is given the sets the system took (the programs'
``expert_choice``) and takes the system's set wherever ITS OWN scores call it
a tie (``ROUTE_TIE_MARGIN``, in units of the selection score); a set further
off is ``refused``: the reference keeps its own there, and the check fails on
the count.
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
# has, by the reference's OWN selection scores (sigmoid + bias, in (0, 1)), at
# least the reference's k-th score less this: `reference_kimi_linear`'s rule
# and its value (the same router: sigmoid scores of 256 experts, the top 8 by
# score + a stored bias). At THIS configuration's widths (my chip runs, PR 58:
# 4,807 tokens x 10 sparse layers = 48,070 pairs a check) the system's sets
# differ from the reference's own in 3,153-3,391 pairs, the largest gap
# 0.0039-0.0064 in ten checks; a reference with a bfloat16 accumulator differs
# by more than the margin in 1,340 pairs, one without the sink in 358
# (`runners/serve_mimo.py` has the limits these feed).
ROUTE_TIE_MARGIN = 0.025
ROWS = 64  # precision="bfloat16": rows of a matmul computed together


def _mm(a, b, precision):
    """``reference_zaya._mm``, a block of ``ROWS`` rows at a time where the
    accumulator is bfloat16 (that path keeps every partial sum of 8
    products)."""
    rows = math.prod(a.shape[:-1])
    if precision == "highest" or rows <= ROWS:
        return _mm_whole(a, b, precision)
    flat = a.reshape(rows, a.shape[-1])
    flat = jnp.pad(flat, ((0, -rows % ROWS), (0, 0)))
    out = jax.lax.map(lambda block: _mm_whole(block, b, precision),
                      flat.reshape(-1, ROWS, a.shape[-1]))
    return out.reshape(-1, b.shape[-1])[:rows].reshape(
        *a.shape[:-1], b.shape[-1])


def rotary(x, positions, rot: int, theta: float):
    """x [S, heads, D]: the first ``rot`` dimensions rotated (rotate-half:
    dimension i pairs with i + rot / 2), the rest pass."""
    inv = 1.0 / theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    angles = positions[:, None].astype(jnp.float32) * jnp.asarray(
        inv, jnp.float32)  # [S, rot / 2]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, None, :]
    turned, rest = x[..., :rot], x[..., rot:]
    half = jnp.concatenate([-turned[..., rot // 2:], turned[..., : rot // 2]],
                           axis=-1)
    return jnp.concatenate([turned * cos + half * sin, rest], axis=-1)


def attention(y, layer, positions, *, kv_heads, window, rot, theta,
              value_scale, precision="highest", drop=()):
    """Step 1 on the normed stream y [S, hidden] -> [S, hidden]; ``window`` 0
    is a full layer; ``layer["sink"]`` [heads] where the layer has one."""
    s, hidden = y.shape
    n, d = layer["wq"].shape[-2:]
    dv = layer["wv"].shape[-1]
    rep = n // kv_heads
    mm = functools.partial(_mm, precision=precision)
    q = mm(y, layer["wq"].reshape(hidden, -1)).reshape(s, n, d)
    k = mm(y, layer["wk"].reshape(hidden, -1)).reshape(s, kv_heads, d)
    v = mm(y, layer["wv"].reshape(hidden, -1)).reshape(s, kv_heads, dv)
    q, k = rotary(q, positions, rot, theta), rotary(k, positions, rot, theta)
    if "value_scale" not in drop:
        v = value_scale * v
    back = positions[:, None] - positions[None, :]  # i - j
    seen = back >= 0
    if window and "window" not in drop:
        seen = seen & (back < window)
    sink = layer.get("sink")
    if sink is None or "sink" in drop:
        sink = jnp.full((n,), -jnp.inf, jnp.float32)

    def one_head(r):  # query head r reads KV head r // rep
        qr = jax.lax.dynamic_index_in_dim(q, r, axis=1, keepdims=False)
        kg = jax.lax.dynamic_index_in_dim(k, r // rep, axis=1, keepdims=False)
        vg = jax.lax.dynamic_index_in_dim(v, r // rep, axis=1, keepdims=False)
        scores = jnp.where(seen, (qr @ kg.T) / (d ** 0.5), -jnp.inf)
        b = sink[r]
        m = jnp.maximum(jnp.max(scores, axis=-1, keepdims=True), b)
        e = jnp.exp(scores - m)
        p = e / (jnp.exp(b - m) + jnp.sum(e, axis=-1, keepdims=True))
        return p @ vg  # [S, dv]

    out = jax.lax.map(one_head, jnp.arange(n))  # [heads, S, dv]
    return mm(jnp.moveaxis(out, 0, 1).reshape(s, -1),
              layer["wo"].reshape(-1, hidden))


def swiglu(y, gate, up, down, precision="highest"):
    mm = functools.partial(_mm, precision=precision)
    return mm(jax.nn.silu(mm(y, gate)) * mm(y, up), down)


def router_weights(y, small, *, top_k, norm_topk_prob, scale, follow=None,
                   precision="highest", drop=()):
    """y [T, hidden] -> (w [T, E] float32, zero outside each token's k
    experts; chosen [T, k]; gap [T]): step 3's router. ``follow`` [T, k] is
    the set the system took: it is taken here too where the reference's own
    selection scores call it a TIE, every expert of it within
    ``ROUTE_TIE_MARGIN`` of the reference's k-th score; ``gap`` is how far
    below it the set's lowest lies (0 where the sets agree), or -1 where the
    set was refused and the reference keeps its own."""
    scores = jax.nn.sigmoid(_mm(y, small["router"], precision))
    choose = scores if "bias" in drop else scores + small["router_bias"]
    values, chosen = jax.lax.top_k(choose, top_k)
    gap = jnp.zeros(scores.shape[:1], jnp.float32)
    if follow is not None:
        theirs = jnp.take_along_axis(choose, follow, axis=-1)
        gap = jnp.maximum(values[:, -1] - jnp.min(theirs, axis=-1), 0.0)
        accept = gap <= ROUTE_TIE_MARGIN
        chosen = jnp.where(accept[:, None], follow, chosen)
        gap = jnp.where(accept, gap, -1.0)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    weights = weights * scale
    one_hot = jax.nn.one_hot(chosen, scores.shape[-1], dtype=jnp.float32)
    return jnp.sum(one_hot * weights[..., None], axis=1), chosen, gap


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


@functools.partial(jax.jit, static_argnames=(
    "kv_heads", "window", "rot", "theta", "value_scale", "eps", "precision",
    "drop"))
def attention_block(x, layer, positions, *, kv_heads, window, rot, theta,
                    value_scale, eps, precision="highest", drop=()):
    with jax.default_matmul_precision("highest"):
        layer = _f32(layer)
        return x + attention(
            rms_norm(x, layer["ln_attn"], eps), layer, positions,
            kv_heads=kv_heads, window=window, rot=rot, theta=theta,
            value_scale=value_scale, precision=precision, drop=drop)


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
    copy) and ``layer`` the sparse layer. The routed experts stay at the
    highest precision whatever ``precision`` says (``reference_laguna``)."""
    with jax.default_matmul_precision("highest"):
        small = _f32(small)
        stacks = {n: a.reshape(-1, *a.shape[2:]) for n, a in experts.items()}
        y = rms_norm(x, small["ln_mlp"], eps)
        w, chosen, gap = router_weights(
            y, small, top_k=top_k, norm_topk_prob=norm_topk_prob, scale=scale,
            follow=follow, precision=precision, drop=drop)
        return x + routed_part(y, w, stacks, layer * count, first, count), \
            chosen, gap


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def head(x, ln_f, unembed, *, eps, precision="highest"):
    with jax.default_matmul_precision("highest"):
        return _mm(rms_norm(x, _f32(ln_f), eps), _f32(unembed), precision)


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
    tokens = jnp.asarray(tokens, jnp.int32)[0]
    positions = jnp.arange(tokens.shape[0])
    x = jnp.asarray(params["embed"][tokens], jnp.float32)
    blocks, take = params["blocks"], _take()
    eps = float(config["layernorm_epsilon"])
    rot = int(config["head_dim"] * float(config["partial_rotary_factor"]))
    thetas = {"full": float(config["rope_theta"]),
              "window": float(config["swa_rope_theta"])}
    if "rope" in drop:  # each kind by the other kind's theta
        thetas = dict(zip(thetas, reversed(list(thetas.values()))))
    kv = {"full": config["num_key_value_heads"],
          "window": config["swa_num_key_value_heads"]}
    has_sink = {"full": bool(config["add_full_attention_sink_bias"]),
                "window": bool(config["add_swa_attention_sink_bias"])}
    sparse = {n: a for n, a in blocks["sparse"].items()
              if n not in EXPERT_LEAVES}
    experts = {n: blocks["sparse"][n] for n in EXPERT_LEAVES}
    seen = {"full": 0, "window": 0, "sparse": 0}
    routing, gaps = [], []
    for l in range(config["num_hidden_layers"]):
        kind = "window" if config["hybrid_layer_pattern"][l] else "full"
        layer = take(blocks[kind], seen[kind])
        if ("sink" in layer) != has_sink[kind]:
            raise ValueError(f"layer {l} ({kind}): the configuration "
                             f"{'has' if has_sink[kind] else 'has no'} sink "
                             "there, the parameters differ")
        seen[kind] += 1
        x = attention_block(
            x, layer, positions, kv_heads=kv[kind],
            window=config["sliding_window"] if kind == "window" else 0,
            rot=rot, theta=thetas[kind],
            value_scale=float(config["attention_value_scale"]), eps=eps,
            precision=precision, drop=tuple(drop))
        if not config["moe_layer_freq"][l]:
            x = dense_block(x, blocks["dense"], eps=eps, precision=precision)
        else:
            told = None if follow is None else jnp.asarray(
                follow[seen["sparse"]], jnp.int32)
            x, chosen, gap = sparse_block(
                x, take(sparse, seen["sparse"]), experts, seen["sparse"], told,
                count=experts["wi_gate"].shape[1],
                top_k=config["num_experts_per_tok"],
                norm_topk_prob=bool(config["norm_topk_prob"]),
                scale=float(config["routed_scaling_factor"] or 1.0),
                first=int(config.get("experts_held_first", 0)), eps=eps,
                precision=precision, drop=tuple(drop))
            routing.append(chosen)
            gaps.append(gap)
            seen["sparse"] += 1
        x.block_until_ready()  # one float32 layer at a time (reference.py)
    if last:
        x = x[-last:]
    out = head(x, params["ln_f"], params["unembed"], eps=eps,
               precision=precision)
    return out[None], _routes(jnp.stack(routing), jnp.stack(gaps))


def _routes(chosen, gaps) -> dict:
    gaps = np.asarray(gaps)
    return {"chosen": np.asarray(chosen), "pairs": int(gaps.size),
            "followed": int((gaps > 0).sum()),
            "max_followed_gap": float(gaps.max(initial=0.0)),
            # the next largest too: how thin the tail is under the margin
            "largest_gaps": [round(float(g), 5) for g in
                             np.sort(gaps[gaps > 0])[::-1][:5]],
            "refused": int((gaps < 0).sum()), "margin": ROUTE_TIE_MARGIN}
