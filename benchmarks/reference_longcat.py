"""Plain reference of the LongCat-Flash block (meituan-longcat/LongCat-Flash-
Chat, ``model_type`` longcat_flash): ``jax.numpy``, float32, one sequence at a
time, attention by the expanded form only, one head at a time, no cache, no
blocks of queries, no sort, no grouped matmul, nothing from
``ray_tpu.models``.

Written from the keys of the model's ``config.json`` (the layer equations of
ISSUE 44's Tentpole); what the keys do not fix is listed under ``assumed`` in
``configs/longcat-flash-serve-ep32-d4.json``, each item with its reason. This
sandbox has no network: where the published modeling code differs from an
item there, the published code wins, and the difference is to be written down
HERE (none is known; two things are remembered and not confirmed: that the
two ``mla_scale_*`` factors are ``sqrt(hidden_size / rank)``, and that the
router's weights are the scores without the bias, not renormalised). With
``N`` an RMSNorm (eps ``rms_norm_eps``; no bias anywhere), h ``hidden_size``:

1. Latent attention ``A(x)``, ``num_attention_heads`` heads: ``cq =
   N_q(x Wqa)``; ``q = (cq Wqb) * sqrt(h / q_lora_rank)``
   (``mla_scale_q_lora``), a head's ``q_n`` [``qk_nope_head_dim``] and ``q_r``
   [``qk_rope_head_dim``]; ``[ckv ; k_r] = x Wkva``; ``c = N_kv(ckv) * sqrt(h
   / kv_lora_rank)`` (``mla_scale_kv_lora``: on the latent alone, not on
   ``k_r``); ``[k_n ; v]_i = c Wkvb_i``; ``q_r`` of every head and the ONE
   shared ``k_r`` are rotated by position, pairs INTERLEAVED (dimensions 2j
   and 2j + 1 turn by ``position * rope_theta ** (-2j / qk_rope_head_dim)``);
   ``s_ij = (q_n_i . k_n_j + q_r_i . k_r_j) / sqrt(qk_nope_head_dim +
   qk_rope_head_dim)``, causal softmax, ``o_i = sum_j p_ij v_j``, ``A(x) =
   concat(o) Wo``.
2. Router ``R(y)``: ``s = softmax(y Wr)`` over ``n_routed_experts`` (as
   published) + ``zero_expert_num`` outputs; chosen: the top ``moe_topk`` of
   ``s + b`` (``e_score_correction_bias``, stored); weights ``w =
   routed_scaling_factor * s[chosen]``, not renormalised. Expert layer ``M(y)
   = sum over chosen e < n_routed_experts, HELD, of w_e SwiGLU_e(y) + (sum
   over chosen e >= n_routed_experts of w_e) * y``: a zero-compute
   (``zero_expert_type`` identity) expert returns its input.
3. Double layer: ``a = x + A_0(N_0(x))``; ``y = N_1(a)``; ``m = M(y)``; ``b =
   a + D_0(y)`` (dense SwiGLU of ``ffn_hidden_size``); ``c = b +
   A_1(N_2(b))``; ``out = c + D_1(N_3(c)) + m``.
4. Final RMSNorm, logits through ``unembed`` over the held rows.

**The share.** One chip's share of a layer that 32 chips hold
(``deployment``): the router has all its published outputs, the parameter tree
holds the experts ``experts_held_first ..`` of every double layer (as many as
its expert stacks have) and the first ``vocab_size`` rows of the vocabulary.
What the absent routed experts would add is left out, here as in the program;
the zero-compute part is computed whole (a token's home chip does it).
``expert_layer(..., first, count, zero=...)`` is one share's part alone, so
that a test can add the shares up to the uncut layer.

It reads the program's parameter tree (``blocks["mla"]``, every leaf [double
layers, 2, ...]: ``ln_attn``, ``wq_a`` [hidden, rank], ``q_norm``, ``wq``
[rank, H * nope + H * rope] (every head's nope columns, then every head's
rope columns), ``wkv_a`` [hidden, latent + rope], ``kv_norm``, ``wkv_b``
[latent, H, nope + v], ``wo`` [H, v, hidden]; ``blocks["dense"]`` [double
layers, 2, ...]: ``ln_mlp``, ``wi_gate``, ``wi_up``, ``wo_mlp``;
``blocks["sparse"]`` [double layers, ...]: ``router`` [hidden, outputs],
``router_bias``, the three expert stacks [held, ...]). Every matmul runs under
``default_matmul_precision("highest")``; ``precision="bfloat16"`` computes
every projection on bfloat16 operands with a bfloat16 accumulator, the
router's among them (the routed experts' and the attention's own products
stay at the highest, as in ``reference_kimi_linear``): what the check's limits
must refuse. ``drop`` names a
part to leave out or misplace, which they must refuse too: "rotate" (no
rotation), "scale_q", "scale_kv" (a factor left at 1), "zero" (no
zero-compute part), "bias" (no selection bias), "scale" (no
``routed_scaling_factor``), "shortcut" (``m`` joins the stream before the
second attention, as a layer half would).

**Routes.** Twelve of 768 outputs a token: the 12th and 13th selection scores
lie close, and the system's bfloat16 stream flips them now and then. Most
flips change nothing here (both outputs absent) or little (both zero-compute:
two near-equal weights); one that moves a held expert in or out moves the
logits. ``logits(follow=...)`` is given the sets the system took and takes the
system's set wherever ITS OWN selection scores call it a tie
(``ROUTE_TIE_MARGIN``, as a share of the reference's k-th selection score); a
set further off is ``refused``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference import (  # noqa: F401 — shared, model-free pieces
    _f32, compare_logits, compare_tokens, rms_norm)
from benchmarks.reference_laguna import (  # noqa: F401
    EXPERT_LEAVES, _mm, _routes, head, routed_part, swiglu)

# The reference takes the system's set of k outputs where every output of it
# has, by the reference's OWN selection scores (softmax + bias), at least (1 -
# this) of the reference's k-th score. Between its two readings (my chip
# runs, PR 44, published widths, 2,407 tokens = 9,628 pairs a check): the
# system's sets differ from the reference's own in 1,422-1,621 pairs (the
# 12th and 13th of 768 scores lie 1% apart and the router reads a bfloat16
# stream), the largest gap 0.043-0.059 of the k-th score in seventeen checks
# (a check's five largest lie within 0.006 of each other: the tail is thin);
# a reference with a bfloat16 accumulator differs by more than 0.2 in 1,328
# pairs, one without a part (the rotation, a factor, the zero-compute part,
# the factor 6, the shortcut's place) in 5,200-9,600; one without the
# selection bias stays under 0.087 in all of its 3,189 (a tenth of a score's
# mean moves a near-tie, no further): the runner's limit on how MANY sets
# are followed refuses that one.
ROUTE_TIE_MARGIN = 0.12
DENSE_PIECES = 4  # a dense MLP's float32 weights, a piece of its width a time


def rotate(x, positions, theta: float):
    """x [S, ..., R] rotated by ``positions`` [S], pairs interleaved."""
    r = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = positions.astype(jnp.float32)[:, None] * inv  # [S, R / 2]
    ang = ang.reshape(ang.shape[0], *(1,) * (x.ndim - 2), r // 2)
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                        even * jnp.sin(ang) + odd * jnp.cos(ang)], axis=-1)
    return turned.reshape(x.shape)


def mla(y, layer, *, nope, rope, scale_q, scale_kv, theta, eps,
        precision="highest", drop=()):
    """Step 1 on the normed stream y [S, hidden] -> [S, hidden], expanded,
    one head at a time."""
    s, hidden = y.shape
    lat, nh = layer["kv_norm"].shape[0], layer["wkv_b"].shape[1]
    mm = functools.partial(_mm, precision=precision)
    q = mm(rms_norm(mm(y, layer["wq_a"]), layer["q_norm"], eps), layer["wq"])
    q = q * (1.0 if "scale_q" in drop else scale_q)
    q_n = q[:, :nh * nope].reshape(s, nh, nope)
    q_r = q[:, nh * nope:].reshape(s, nh, rope)
    kv = mm(y, layer["wkv_a"])
    c = rms_norm(kv[:, :lat], layer["kv_norm"], eps) \
        * (1.0 if "scale_kv" in drop else scale_kv)
    k_r = kv[:, lat:]
    if "rotate" not in drop:
        at = jnp.arange(s)
        q_r, k_r = rotate(q_r, at, theta), rotate(k_r, at, theta)
    scale = (nope + rope) ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def one_head(i):
        w = jax.lax.dynamic_index_in_dim(layer["wkv_b"], i, axis=1,
                                         keepdims=False)  # [latent, nope + v]
        expanded = mm(c, w)
        qn = jax.lax.dynamic_index_in_dim(q_n, i, axis=1, keepdims=False)
        qr = jax.lax.dynamic_index_in_dim(q_r, i, axis=1, keepdims=False)
        scores = (qn @ expanded[:, :nope].T + qr @ k_r.T) * scale
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return probs @ expanded[:, nope:]

    o = jax.lax.map(one_head, jnp.arange(nh))  # [H, S, v]
    return mm(jnp.moveaxis(o, 0, 1).reshape(s, -1),
              layer["wo"].reshape(-1, hidden))


def router_weights(y, small, *, top_k, scale, follow=None,
                   precision="highest", drop=()):
    """y [T, hidden] -> (w [T, outputs] float32, zero outside each token's k
    outputs; chosen [T, k]; gap [T]): step 2's router. ``follow`` [T, k] is
    the set the system took: it is taken here too where the reference's own
    selection scores call it a TIE, every output of it within
    ``ROUTE_TIE_MARGIN`` (as a share of the reference's k-th selection score)
    of that k-th score; ``gap`` is how far below it the set's lowest lies (0
    where the sets agree), or -1 where the set was refused and the reference
    keeps its own."""
    scores = jax.nn.softmax(_mm(y, small["router"], precision), axis=-1)
    choose = scores if "bias" in drop else scores + small["router_bias"]
    values, chosen = jax.lax.top_k(choose, top_k)
    gap = jnp.zeros(scores.shape[:1], jnp.float32)
    if follow is not None:
        theirs = jnp.take_along_axis(choose, follow, axis=-1)
        kth = values[:, -1]
        gap = jnp.maximum(kth - jnp.min(theirs, axis=-1), 0.0) / kth
        accept = gap <= ROUTE_TIE_MARGIN
        chosen = jnp.where(accept[:, None], follow, chosen)
        gap = jnp.where(accept, gap, -1.0)
    weights = jnp.take_along_axis(scores, chosen, axis=-1) \
        * (1.0 if "scale" in drop else scale)
    one_hot = jax.nn.one_hot(chosen, scores.shape[-1], dtype=jnp.float32)
    return jnp.sum(one_hot * weights[..., None], axis=1), chosen, gap


def expert_layer(y, w, stacks, at, first, count, routed, zero=True):
    """Step 2's ``M(y)`` for y [T, hidden] under the weights w [T, outputs]:
    the part of the experts ``first .. first + count - 1`` (``stacks`` and
    ``at`` as ``reference_laguna.routed_part``'s) and, with ``zero``, of the
    zero-compute outputs ``routed ..``, which return y."""
    out = routed_part(y, w, stacks, at, first, count)
    if zero:
        out = out + jnp.sum(w[:, routed:], axis=-1, keepdims=True) * y
    return out


@functools.partial(jax.jit, static_argnames=(
    "nope", "rope", "scale_q", "scale_kv", "theta", "eps", "precision",
    "drop"))
def attention_block(x, layer, *, nope, rope, scale_q, scale_kv, theta, eps,
                    precision="highest", drop=()):
    with jax.default_matmul_precision("highest"):
        layer = _f32(layer)
        return x + mla(rms_norm(x[0], layer["ln_attn"], eps), layer,
                       nope=nope, rope=rope, scale_q=scale_q,
                       scale_kv=scale_kv, theta=theta, eps=eps,
                       precision=precision, drop=drop)[None]


@functools.partial(jax.jit, static_argnames=("eps",))
def normed(x, weight, *, eps):
    return rms_norm(x, _f32(weight), eps)


@functools.partial(jax.jit, static_argnames=("pieces", "precision"))
def dense(y, layer, *, pieces=1, precision="highest"):
    """A dense SwiGLU on y [1, S, hidden], a piece of its width at a time
    (the sum over the pieces is the whole product)."""
    with jax.default_matmul_precision("highest"):
        h, m = layer["wi_gate"].shape
        gate = layer["wi_gate"].reshape(h, pieces, m // pieces)
        up = layer["wi_up"].reshape(h, pieces, m // pieces)
        down = layer["wo_mlp"].reshape(pieces, m // pieces, h)

        def piece(total, i):
            g, u = (_f32(jax.lax.dynamic_index_in_dim(
                a, i, axis=1, keepdims=False)) for a in (gate, up))
            d = _f32(jax.lax.dynamic_index_in_dim(down, i, keepdims=False))
            return total + swiglu(y[0], g, u, d, precision), None

        out, _ = jax.lax.scan(piece, jnp.zeros_like(y[0]), jnp.arange(pieces))
        return out[None]


@functools.partial(jax.jit, static_argnames=(
    "count", "top_k", "scale", "first", "routed", "precision", "drop"))
def expert_block(y, small, experts, layer, follow, *, count, top_k, scale,
                 first, routed, precision="highest", drop=()):
    """``M(y)`` of double layer ``layer`` on the normed y [1, S, hidden];
    ``experts`` are the WHOLE stacks [layers, count, ...]
    (``reference_laguna.sparse_block``'s way)."""
    with jax.default_matmul_precision("highest"):
        small = _f32(small)
        stacks = {n: a.reshape(-1, *a.shape[2:]) for n, a in experts.items()}
        w, chosen, gap = router_weights(
            y[0], small, top_k=top_k, scale=scale, follow=follow,
            precision=precision, drop=drop)
        # the routed experts stay at the highest precision, as Laguna's
        out = expert_layer(y[0], w, stacks, layer * count, first, count,
                           routed, zero="zero" not in drop)
        return out[None], chosen, gap


def factors(config: dict):
    """(on the query, on the latent): ``sqrt(hidden_size / rank)`` where the
    configuration's flag is set, else 1."""
    h = config["hidden_size"]
    return (math.sqrt(h / config["q_lora_rank"])
            if config["mla_scale_q_lora"] else 1.0,
            math.sqrt(h / config["kv_lora_rank"])
            if config["mla_scale_kv_lora"] else 1.0)


def double_layer(x, mla_pair, mlp_pair, small, experts, i, follow,
                 config: dict, count=None, precision="highest", drop=()):
    """Step 3 on x [1, S, hidden]: double layer ``i`` with its two attention
    sublayers' and dense MLPs' parameters ([2, ...] each), its router's
    (``small``) and the WHOLE expert stacks, of which ``count`` experts a
    layer are this share's (all the stacks hold, unless given). Returns (x,
    chosen [S, k], gap [S])."""
    eps = float(config["rms_norm_eps"])
    scale_q, scale_kv = factors(config)
    width = config["ffn_hidden_size"]
    pieces = DENSE_PIECES if width % DENSE_PIECES == 0 and width > 4096 else 1

    def sub(tree, j):
        return jax.tree.map(lambda a: a[j], tree)

    def attend(x, j):
        return attention_block(
            x, sub(mla_pair, j), nope=config["qk_nope_head_dim"],
            rope=config["qk_rope_head_dim"], scale_q=scale_q,
            scale_kv=scale_kv, theta=float(config["rope_theta"]), eps=eps,
            precision=precision, drop=drop)

    a = attend(x, 0)
    y = normed(a, mlp_pair["ln_mlp"][0], eps=eps)
    m, chosen, gap = expert_block(
        y, small, experts, i, follow,
        count=experts["wi_gate"].shape[1] if count is None else count,
        top_k=config["moe_topk"],
        scale=float(config["routed_scaling_factor"]),
        first=int(config.get("experts_held_first", 0)),
        routed=config["published"]["n_routed_experts"],
        precision=precision, drop=drop)
    b = a + dense(y, sub(mlp_pair, 0), pieces=pieces, precision=precision)
    if "shortcut" in drop:  # the expert layer as a layer half
        b, m = b + m, 0.0
    c = attend(b, 1)
    out = c + dense(normed(c, mlp_pair["ln_mlp"][1], eps=eps),
                    sub(mlp_pair, 1), pieces=pieces, precision=precision) + m
    return out, chosen, gap


def logits(params, tokens, config: dict, last: int = 0, follow=None,
           precision: str = "highest", drop=()):
    """Full forward of ``tokens`` [1, S] -> (float32 logits [1, S, vocab] or
    of the last ``last`` positions, routes), as ``reference_laguna.logits``:
    ``routes`` has ``chosen`` [double layers, S, k] and, with ``follow``, how
    many (layer, token) pairs were ``followed`` as ties and how many
    ``refused``."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = jnp.asarray(params["embed"][tokens], jnp.float32)
    blocks = params["blocks"]
    small = {n: a for n, a in blocks["sparse"].items()
             if n not in EXPERT_LEAVES}
    experts = {n: blocks["sparse"][n] for n in EXPERT_LEAVES}
    routing, gaps = [], []
    for i in range(config["num_layers"]):
        at = functools.partial(jax.tree.map, lambda a: a[i])
        x, chosen, gap = double_layer(
            x, at(blocks["mla"]), at(blocks["dense"]), at(small), experts, i,
            None if follow is None else jnp.asarray(follow[i], jnp.int32),
            config, precision=precision, drop=tuple(drop))
        routing.append(chosen)
        gaps.append(gap)
        x.block_until_ready()  # one float32 layer at a time (reference.py)
    if last:
        x = x[:, -last:]
    vocab = params["unembed"].shape[-1]
    out = head(x, params["ln_f"], params["unembed"],
               eps=float(config["rms_norm_eps"]),
               pieces=8 if vocab % 8 == 0 and vocab > 32768 else 1,
               precision=precision)
    routes = _routes(jnp.stack(routing), jnp.stack(gaps))
    routes["margin"] = ROUTE_TIE_MARGIN
    return out, routes
