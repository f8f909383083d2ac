"""Plain reference of the Kimi-Linear block (moonshotai/Kimi-Linear-48B-A3B-
Instruct, ``model_type`` kimi_linear; the recurrence is arXiv:2510.26692):
``jax.numpy``, float32, one sequence at a time, the recurrence token by token,
attention by the expanded form only, no cache, no chunk, no sort, no grouped
matmul, nothing from ``ray_tpu.models``.

Written from the keys of the model's ``config.json`` (the layer equations of
ISSUE 38's Tentpole); what the keys do not fix is listed under ``assumed`` in
``configs/kimi-linear-48b-a3b-serve-ep16.json``, each item with its reason.
This sandbox has no network: where the published text differs from an item
there, the published text wins, and the difference is to be written down HERE
(none is known). Layers are numbered from 1 as ``linear_attn_config`` numbers
them: layer l is a KDA layer if l is in ``kda_layers``, an MLA layer if in
``full_attn_layers``; layers up to ``first_k_dense_replace`` have the dense
SwiGLU, the others the experts. With y the RMS-normed stream (eps
``rms_norm_eps``, no bias anywhere):

1. KDA layer, ``num_heads`` heads of ``head_dim`` D (keys and values alike):
   ``q~, k~, v~ = y Wq, y Wk, y Wv``; a causal depthwise convolution of
   ``short_conv_kernel_size`` taps, then SiLU, on each: ``c_t = silu(sum_j
   w_j z_{t-3+j})``; ``q = l2norm(c_q) / sqrt(D)``, ``k = l2norm(c_k)`` (x /
   sqrt(sum x^2 + 1e-6)), ``v = c_v``; decay by channel ``a_t = exp(-exp(
   A_log[h]) softplus((y Wfa) Wfb + dt_bias))``; ``beta_t = sigmoid(y Wb)``;
   the state S of a head [keys, values], zero at the start: ``S_t = (I -
   beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T
   q_t``; ``o <- RMSNorm(o; weight [D]) * sigmoid((y Wga) Wgb)``; ``x <- x +
   concat(o) Wo``. No positional encoding.
2. MLA layer (``mla_use_nope``: no rotation; the ``qk_rope_head_dim``
   dimensions are one key part all heads share): ``[q_n ; q_r]_i = y Wq_i``;
   ``[c~ ; k_r] = y Wkva``; ``c = RMSNorm(c~)``; ``[k_n ; v]_i = c Wkvb_i``;
   ``s_ij = (q_n_i . k_n_j + q_r_i . k_r_j) / sqrt(qk_nope_head_dim +
   qk_rope_head_dim)``, causal softmax, ``o_i = sum_j p_ij v_j``, ``x <- x +
   concat(o) Wo``.
3. Experts: ``s = sigmoid(y Wr)`` over all the published experts; chosen: the
   top ``num_experts_per_token`` of ``s + b`` (one group: no grouping);
   weights ``s[chosen]`` (without b) over their sum (``moe_renormalize``),
   times ``routed_scaling_factor``; ``x <- x + sum over chosen e HELD of w_e
   SwiGLU_e(y) + SwiGLU_shared(y)``.
4. Final RMSNorm, logits through ``unembed`` over the held rows.

**The share.** One chip's share of a layer that 16 chips hold
(``deployment``): the router has all its published outputs, the parameter
tree holds the experts ``experts_held_first ..`` of every sparse layer (as
many as its expert stacks have) and the first ``vocab_size`` rows of the
vocabulary. What the absent experts would add is left out, here as in the
program. ``routed_part(..., first, count)`` (``reference_laguna``'s) is one
share's part alone, so that a test can add the shares up to the uncut layer.

It reads the program's parameter tree (``blocks["kda"]``: ``wq``, ``wk``,
``wv`` [L, hidden, H * D], ``conv_q|k|v`` [L, taps, H, D], ``w_fa``, ``w_ga``
[L, hidden, D], ``w_fb``, ``w_gb`` [L, D, H * D], ``a_log`` [L, H], ``dt_bias``
[L, H, D], ``w_b`` [L, hidden, H], ``o_norm`` [L, D], ``wo`` [L, H, D,
hidden]; ``blocks["mla"]``: ``wq`` [L, hidden, H * nope + H * rope] (every head's nope
columns, then every head's rope columns), ``wkv_a`` [L,
hidden, latent + rope], ``kv_norm`` [L, latent], ``wkv_b`` [L, latent, H, nope
+ v], ``wo``; ``blocks["dense"]``; ``blocks["sparse"]`` as Laguna's plus
``router_bias`` [Ls, E]). Every matmul runs under ``default_matmul_precision(
"highest")``; ``precision="bfloat16"`` computes every matmul on bfloat16
operands with a bfloat16 accumulator (all but the routed experts' and the
recurrence's own products); ``state="bfloat16"`` keeps the matrix state in
bfloat16 between positions: both are what the check's limits must refuse.
``drop`` names a part to leave out, which they must refuse too: "decay" (a =
1), "beta" (beta = 1), "gate" (no output gate), "conv" (no convolution: c =
silu(z)), "bias" (no selection bias), "scale" (no ``routed_scaling_factor``),
"shared" (no shared expert), "rope" (no ``k_r`` part in the scores).

**Routes.** Eight of 256 experts a token: the 8th and 9th selection scores lie
close, and the system's bfloat16 stream flips them now and then. ``logits(
follow=...)`` is given the sets the system took and takes the system's set
wherever ITS OWN scores call it a tie (``ROUTE_TIE_MARGIN``, in units of the
selection score); a set further off is ``refused``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference import (  # noqa: F401 — shared, model-free pieces
    _f32, compare_logits, compare_tokens, rms_norm)
from benchmarks.reference_laguna import (  # noqa: F401
    EXPERT_LEAVES, _mm, _routes, _take, dense_block, head, routed_part,
    swiglu)

# The reference takes the system's set of k experts where every expert of it
# has, by the reference's OWN selection scores (sigmoid + bias, in (0, 1)),
# at least the reference's k-th score less this. Between its two readings (my
# chip runs, PR 38, published widths and depth, 1,207 tokens = 31,382 pairs a
# check): the system's sets differ from the reference's own in 8,270-8,650
# pairs (the 8th and 9th of 256 sigmoid scores lie 0.007 apart on average,
# and the router reads a stream that carries 27 layers' bfloat16 roundings),
# 2,000-2,100 of them by more than 0.005, 380-440 by more than 0.01, 6-10 by
# more than 0.02, the largest 0.0237-0.0261 in six checks and none over 0.03;
# a reference with a bfloat16 state differs by more than 0.02 in 134 pairs
# and by more than 0.03 in 7, one without the selection bias in 1,730 and
# 260, one without the shared key part in 3,030 and 1,547.
ROUTE_TIE_MARGIN = 0.025


def _l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def kda(y, layer, *, eps, precision="highest", state="float32", drop=()):
    """Step 1 on the normed stream y [S, hidden] -> [S, hidden], the
    recurrence a position at a time."""
    s, hidden = y.shape
    taps, nh, d = layer["conv_q"].shape
    mm = functools.partial(_mm, precision=precision)

    def conv(z, w):  # z [S, H, D], w [taps, H, D]
        if "conv" in drop:
            return jax.nn.silu(z)
        seen = jnp.concatenate([jnp.zeros((taps - 1, nh, d), z.dtype), z])
        return jax.nn.silu(sum(w[j] * seen[j:j + s] for j in range(taps)))

    c = [conv(mm(y, layer[w].reshape(hidden, -1)).reshape(s, nh, d),
              layer["conv_" + w[1]]) for w in ("wq", "wk", "wv")]
    q, k, v = _l2norm(c[0]) / d ** 0.5, _l2norm(c[1]), c[2]
    f = mm(mm(y, layer["w_fa"]), layer["w_fb"].reshape(d, -1)).reshape(
        s, nh, d)
    log_a = -jnp.exp(layer["a_log"])[:, None] * jax.nn.softplus(
        f + layer["dt_bias"])
    if "decay" in drop:
        log_a = jnp.zeros_like(log_a)
    beta = jax.nn.sigmoid(mm(y, layer["w_b"]))  # [S, H]
    if "beta" in drop:
        beta = jnp.ones_like(beta)
    kept = jnp.dtype(state)

    def position(mat, xs):  # mat [H, K, V]
        q, k, v, log_a, beta = xs
        mat = mat.astype(jnp.float32) * jnp.exp(log_a)[..., None]
        u = beta[:, None] * (v - jnp.einsum("hk,hkv->hv", k, mat))
        mat = (mat + k[..., None] * u[:, None, :]).astype(kept)
        return mat, jnp.einsum("hk,hkv->hv", q, mat.astype(jnp.float32))

    _, o = jax.lax.scan(position, jnp.zeros((nh, d, d), kept),
                        (q, k, v, log_a, beta))
    o = rms_norm(o, layer["o_norm"], eps)
    if "gate" not in drop:
        o = o * jax.nn.sigmoid(mm(mm(y, layer["w_ga"]), layer["w_gb"].reshape(
            d, -1))).reshape(s, nh, d)
    return mm(o.reshape(s, -1), layer["wo"].reshape(-1, hidden))


def mla(y, layer, *, eps, precision="highest", drop=()):
    """Step 2 on the normed stream y [S, hidden] -> [S, hidden], expanded,
    one head at a time."""
    s, hidden = y.shape
    lat = layer["kv_norm"].shape[0]
    nh = layer["wkv_b"].shape[1]
    d = layer["wkv_b"].shape[-1] // 2  # nope == v head size
    mm = functools.partial(_mm, precision=precision)
    q = mm(y, layer["wq"])  # every head's nope part, then every head's rope
    rope = q.shape[-1] // nh - d
    q = jnp.concatenate([q[:, :nh * d].reshape(s, nh, d),
                         q[:, nh * d:].reshape(s, nh, rope)], axis=-1)
    kv = mm(y, layer["wkv_a"])
    c, k_r = rms_norm(kv[:, :lat], layer["kv_norm"], eps), kv[:, lat:]
    scale = q.shape[-1] ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    shared = jnp.zeros((nh, s, s), jnp.float32) if "rope" in drop else \
        jnp.einsum("qhr,kr->hqk", q[..., d:], k_r)

    def one_head(i):
        w = jax.lax.dynamic_index_in_dim(layer["wkv_b"], i, axis=1,
                                         keepdims=False)  # [latent, 2 D]
        expanded = mm(c, w)
        qi = jax.lax.dynamic_index_in_dim(q, i, axis=1, keepdims=False)
        scores = (qi[:, :d] @ expanded[:, :d].T + jax.lax.dynamic_index_in_dim(
            shared, i, keepdims=False)) * scale
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return probs @ expanded[:, d:]

    o = jax.lax.map(one_head, jnp.arange(nh))  # [H, S, D]
    return mm(jnp.moveaxis(o, 0, 1).reshape(s, -1),
              layer["wo"].reshape(-1, hidden))


def router_weights(y, small, *, top_k, renormalize, scale, follow=None,
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
    if renormalize:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    weights = weights * (1.0 if "scale" in drop else scale)
    one_hot = jax.nn.one_hot(chosen, scores.shape[-1], dtype=jnp.float32)
    return jnp.sum(one_hot * weights[..., None], axis=1), chosen, gap


@functools.partial(jax.jit, static_argnames=(
    "kind", "eps", "precision", "state", "drop"))
def attention_block(x, layer, *, kind, eps, precision="highest",
                    state="float32", drop=()):
    with jax.default_matmul_precision("highest"):
        layer = _f32(layer)
        y = rms_norm(x[0], layer["ln_attn"], eps)
        out = kda(y, layer, eps=eps, precision=precision, state=state,
                  drop=drop) if kind == "kda" else mla(
            y, layer, eps=eps, precision=precision, drop=drop)
        return x + out[None]


@functools.partial(jax.jit, static_argnames=(
    "count", "top_k", "renormalize", "scale", "first", "eps", "precision",
    "drop"))
def sparse_block(x, small, experts, layer, follow, *, count, top_k,
                 renormalize, scale, first, eps, precision="highest",
                 drop=()):
    """``experts`` are the WHOLE stacks [Ls, count, ...] and ``layer`` the
    sparse layer (``reference_laguna.sparse_block``'s way)."""
    with jax.default_matmul_precision("highest"):
        b, s, h = x.shape
        small = _f32(small)
        stacks = {n: a.reshape(-1, *a.shape[2:]) for n, a in experts.items()}
        y = rms_norm(x, small["ln_mlp"], eps).reshape(b * s, h)
        w, chosen, gap = router_weights(
            y, small, top_k=top_k, renormalize=renormalize, scale=scale,
            follow=follow, precision=precision, drop=drop)
        # the routed experts stay at the highest precision, as Laguna's
        out = routed_part(y, w, stacks, layer * count, first, count)
        if "shared" not in drop:
            out = out + swiglu(y, small["shared_gate"], small["shared_up"],
                               small["shared_down"], precision)
        return x + out.reshape(b, s, h), chosen, gap


def kinds_of(config: dict) -> list:
    """Every layer's kind ("kda" or "mla"), layer 1 first, from the
    published lists."""
    lin = config["linear_attn_config"]
    out = []
    for l in range(1, config["num_hidden_layers"] + 1):
        if (l in lin["kda_layers"]) == (l in lin["full_attn_layers"]):
            raise ValueError(f"layer {l} is in both or neither of kda_layers "
                             "and full_attn_layers")
        out.append("kda" if l in lin["kda_layers"] else "mla")
    return out


def logits(params, tokens, config: dict, last: int = 0, follow=None,
           precision: str = "highest", state: str = "float32", drop=()):
    """Full forward of ``tokens`` [1, S] -> (float32 logits [1, S, vocab] or
    of the last ``last`` positions, routes), as ``reference_laguna.logits``:
    ``routes`` has ``chosen`` [sparse layers, S, k] and, with ``follow``, how
    many (layer, token) pairs were ``followed`` as ties and how many
    ``refused``."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = jnp.asarray(params["embed"][tokens], jnp.float32)
    blocks, take = params["blocks"], _take()
    eps = float(config["rms_norm_eps"])
    sparse = {n: a for n, a in blocks["sparse"].items()
              if n not in EXPERT_LEAVES}
    experts = {n: blocks["sparse"][n] for n in EXPERT_LEAVES}
    seen = {"kda": 0, "mla": 0, "sparse": 0}
    routing, gaps = [], []
    for l, kind in enumerate(kinds_of(config)):
        x = attention_block(x, take(blocks[kind], seen[kind]), kind=kind,
                            eps=eps, precision=precision, state=state,
                            drop=tuple(drop))
        seen[kind] += 1
        if l < config["first_k_dense_replace"]:
            x = dense_block(x, blocks["dense"], eps=eps, precision=precision)
        else:
            told = None if follow is None else jnp.asarray(
                follow[seen["sparse"]], jnp.int32)
            x, chosen, gap = sparse_block(
                x, take(sparse, seen["sparse"]), experts, seen["sparse"], told,
                count=experts["wi_gate"].shape[1],
                top_k=config["num_experts_per_token"],
                renormalize=bool(config["moe_renormalize"]),
                scale=float(config["routed_scaling_factor"]),
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
    routes = _routes(jnp.stack(routing), jnp.stack(gaps))
    routes["margin"] = ROUTE_TIE_MARGIN
    return out, routes
