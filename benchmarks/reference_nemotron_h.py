"""Plain reference of the Nemotron-H block (nvidia/NVIDIA-Nemotron-3-Super-
120B-A12B-BF16, ``model_type`` nemotron_h): ``jax.numpy``, float32, one
sequence at a time, the state-space recurrence a position at a time,
attention by the full score matrix of a KV group, no cache, no chunk, no
sort, no grouped matmul, no kernel, nothing from ``ray_tpu.models`` or
``ray_tpu.ops``.

Written from the keys of the model's ``config.json`` (the layer equations of
ISSUE 54's Tentpole); what the keys do not fix is listed under ``assumed`` in
``configs/nemotron-3-super-serve-ep8-d22.json``, each item with its reason.
This sandbox has no network: where the published text differs from an item
there, the published text wins, and the difference is to be written down HERE
(none is known). ``hybrid_override_pattern`` has one letter a layer; every
layer is ONE sublayer, ``x <- x + f(y)`` with y the RMS-normed stream (eps
``layer_norm_epsilon``, no bias but the convolution's):

1. ``M``, a Mamba-2 mixer (``mamba_num_heads`` H heads of ``mamba_head_dim``
   P, ``n_groups`` G groups, a state of ``ssm_state_size`` N): ``[z ; u ;
   dt~] = y W_in`` (H P, then H P + 2 G N, then H columns), u = ``[x~ ; B~ ;
   C~]``; a causal depthwise convolution of ``conv_kernel`` taps with a bias,
   then SiLU: ``c_t = silu(sum_j w_j u_{t-3+j} + b)`` = ``[x ; B ; C]``; ``dt
   = softplus(dt~ + dt_bias)``, ``a = exp(-exp(A_log) dt)`` a head; the state
   S of head h [P, N], zero at the start, g = h // (H / G): ``S_t = a_t
   S_{t-1} + dt_t x_t B_{g,t}^T``, ``o_t = S_t C_{g,t} + D_h x_t``; ``o <- o
   * silu(z)``, then an RMSNorm over each group's H P / G channels with a
   weight [H P]; ``x <- x + o W_out``. No positional encoding.
2. ``*``, attention: ``q = y Wq`` (``num_attention_heads`` of ``head_dim``),
   ``k, v = y Wk, y Wv`` (``num_key_value_heads``); causal softmax of ``q
   k^T / sqrt(head_dim)``; ``x <- x + concat(o) Wo``. No rotation.
3. ``E``, experts in a latent: ``s = sigmoid(y Wr)`` over all the published
   experts; chosen: the ``num_experts_per_tok`` largest of ``s + b`` (one
   group: no grouping); weights ``s[chosen]`` (without b) over (their sum +
   1e-20) (``norm_topk_prob``), times ``routed_scaling_factor``; ``l = y
   W_dn`` (``moe_latent_size``); expert e: ``relu(l W1_e)^2 W2_e``; ``x <- x
   + (sum over chosen e HELD of w_e expert_e(l)) W_up + relu(y Ws1)^2 Ws2``.
4. Final RMSNorm, logits through ``unembed`` over the held rows.

**The share.** One chip's share of a layer that 8 chips hold
(``deployment``): the router has all its published outputs, the parameter
tree holds the experts ``experts_held_first ..`` of every expert layer (as
many as its expert stacks have) and the first ``vocab_size`` rows of the
vocabulary. What the absent experts would add is left out, here as in the
program. ``routed_part(..., first, count)`` is one share's part alone (in the
latent, before ``W_up``), so that a test can add the shares up to the uncut
layer.

It reads the program's parameter tree (``blocks["ssm"]``: ``ln``, ``w_in``
[L, hidden, 2 H P + 2 G N + H], ``conv_w`` [L, taps, H P + 2 G N], ``conv_b``,
``dt_bias``, ``a_log``, ``d`` [L, H], ``norm`` [L, H P], ``w_out``;
``blocks["gqa"]``: ``ln``, ``wq`` [L, hidden, heads * D], ``wk``, ``wv`` [L,
hidden, kv heads * D], ``wo``; ``blocks["sparse"]``: ``ln_mlp``, ``router``,
``router_bias``, ``latent_down``, ``latent_up``, ``wi_up`` [L, held, latent,
m], ``wo_mlp`` [L, held, m, latent], ``shared_up``, ``shared_down``). Every
matmul runs under ``default_matmul_precision("highest")``;
``precision="bfloat16"`` computes every matmul on bfloat16 operands with a
bfloat16 accumulator (all but the routed experts' and the recurrence's own
products); ``state="bfloat16"`` keeps the state in bfloat16 between
positions: both are what the check's limits must refuse. ``drop`` names a
part to leave out, which they must refuse too: "relu" (ReLU for ReLU
squared, experts and shared expert), "d" (no ``D x``), "conv_bias", "scale"
(no ``routed_scaling_factor``), "decay" (a = 1), "gate" (no ``silu(z)``),
"bias" (no selection bias), "shared" (no shared expert).

**Routes.** 22 of 512 experts a token: the 22nd and 23rd selection scores lie
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
from benchmarks.reference_laguna import _mm, _routes, _take, head  # noqa: F401

EXPERT_LEAVES = ("wi_up", "wo_mlp")
KIND = {"M": "ssm", "*": "gqa", "E": "sparse"}
# The reference takes the system's set of k experts where every expert of it
# has, by the reference's OWN selection scores (sigmoid + bias, in (0, 1)),
# at least the reference's k-th score less this. Between its two readings (my
# chip runs, PR 54, published widths, 407 tokens = 4,070 pairs a check): the
# system's sets differ from the reference's own in 1,004-1,109 pairs (the
# 22nd and 23rd of 512 sigmoid scores lie close and the router reads a
# bfloat16 stream), the largest gap 0.0068-0.0092 in fourteen checks (a
# check's five largest lie within 0.003 of each other: the tail is thin); a
# reference with a bfloat16 accumulator differs by more than 0.02 in 2,482
# pairs, one without the selection bias in 801, one without a part (the
# factor 5, the square, D, the decay, the gate, the shared expert) in 3,582
# to all 4,070. (A bfloat16 STATE moves no set beyond 0.0092: the margin
# cannot refuse it, nor can the logits; the runner's comment says so.)
ROUTE_TIE_MARGIN = 0.02


def mamba(y, layer, *, heads, groups, eps, precision="highest",
          state="float32", drop=()):
    """Step 1 on the normed stream y [S, hidden] -> [S, hidden], the
    recurrence a position at a time."""
    s, _ = y.shape
    taps, chans = layer["conv_w"].shape
    inner = layer["norm"].shape[0]
    p, n = inner // heads, (chans - inner) // (2 * groups)
    mm = functools.partial(_mm, precision=precision)
    zxbcdt = mm(y, layer["w_in"])
    z, u, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:inner + chans],
                zxbcdt[:, inner + chans:])
    seen = jnp.concatenate([jnp.zeros((taps - 1, chans), u.dtype), u])
    c = sum(layer["conv_w"][j] * seen[j:j + s] for j in range(taps))
    if "conv_bias" not in drop:
        c = c + layer["conv_b"]
    c = jax.nn.silu(c)
    x = c[:, :inner].reshape(s, heads, p)
    b = c[:, inner:inner + groups * n].reshape(s, groups, n)
    cc = c[:, inner + groups * n:].reshape(s, groups, n)
    dt = jax.nn.softplus(dt + layer["dt_bias"])  # [S, H]
    a = jnp.exp(-jnp.exp(layer["a_log"]) * dt)
    if "decay" in drop:
        a = jnp.ones_like(a)
    kept = jnp.dtype(state)
    per_group = heads // groups

    def position(mat, xs):  # mat [H, P, N]
        x, b, cc, dt, a = xs
        b, cc = (jnp.repeat(v, per_group, axis=0) for v in (b, cc))  # [H, N]
        mat = mat.astype(jnp.float32) * a[:, None, None] \
            + (dt[:, None] * x)[:, :, None] * b[:, None, :]
        mat = mat.astype(kept)
        return mat, jnp.einsum("hpn,hn->hp", mat.astype(jnp.float32), cc)

    _, o = jax.lax.scan(position, jnp.zeros((heads, p, n), kept),
                        (x, b, cc, dt, a))
    if "d" not in drop:
        o = o + layer["d"][:, None] * x
    o = o.reshape(s, inner)
    if "gate" not in drop:
        o = o * jax.nn.silu(z)
    o = rms_norm(o.reshape(s, groups, -1), 1.0, eps).reshape(s, inner) \
        * layer["norm"]
    return mm(o, layer["w_out"])


def attention(y, layer, *, kv_heads, head_dim, precision="highest"):
    """Step 2 on the normed stream y [S, hidden] -> [S, hidden], one KV head's
    group of query heads at a time."""
    s, _ = y.shape
    mm = functools.partial(_mm, precision=precision)
    q = mm(y, layer["wq"]).reshape(s, kv_heads, -1, head_dim)
    k = mm(y, layer["wk"]).reshape(s, kv_heads, head_dim)
    v = mm(y, layer["wv"]).reshape(s, kv_heads, head_dim)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def one_group(g):
        qg = jax.lax.dynamic_index_in_dim(q, g, axis=1, keepdims=False)
        kg = jax.lax.dynamic_index_in_dim(k, g, axis=1, keepdims=False)
        vg = jax.lax.dynamic_index_in_dim(v, g, axis=1, keepdims=False)
        scores = jnp.einsum("qrd,kd->rqk", qg, kg) / head_dim ** 0.5
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("rqk,kd->qrd", probs, vg)

    o = jax.lax.map(one_group, jnp.arange(kv_heads))  # [G, S, rep, D]
    return mm(jnp.moveaxis(o, 0, 1).reshape(s, -1), layer["wo"])


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
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    weights = weights * (1.0 if "scale" in drop else scale)
    one_hot = jax.nn.one_hot(chosen, scores.shape[-1], dtype=jnp.float32)
    return jnp.sum(one_hot * weights[..., None], axis=1), chosen, gap


def relu2(x, up, down, precision="highest", drop=()):
    """``relu(x W1)^2 W2`` ("relu" in ``drop``: without the square)."""
    act = jax.nn.relu(_mm(x, up, precision))
    return _mm(act if "relu" in drop else act * act, down, precision)


def routed_part(latent, w, stacks, at, first, count, drop=()):
    """``sum over the experts first .. first + count - 1 of w[:, e] *
    expert_e(latent)`` for latent [T, moe_latent_size]: one share's part of
    the routed result, IN the latent. ``stacks`` are the two expert stacks
    with every leading axis joined and ``at`` the index of the share's first
    expert in them. One expert upcast at a time."""
    def one_expert(total, c):
        up, down = (jax.lax.dynamic_index_in_dim(
            stacks[name], at + c, keepdims=False).astype(jnp.float32)
            for name in EXPERT_LEAVES)
        weight = jax.lax.dynamic_index_in_dim(w, first + c, axis=1)
        return total + weight * relu2(latent, up, down, drop=drop), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(latent),
                          jnp.arange(count))
    return out


@functools.partial(jax.jit, static_argnames=(
    "kind", "heads", "groups", "kv_heads", "head_dim", "eps", "precision",
    "state", "drop"))
def mixer_block(x, layer, *, kind, heads, groups, kv_heads, head_dim, eps,
                precision="highest", state="float32", drop=()):
    """An ``M`` or a ``*`` layer on x [1, S, hidden]."""
    with jax.default_matmul_precision("highest"):
        layer = _f32(layer)
        y = rms_norm(x[0], layer["ln"], eps)
        out = mamba(y, layer, heads=heads, groups=groups, eps=eps,
                    precision=precision, state=state, drop=drop) \
            if kind == "ssm" else attention(
                y, layer, kv_heads=kv_heads, head_dim=head_dim,
                precision=precision)
        return x + out[None]


@functools.partial(jax.jit, static_argnames=(
    "count", "top_k", "renormalize", "scale", "first", "eps", "precision",
    "drop"))
def sparse_block(x, small, experts, layer, follow, *, count, top_k,
                 renormalize, scale, first, eps, precision="highest",
                 drop=()):
    """An ``E`` layer on x [1, S, hidden]. ``experts`` are the WHOLE stacks
    [Ls, count, ...] and ``layer`` the expert layer
    (``reference_laguna.sparse_block``'s way)."""
    with jax.default_matmul_precision("highest"):
        b, s, h = x.shape
        small = _f32(small)
        stacks = {n: a.reshape(-1, *a.shape[2:]) for n, a in experts.items()}
        y = rms_norm(x, small["ln_mlp"], eps).reshape(b * s, h)
        w, chosen, gap = router_weights(
            y, small, top_k=top_k, renormalize=renormalize, scale=scale,
            follow=follow, precision=precision, drop=drop)
        latent = _mm(y, small["latent_down"], precision)
        # the routed experts stay at the highest precision, as Laguna's
        out = _mm(routed_part(latent, w, stacks, layer * count, first, count,
                              drop), small["latent_up"], precision)
        if "shared" not in drop:
            out = out + relu2(y, small["shared_up"], small["shared_down"],
                              precision, drop)
        return x + out.reshape(b, s, h), chosen, gap


def logits(params, tokens, config: dict, last: int = 0, follow=None,
           precision: str = "highest", state: str = "float32", drop=()):
    """Full forward of ``tokens`` [1, S] -> (float32 logits [1, S, vocab] or
    of the last ``last`` positions, routes), as ``reference_laguna.logits``:
    ``routes`` has ``chosen`` [expert layers, S, k] and, with ``follow``, how
    many (layer, token) pairs were ``followed`` as ties and how many
    ``refused``."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = jnp.asarray(params["embed"][tokens], jnp.float32)
    blocks, take = params["blocks"], _take()
    eps = float(config["layer_norm_epsilon"])
    pattern = config["hybrid_override_pattern"]
    if len(pattern) != config["num_hidden_layers"] or set(pattern) - set(KIND):
        raise ValueError(
            f"hybrid_override_pattern {pattern!r} is not "
            f"{config['num_hidden_layers']} letters of M, * and E")
    sparse = {n: a for n, a in blocks.get("sparse", {}).items()
              if n not in EXPERT_LEAVES}
    experts = {n: blocks["sparse"][n] for n in EXPERT_LEAVES} if sparse else {}
    seen = dict.fromkeys(KIND.values(), 0)
    routing, gaps = [], []
    for letter in pattern:
        kind = KIND[letter]
        if kind == "sparse":
            told = None if follow is None else jnp.asarray(
                follow[seen[kind]], jnp.int32)
            x, chosen, gap = sparse_block(
                x, take(sparse, seen[kind]), experts, seen[kind], told,
                count=experts["wi_up"].shape[1],
                top_k=config["num_experts_per_tok"],
                renormalize=bool(config["norm_topk_prob"]),
                scale=float(config["routed_scaling_factor"]),
                first=int(config.get("experts_held_first", 0)), eps=eps,
                precision=precision, drop=tuple(drop))
            routing.append(chosen)
            gaps.append(gap)
        else:
            x = mixer_block(
                x, take(blocks[kind], seen[kind]), kind=kind,
                heads=config["mamba_num_heads"], groups=config["n_groups"],
                kv_heads=config["num_key_value_heads"],
                head_dim=config["head_dim"], eps=eps, precision=precision,
                state=state, drop=tuple(drop))
        seen[kind] += 1
        x.block_until_ready()  # one float32 layer at a time (reference.py)
    if last:
        x = x[:, -last:]
    vocab = params["unembed"].shape[-1]
    out = head(x, params["ln_f"], params["unembed"], eps=eps,
               pieces=8 if vocab % 8 == 0 and vocab > 32768 else 1,
               precision=precision)
    routes = _routes(jnp.stack(routing), jnp.stack(gaps)) if routing else {}
    routes["margin"] = ROUTE_TIE_MARGIN
    return out, routes
