"""Plain reference of the ZAYA1-8B block (Zyphra/ZAYA1-8B, ``model_type``
zaya): ``jax.numpy``, float32, no cache, no state, no sort, no grouped
matmul, nothing from ``ray_tpu.models``.

Written from the two public descriptions (Compressed Convolutional Attention,
arXiv:2510.04476; the ZAYA1 technical report, arXiv:2511.17127) and the keys of
the model's ``config.json``. One whole sequence at a time; position t-1 is the
sequence shifted, never a stored state. With x the RMS-normed stream:

1. Latent projections: ``q~ = Wq x`` (heads x D), ``k~ = Wk x`` (kv_heads x D),
   ``v = [Wv1 x_t ; Wv2 x_{t-1}]``: the first half of the KV heads sees this
   token, the second half the one before (``x_{-1} = 0``).
2. ``c = [q~ ; k~]``. ``conv0``: causal, depthwise over time, ``cca_time0`` = 2
   taps, ``u_t = a0 * c_t + a1 * c_{t-1}``. ``conv1``: causal, ``cca_time1`` =
   2 taps, one group a head (mixes a head's D channels): ``w_t[g] = u_t[g]
   B0[g] + u_{t-1}[g] B1[g]``.
3. q-k mean over the ``rep`` query heads of a KV head: ``q[h] = w[h] + (q~[h] +
   k~[h // rep]) / 2``, ``k[j] = w[heads + j] + (mean_h q~[h] + k~[j]) / 2``.
4. ``q <- sqrt(D) q / |q|``, ``k <- sqrt(D) k / |k| * tau[j]``; rotary
   embedding (rotate-half layout) on the first ``partial_rotary_factor`` of
   each head's dimensions, the rest pass.
5. Causal softmax attention, scale 1/sqrt(D), ``Wo`` back to the stream.
6. Router on the expert sublayer's normed input y: ``r_l = Wd y + gamma_l *
   r_{l-1}`` (zero before the first layer), ``p = softmax(W3 gelu(W2 gelu(W1
   r_l)))`` with the exact (erf) GELU, expert ``argmax(p + b_l)``, output
   ``p[e] * SwiGLU_e(y)``.
7. Pre-norm residual around both sublayers, final RMSNorm, logits through the
   embedding's transpose.

Departures from the published model, none of them silent: the catalog's
``described_as`` also says "residual-scaled" and "MoD"; ``config.json`` has no
key for either and the reference (and the program) have neither. Everything
the config's keys do not fix (the taps' shapes, the q-k mean, the
normalisation's epsilon 1e-12, a scalar gamma, the exact GELU) is listed under
``assumed`` in ``configs/zaya1-8b-serve-d16.json`` with where it was taken
from.

**Routes.** One expert a token: where the system's rounding flips a near-tie
of ``p + b`` the token runs a whole other expert, and logits computed down the
reference's own route say nothing about the rest of the system's arithmetic.
So ``logits(follow=...)`` is given the route the system took ([layers,
tokens], the programs' ``expert_choice``) and takes the system's expert
wherever ITS OWN ``p + b`` puts that expert within ``ROUTE_TIE_MARGIN`` of its
own first: both are then first within rounding. A route further off is
``refused``: the reference keeps its own expert there, and the check fails on
the count. How many routes were followed, and the largest gap among them, are
reported.

It reads the program's parameter tree (``conv0 [L,2,G,D]``, ``conv1
[L,2,G,D,D]``, ``tau [L,kvH]``, ``router_down [L,h,R]``, ``router_w1/2
[L,R,R]``, ``router_w3 [L,R,E]``, ``router_gamma [L]``, ``router_bias
[L,E]``, the experts as OLMoE's), because the comparison needs the same
weights. Every matmul runs under ``default_matmul_precision("highest")``;
``precision="bfloat16"`` instead computes every matmul on bfloat16 operands
with a bfloat16 accumulator (8 products at a time summed exactly, the running
sum rounded after each addition): the nearest precision below the system's
bfloat16 products with float32 sums, which the check's limits must refuse.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference import (  # noqa: F401 — shared, model-free pieces
    _f32, _layer_at, compare_logits, compare_tokens, rms_norm, rotary)

# The reference takes the system's expert where its own p + b has it within
# this of its own first. Between its two readings (my chip runs, PR 30,
# published widths, 16 layers, 775 tokens = 12,400 pairs a run): the system's
# largest followed gap over seeds is 0.017-0.024 (about 130 pairs in 12,400
# differ at all, the fifth largest gap is 0.011-0.016: the tail is thin), and
# a reference that routes from other inputs (a dropped convolution, a dropped
# value shift, no q-k mean) differs in 5,000-7,700 pairs with gaps up to
# 0.87-0.98. The gaps are not the rounding of p alone: the stream the router
# reads carries sixteen layers of bfloat16 roundings (0.9% of the logits'
# standard deviation at the head) through an MLP with gain.
ROUTE_TIE_MARGIN = 0.06
CHUNK = 8  # precision="bfloat16": products summed exactly between roundings


def _mm(a, b, precision):
    """a [..., K] @ b [K, N] in float32, or on bfloat16 operands with a
    bfloat16 accumulator: CHUNK products summed exactly, the running sum
    rounded to bfloat16 after each such addition."""
    if precision == "highest":
        return a @ b
    bf = jnp.bfloat16
    k, chunks = a.shape[-1], max(a.shape[-1] // CHUNK, 1)
    a = a.astype(bf).reshape(*a.shape[:-1], chunks, k // chunks)
    b = b.astype(bf).reshape(chunks, k // chunks, b.shape[-1])
    parts = jnp.einsum("...ck,ckn->c...n", a, b, preferred_element_type=bf)
    total, _ = jax.lax.scan(lambda acc, part: ((acc + part).astype(bf), None),
                            jnp.zeros(parts.shape[1:], bf), parts)
    return total.astype(jnp.float32)


def _previous(seq):
    """seq [B, S, ...] at t-1, zero at the first position."""
    return jnp.pad(seq, [(0, 0), (1, 0)] + [(0, 0)] * (seq.ndim - 2))[:, :-1]


def attention(y, layer, positions, *, heads, kv_heads, theta, rotary_factor,
              precision="highest", drop=()):
    """Steps 1-5 on the normed stream y [B, S, hidden] -> [B, S, hidden].
    ``drop`` names parts to leave out (tests and the check's second reading:
    the comparison must then fail): "conv0", "conv1", "shift", "mean"."""
    b, s, hidden = y.shape
    d = layer["wq"].shape[-1]
    rep, half = heads // kv_heads, kv_heads // 2
    mm = functools.partial(_mm, precision=precision)
    q_lat = mm(y, layer["wq"].reshape(hidden, -1)).reshape(b, s, heads, d)
    k_lat = mm(y, layer["wk"].reshape(hidden, -1)).reshape(b, s, kv_heads, d)
    wv = layer["wv"]
    v_now = mm(y, wv[:, :half].reshape(hidden, -1)).reshape(b, s, half, d)
    y_before = y if "shift" in drop else _previous(y)
    v_before = mm(y_before, wv[:, half:].reshape(hidden, -1)).reshape(
        b, s, half, d)
    v = jnp.concatenate([v_now, v_before], axis=2)

    c = jnp.concatenate([q_lat, k_lat], axis=2)  # [B, S, G, D]
    a0, a1 = layer["conv0"][0], layer["conv0"][1]
    u = c if "conv0" in drop else a0 * c + a1 * _previous(c)
    if "conv1" in drop:
        w = u
    else:
        w = jnp.stack([  # one group a head: its own two D x D matrices
            mm(u[:, :, g], layer["conv1"][0, g])
            + mm(_previous(u)[:, :, g], layer["conv1"][1, g])
            for g in range(heads + kv_heads)], axis=2)
    if "mean" in drop:
        q, k = w[:, :, :heads], w[:, :, heads:]
    else:
        q = w[:, :, :heads] + (q_lat + jnp.repeat(k_lat, rep, axis=2)) / 2
        k = w[:, :, heads:] + (jnp.mean(q_lat.reshape(
            b, s, kv_heads, rep, d), axis=3) + k_lat) / 2

    def unit(x):
        return x * jnp.sqrt(d / (jnp.sum(x * x, -1, keepdims=True) + 1e-12))

    q, k = unit(q), unit(k) * layer["tau"][:, None]
    rot = int(d * rotary_factor)
    q = jnp.concatenate([rotary(q[..., :rot], positions, theta),
                         q[..., rot:]], axis=-1)
    k = jnp.concatenate([rotary(k[..., :rot], positions, theta),
                         k[..., rot:]], axis=-1)
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k) / (d ** 0.5)
    causal = positions[:, None, :, None] >= positions[:, None, None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    attn = jnp.einsum("bnqk,bknd->bqnd", probs, v)
    return mm(attn.reshape(b, s, -1), layer["wo"].reshape(-1, hidden))


def route(y, layer, r_before, follow=None, precision="highest"):
    """Step 6's router on y [T, hidden] -> (expert [T], weight [T], r [T, R],
    gap [T]): ``gap`` is how far below the reference's own first (in p + b)
    the expert that was taken lies: 0 where it is the reference's own, and
    where ``follow`` [T] named another within ROUTE_TIE_MARGIN; -1 where
    ``follow`` was refused (the reference's own is taken)."""
    mm = functools.partial(_mm, precision=precision)
    r = mm(y, layer["router_down"]) + layer["router_gamma"] * r_before
    hidden = jax.nn.gelu(mm(r, layer["router_w1"]), approximate=False)
    hidden = jax.nn.gelu(mm(hidden, layer["router_w2"]), approximate=False)
    p = jax.nn.softmax(mm(hidden, layer["router_w3"]), axis=-1)
    biased = p + layer["router_bias"]
    own = jnp.argmax(biased, axis=-1)
    if follow is None:
        expert, gap = own, jnp.zeros(own.shape, jnp.float32)
    else:
        behind = jnp.max(biased, -1) - jnp.take_along_axis(
            biased, follow[:, None], axis=-1)[:, 0]
        accept = behind <= ROUTE_TIE_MARGIN
        expert = jnp.where(accept, follow, own)
        gap = jnp.where(accept, behind, -1.0)
    weight = jnp.take_along_axis(p, expert[:, None], axis=-1)[:, 0]
    return expert, weight, r, gap


def experts(y, layer, expert, weight, precision="highest"):
    """``weight[t] * SwiGLU_expert[t](y_t)`` for y [T, hidden]: every expert
    on every token, kept where it is the token's, one expert at a time."""
    mm = functools.partial(_mm, precision=precision)

    def one_expert(total, e):
        gate, up, down = (jax.lax.dynamic_index_in_dim(
            layer[name], e, keepdims=False).astype(jnp.float32)
            for name in ("wi_gate", "wi_up", "wo_mlp"))
        out = mm(jax.nn.silu(mm(y, gate)) * mm(y, up), down)
        return total + jnp.where((expert == e)[:, None], out, 0.0), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(y),
                          jnp.arange(layer["wi_gate"].shape[0]))
    return out * weight[:, None]


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "theta", "eps", "rotary_factor", "precision",
    "drop"))
def block(x, layer, positions, r_before, follow, *, heads, kv_heads, theta,
          eps, rotary_factor, precision="highest", drop=()):
    """One decoder block on x [B, S, hidden] float32 -> (x, r, expert, gap);
    ``follow`` [B*S] int32 or None."""
    with jax.default_matmul_precision("highest"):
        b, s, h = x.shape
        small = {n: _f32(a) for n, a in layer.items()
                 if n not in ("wi_gate", "wi_up", "wo_mlp")}
        x = x + attention(
            rms_norm(x, small["ln_attn"], eps), small, positions, heads=heads,
            kv_heads=kv_heads, theta=theta, rotary_factor=rotary_factor,
            precision=precision, drop=drop)
        y = rms_norm(x, small["ln_mlp"], eps).reshape(b * s, h)
        expert, weight, r, gap = route(y, small, r_before, follow, precision)
        out = experts(y, layer, expert, weight, precision)
        return x + out.reshape(b, s, h), r, expert, gap


def static_of(config: dict) -> dict:
    """``block``'s static arguments from the published ``config.json``."""
    if config["cca_time0"] != 2 or config["cca_time1"] != 2:
        raise ValueError("the convolutions are written for two taps each")
    return dict(heads=config["num_attention_heads"],
                kv_heads=config["num_key_value_heads"],
                theta=float(config["rope_parameters"]["hybrid"]["rope_theta"]),
                eps=float(config["rms_norm_eps"]),
                rotary_factor=float(config["partial_rotary_factor"]))


@functools.partial(jax.jit, static_argnames=("eps", "blocks", "precision"))
def head(x, ln_f, embed, *, eps, blocks=1, precision="highest"):
    """Logits through the embedding's transpose, the vocabulary in
    ``blocks`` pieces so that one float32 piece of it is live."""
    with jax.default_matmul_precision("highest"):
        x = rms_norm(x, _f32(ln_f), eps)
        pieces = embed.reshape(blocks, -1, embed.shape[-1])
        out = jax.lax.map(lambda piece: _mm(x, _f32(piece).T, precision),
                          pieces)  # [blocks, B, S, V / blocks]
        return jnp.moveaxis(out, 0, -2).reshape(*x.shape[:-1], -1)


def logits(params, tokens, config: dict, last: int = 0, follow=None,
           precision: str = "highest", drop=()):
    """Full forward of ``tokens`` [1, S] -> (float32 logits [1, S, vocab] or
    of the last ``last`` positions, routes): ``routes`` has ``chosen`` [L, S]
    (the expert every token ran in every layer), and with ``follow`` [L, S]
    (the system's route) how many of its (layer, token) pairs differed from
    the reference's own and were ``followed``, the largest ``p + b`` gap among
    those, and how many were ``refused``. One layer's weights are brought out
    of the stack at a time."""
    tokens = jnp.asarray(tokens, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    x = jnp.asarray(params["embed"][tokens], jnp.float32)
    layer_at = _layer_at(getattr(params["embed"].sharding, "mesh", None))
    r = jnp.zeros((tokens.size, params["blocks"]["router_w1"].shape[-1]),
                  jnp.float32)
    chosen, gaps = [], []
    for i in range(config["num_hidden_layers"]):
        layer = layer_at(params["blocks"], i)
        told = None if follow is None else jnp.asarray(follow[i], jnp.int32)
        x, r, expert, gap = block(x, layer, positions, r, told,
                                  precision=precision, drop=tuple(drop),
                                  **static_of(config))
        x.block_until_ready()  # one float32 layer at a time (reference.py)
        chosen.append(expert)
        gaps.append(gap)
    if last:
        x = x[:, -last:]
    vocab = params["embed"].shape[0]
    out = head(x, params["ln_f"], params["embed"],
               eps=float(config["rms_norm_eps"]),
               blocks=8 if vocab % 8 == 0 and vocab > 65536 else 1,
               precision=precision)
    return out, _routes(jnp.stack(chosen), jnp.stack(gaps))


def _routes(chosen, gaps) -> dict:
    import numpy as np

    gaps = np.asarray(gaps)
    return {"chosen": np.asarray(chosen), "pairs": int(gaps.size),
            "followed": int((gaps > 0).sum()),
            "max_followed_gap": float(gaps.max(initial=0.0)),
            # the next largest too: how thin the tail is under the margin
            "largest_gaps": [round(float(g), 5) for g in
                             np.sort(gaps[gaps > 0])[::-1][:5]],
            "refused": int((gaps < 0).sum()), "margin": ROUTE_TIE_MARGIN}
