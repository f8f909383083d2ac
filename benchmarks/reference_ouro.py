"""Plain reference of the looped block (ByteDance/Ouro-2.6B, ``model_type``
ouro; "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741): ``jax.numpy``, float32, a Python loop over the passes and
over the layers, attention by the full score matrix, no cache, no scan, no
kernel, nothing from ``ray_tpu.models`` or ``ray_tpu.ops``.

Written from the keys of the model's ``config.json`` (the catalog's row) and
the layer equations of ISSUE 64's Motivation; what the keys do not fix is
listed under ``assumed`` in ``configs/ouro-2.6b-serve-whole.json``. This
sandbox has no network: where the published text differs from an item there,
the published text wins, and the difference is to be written down HERE (none
is known). With E the embedding, L = ``num_hidden_layers`` layers and T =
``total_ut_steps`` passes over the SAME L layers:

    x = E[tokens]
    for t in 0..T-1:
      for i in 0..L-1:
        a = RMSNorm(x; ln_attn_i)
        q, k, v = a Wq_i, a Wk_i, a Wv_i        (no bias, no QK norm)
        q, k rotated over the whole head, pairs (j, j + D/2), ``rope_theta``,
          by the token's position, the same in every pass
        o = softmax(q k^T / sqrt(D), causal) v  (this pass's OWN k and v)
        x = x + RMSNorm(o Wo_i; ln_attn_post_i) (the sandwich: a norm BEHIND)
        m = RMSNorm(x; ln_mlp_i)
        x = x + RMSNorm((SiLU(m Wg_i) * (m Wu_i)) Wd_i; ln_mlp_post_i)
      x = RMSNorm(x; ln_f)                      (the final norm, EVERY pass)
      h_t = x                                   (and the next pass's input)
      lam_t = sigmoid(h_t . w_e + b_e)          (the exit gate)
    p_t = lam_t prod_{j<t} (1 - lam_j),  p_{T-1} = prod_{j<T-1} (1 - lam_j)
    leave at the first t with sum_{j<=t} p_j >= ``early_exit_threshold``,
      else at the last; threshold 1: the last
    logits = h_leave W_head

RMSNorm is ``x * rsqrt(mean(x^2) + rms_norm_eps) * g`` in float32.

**Departures** from the published text: none is known. It reads the
program's parameter tree and not a checkpoint: layer weights stacked on a
leading layer axis (``blocks[name][i]``), ``wq`` / ``wk`` / ``wv`` [hidden,
heads, D], ``wo`` [heads, D, hidden], ``wi_gate`` / ``wi_up`` / ``wo_mlp``,
the four norms ``ln_attn`` / ``ln_attn_post`` / ``ln_mlp`` / ``ln_mlp_post``,
``ln_f``, the gate ``exit_w`` [hidden] and ``exit_b`` [1], ``embed``,
``unembed``.

Every matmul runs under ``default_matmul_precision("highest")``. What the
check's limits must refuse, each a keyword of ``logits``: ``passes`` (three
for four); ``drop`` "sandwich" (the norm behind a sublayer), "between" (the
final norm between passes: it then runs once, behind the last), "gate" (lam
one half everywhere); ``one_cache_from`` P (queries at positions P and later,
a decode step's, read pass 0's rows of every earlier position in every pass:
one cache for four); ``precision`` "bfloat16" (bfloat16 operands and a
bfloat16 accumulator, ``reference_zaya._mm``).

``precision="stated"`` is the precision the configuration STATES and no
lower one (it sets no limit: it says how far that precision alone stands from
float32, so that what the system reads beyond it is the program's): every
matmul on bfloat16 operands with a float32 accumulator, and every value the
program stores between operations (a norm's output, q, k, v, a sublayer's
output, the stream after each residual sum) rounded to bfloat16
(``lax.reduce_precision``: the chip's compiler takes a conversion there and
back out as excess precision). 384 sublayer outputs are summed into ONE
stream that is re-normed four times: a rounding of 2^-9 a stored value does
not average out over them as it does over 16 to 56 sublayers elsewhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference import (  # noqa: F401 — shared, model-free pieces
    _f32, compare_logits, compare_tokens, rms_norm, rotary)
from benchmarks.reference_laguna import _mm as _mm_lower, _take


def _carried(v, precision):
    """A value the program stores between two operations, in the stated
    precision: rounded to bfloat16."""
    return jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7) \
        if precision == "stated" else v


def _mm(a, b, precision):
    """``reference_laguna._mm``, and the stated precision's product:
    bfloat16 operands, a float32 accumulator, the result stored bfloat16."""
    if precision == "stated":
        return _carried(_carried(a, precision) @ _carried(b, precision),
                        precision)
    return _mm_lower(a, b, precision)


def _norm(x, weight, eps, precision):
    """``rms_norm``; in the stated precision the normed value is bfloat16
    and so is its product with the weight."""
    if precision != "stated":
        return rms_norm(x, weight, eps)
    return _carried(_carried(rms_norm(x, 1.0, eps), precision)
                    * _carried(weight, precision), precision)


def static_of(config: dict) -> dict:
    if config["model_type"] != "ouro" or config["hidden_act"] != "silu" \
            or config.get("sliding_window") or config["tie_word_embeddings"]:
        raise ValueError("the looped block: SiLU-gated MLPs, full attention, "
                         "an untied head")
    return dict(theta=float(config["rope_theta"]),
                eps=float(config["rms_norm_eps"]))


@functools.partial(jax.jit, static_argnames=(
    "theta", "eps", "precision", "sandwich", "shared_from"))
def layer(x, p, positions, shared, *, theta, eps, precision="highest",
          sandwich=True, shared_from=None):
    """One layer of one pass on x [B, S, hidden], float32. Returns (x, (k,
    v)), this pass's rotated keys and its values [B, S, heads, D].
    ``shared`` with ``shared_from``: another pass's (k, v), read by the
    queries at positions ``shared_from`` and later for every position but
    their own."""
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        mm = functools.partial(_mm, precision=precision)
        b, s, h = x.shape
        heads, d = p["wq"].shape[1:]
        stored = functools.partial(_carried, precision=precision)
        norm = functools.partial(_norm, eps=eps, precision=precision)
        a = norm(x, p["ln_attn"])
        q, k, v = (mm(a, p[w].reshape(h, -1)).reshape(b, s, -1, d)
                   for w in ("wq", "wk", "wv"))
        q, k = (stored(rotary(t, positions, theta)) for t in (q, k))
        group = heads // k.shape[2]

        def attend(k, v):
            k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
            return jnp.einsum("bqnd,bknd->bnqk", q, k) / (d ** 0.5), v

        scores, values = attend(k, v)
        causal = positions[:, None, :, None] >= positions[:, None, None, :]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        out = jnp.einsum("bnqk,bknd->bqnd", probs, values)
        if shared_from is not None:
            # a query from `shared_from` on: its own position's row is this
            # pass's, every other row the other pass's
            others, other_values = attend(*shared)
            own = positions[:, None, :, None] == positions[:, None, None, :]
            mixed = jax.nn.softmax(jnp.where(
                causal, jnp.where(own, scores, others), -jnp.inf), axis=-1)
            mixed_out = jnp.einsum("bnqk,bknd->bqnd", jnp.where(
                own, 0.0, mixed), other_values) + jnp.einsum(
                    "bnqk,bknd->bqnd", jnp.where(own, mixed, 0.0), values)
            late = (positions >= shared_from)[:, :, None, None]
            out = jnp.where(late, mixed_out, out)
        out = mm(stored(out).reshape(b, s, -1), p["wo"].reshape(-1, h))
        x = stored(x + (norm(out, p["ln_attn_post"]) if sandwich else out))
        m = norm(x, p["ln_mlp"])
        out = mm(stored(stored(jax.nn.silu(mm(m, p["wi_gate"])))
                        * mm(m, p["wi_up"])), p["wo_mlp"])
        return stored(x + (norm(out, p["ln_mlp_post"]) if sandwich
                           else out)), (k, v)


@functools.partial(jax.jit, static_argnames=("eps", "normed", "gated",
                                             "precision"))
def pass_end(x, ln_f, exit_w, exit_b, *, eps, normed=True, gated=True,
             precision="highest"):
    """(h_t, lam_t [B, S]): the final norm, and the gate on what it gives."""
    with jax.default_matmul_precision("highest"):
        h = _norm(x, _f32(ln_f), eps, precision) if normed else x
        lam = jax.nn.sigmoid(h @ _f32(exit_w) + _f32(exit_b)[0])
        return h, (lam if gated else jnp.full_like(lam, 0.5))


def exit_distribution(lam):
    """lam: a list of T arrays [B, S] -> p [T, B, S], which sums to one."""
    pdf, staying = [], jnp.ones_like(lam[0])
    for gate in lam[:-1]:
        pdf.append(gate * staying)
        staying = staying * (1.0 - gate)
    return jnp.stack(pdf + [staying])


def leaves_at(pdf, threshold: float):
    """The pass each token leaves at, int32 [B, S]: the first whose cumulated
    probability reaches ``threshold``, else the last."""
    reached = jnp.cumsum(pdf, axis=0) >= threshold
    return jnp.where(reached.any(0), jnp.argmax(reached, axis=0),
                     pdf.shape[0] - 1).astype(jnp.int32)


def logits(params, tokens, config: dict, last: int = 0, passes=None,
           drop=(), one_cache_from=None, precision: str = "highest"):
    """Full forward of ``tokens`` [B, S] -> (float32 logits [B, S, vocab] or
    of the last ``last`` positions, ``exit_pdf`` float32 [T, B, S])."""
    static = static_of(config)
    layers = config["num_hidden_layers"]
    passes = passes or int(config["total_ut_steps"])
    threshold = float(config["early_exit_threshold"])
    tokens = jnp.asarray(tokens, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    x = jnp.asarray(params["embed"][tokens], jnp.float32)
    take, first_pass = _take(), {}
    outputs, lam = [], []
    for t in range(passes):
        for i in range(layers):
            shared = first_pass.get(i) if t else None
            x, rows = layer(
                x, take(params["blocks"], i), positions, shared,
                precision=precision, sandwich="sandwich" not in drop,
                shared_from=one_cache_from if shared is not None else None,
                **static)
            if one_cache_from is not None and not t:
                first_pass[i] = rows
            x.block_until_ready()  # one float32 layer at a time
        x, gate = pass_end(
            x, params["ln_f"], params["exit_w"], params["exit_b"],
            eps=static["eps"],
            normed="between" not in drop or t == passes - 1,
            gated="gate" not in drop, precision=precision)
        outputs.append(x)
        lam.append(gate)
    pdf = exit_distribution(lam)
    # the pass a token leaves at chooses its h; threshold 1: the last
    at = leaves_at(pdf, threshold)
    h = jnp.take_along_axis(jnp.stack(outputs), at[None, ..., None], 0)[0]
    if last:
        h = h[:, -last:]
    with jax.default_matmul_precision("highest"):
        return _mm(h, _f32(params["unembed"]), precision), pdf
