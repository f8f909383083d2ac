"""Plain reference of the published OLMoE-1B-7B block: ``jax.numpy``, float32,
no cache, no sort, no grouped matmul, no kernels.

Written from the model's description (OlmoeForCausalLM, ``modeling_olmoe.py``:
pre-norm decoder, RMSNorm, rotary embeddings in the rotate-half layout, causal
multi-head attention, a sparse SwiGLU MLP, no biases, untied output head) and
importing nothing from ``ray_tpu.models``. It differs from ``reference.py``'s
block, which it borrows the shared pieces from, in three places:

1. ``q = RMSNorm_q(x W_q)``, ``k = RMSNorm_k(x W_k)``: a learned RMSNorm over
   the WHOLE projection (all heads together), before the split into heads and
   before the rotary embedding.
2. The router: ``p = softmax(x W_r)`` in float32 over all experts, the top-k
   of ``p``, and the k weights used as they are (``norm_topk_prob`` false) or
   divided by their sum (true).
3. Dropless: ``out_t = sum over e in topk(t) of p[t,e] * W_down,e(silu(W_gate,e
   y_t) * W_up,e y_t)`` for every token, whatever an expert's load. Computed
   here as the sum over ALL experts of ``w[t,e] * expert_e(y_t)`` with ``w``
   zero outside the token's top-k, one expert at a time, so that one float32
   ``[tokens, expert width]`` activation is live.

It reads the program's parameter tree (``router [L,h,E]``, ``wi_gate``,
``wi_up [L,E,h,m]``, ``wo_mlp [L,E,m,h]``, ``ln_q [L,heads*head_dim]``,
``ln_k [L,kv_heads*head_dim]``), because the comparison needs the same
weights. Departures from the published model: none.

Every matmul runs under ``default_matmul_precision("highest")``. A layer's
experts are upcast one expert at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# compare_logits and compare_tokens are re-exported with reference.py's
# tolerances and their reasons: the same precisions meet the same bounds
from benchmarks.reference import (  # noqa: F401
    _f32, _layer_at, compare_logits, compare_tokens, head, rms_norm, rotary)


def router_weights(y, router, *, top_k: int, norm_topk_prob: bool):
    """y [T,h] -> (w [T,E] float32, zero outside each token's top-k;
    chosen [T,k] the experts' indices)."""
    probs = jax.nn.softmax(y @ router, axis=-1)
    values, chosen = jax.lax.top_k(probs, top_k)
    if norm_topk_prob:
        values = values / jnp.sum(values, axis=-1, keepdims=True)
    one_hot = jax.nn.one_hot(chosen, probs.shape[-1], dtype=jnp.float32)
    return jnp.sum(one_hot * values[..., None], axis=1), chosen


def experts(y, layer, *, top_k: int, norm_topk_prob: bool, use=None):
    """The sparse MLP on y [T,h] float32: every expert on every token,
    weighted by ``w`` (zero for the experts a token did not choose).
    ``use`` [T,E] replaces ``w`` (tests: a dropped assignment)."""
    w, chosen = router_weights(y, _f32(layer["router"]), top_k=top_k,
                               norm_topk_prob=norm_topk_prob)
    if use is not None:
        w = use

    def one_expert(total, e):
        gate, up, down = (_f32(jax.lax.dynamic_index_in_dim(
            layer[name], e, keepdims=False))
            for name in ("wi_gate", "wi_up", "wo_mlp"))
        hidden = jax.nn.silu(y @ gate) * (y @ up)  # [T, m]
        return total + w[:, e, None] * (hidden @ down), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(y),
                          jnp.arange(w.shape[-1]))
    return out, chosen


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "theta", "eps", "top_k", "norm_topk_prob", "qk_norm"))
def block(x, layer, positions, *, heads, kv_heads, theta, eps, top_k,
          norm_topk_prob, qk_norm=True):
    """One decoder block on x [B, S, hidden], float32 -> (x, chosen
    [B*S, top_k]). ``qk_norm`` False leaves the two norms out (tests: the
    comparison must then fail)."""
    with jax.default_matmul_precision("highest"):
        b, s, h = x.shape
        y = rms_norm(x, _f32(layer["ln_attn"]), eps)
        q = jnp.einsum("bsh,hnd->bsnd", y, _f32(layer["wq"]))
        k = jnp.einsum("bsh,hnd->bsnd", y, _f32(layer["wk"]))
        v = jnp.einsum("bsh,hnd->bsnd", y, _f32(layer["wv"]))
        if qk_norm:  # over the whole projection, heads joined
            q = rms_norm(q.reshape(b, s, -1), _f32(layer["ln_q"]),
                         eps).reshape(q.shape)
            k = rms_norm(k.reshape(b, s, -1), _f32(layer["ln_k"]),
                         eps).reshape(k.shape)
        q = rotary(q, positions, theta)
        k = rotary(k, positions, theta)
        group = heads // kv_heads
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
        scores = jnp.einsum("bqnd,bknd->bnqk", q, k) / (q.shape[-1] ** 0.5)
        causal = positions[:, None, :, None] >= positions[:, None, None, :]
        scores = jnp.where(causal, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("bnqk,bknd->bqnd", probs, v)
        x = x + jnp.einsum("bsnd,ndh->bsh", attn, _f32(layer["wo"]))
        y = rms_norm(x, _f32(layer["ln_mlp"]), eps)
        out, chosen = experts(y.reshape(b * s, h), layer, top_k=top_k,
                              norm_topk_prob=norm_topk_prob)
        return x + out.reshape(b, s, h), chosen


def static_of(config: dict) -> dict:
    """``block``'s static arguments from a published ``config.json``."""
    return dict(heads=config["num_attention_heads"],
                kv_heads=config["num_key_value_heads"],
                theta=float(config["rope_theta"]),
                eps=float(config["rms_norm_eps"]),
                top_k=config["num_experts_per_tok"],
                norm_topk_prob=bool(config["norm_topk_prob"]))


def logits(params, tokens, config: dict, last: int = 0, qk_norm: bool = True):
    """Full forward of ``tokens`` [B, S] -> (float32 logits [B, S, vocab] or
    of the last ``last`` positions, chosen [L, B*S, top_k]: every layer's
    routing). One layer's weights are brought out of the stack at a time."""
    tokens = jnp.asarray(tokens, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    x = jnp.asarray(params["embed"][tokens], jnp.float32)
    layer_at = _layer_at(getattr(params["embed"].sharding, "mesh", None))
    routing = []
    for i in range(config["num_hidden_layers"]):
        x, chosen = block(x, layer_at(params["blocks"], i), positions,
                          qk_norm=qk_norm, **static_of(config))
        x.block_until_ready()  # one float32 layer at a time (reference.py)
        routing.append(chosen)
    if last:
        x = x[:, -last:]
    out = head(x, params["ln_f"], params["unembed"],
               eps=float(config["rms_norm_eps"]))
    return out, jnp.stack(routing)


def count_routing_differences(system_chosen, reference_chosen) -> dict:
    """How many (layer, token) pairs chose another SET of experts in the
    system than in the reference. A near-tie between a token's k-th and
    (k+1)-th probability flips under the system's bfloat16 rounding; both
    are the smallest of the chosen weights and differ by less than the
    rounding that swapped them, so the output moves by less than a rounding
    step of one expert's contribution: counted and reported, not judged
    (the logits' bound judges)."""
    import numpy as np

    a = np.sort(np.asarray(system_chosen), axis=-1)
    b = np.sort(np.asarray(reference_chosen), axis=-1)
    differ = np.any(a != b, axis=-1)
    return {"topk_sets_differ": int(differ.sum()), "of": int(differ.size)}
