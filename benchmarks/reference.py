"""Plain reference of the published Mistral-7B-v0.3 block: ``jax.numpy``,
float32, no kernels, no cache, no batching tricks.

Written from the model's description (MistralForCausalLM: pre-norm decoder,
RMSNorm, rotary embeddings in the rotate-half layout, grouped-query causal
attention, SwiGLU MLP, no biases, untied output head) and importing nothing
from ``ray_tpu.models``. It reads the program's parameter tree, because the
comparison needs the same weights: layer weights are stacked on a leading
layer axis (``blocks[name][layer]``), projections are stored as
``wq [hidden, heads, head_dim]``, ``wo [heads, head_dim, hidden]``.

One departure from the published model: the LoRA adapters of the train
configurations (``lora``: A and B on wq, wv and the MLP gate, scaled by
alpha / rank), which the model card does not have and the fine-tune adds.

Every matmul runs under ``default_matmul_precision("highest")``: on a TPU a
float32 matmul is otherwise done in bfloat16 passes. Layer weights are upcast
one layer at a time, so the reference holds one float32 layer beside the
system's bfloat16 model.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# Tolerances, with their reasons. The system multiplies in bfloat16 with
# float32 accumulation and keeps activations in bfloat16 (8 bits of
# mantissa, relative rounding 2^-9 = 0.2% per stored value).
#
# LOSS: the mean over ~500 positions of log-sum-exp minus the target logit.
# Rounding errors of single logits are independent and average out, so the
# system's loss sits within a few 1e-4 of the reference (measured on the
# chip: see PERF.md section 6); accumulating the 4096- to 14336-long dot
# products in bfloat16 would shift every logit by percents and the loss by
# 1e-2 or more. 2e-3 relative separates the two.
LOSS_REL_TOL = 2e-3
# LOGITS: the root-mean-square difference over the compared positions,
# divided by the standard deviation of the reference's logits there (the
# largest single difference is reported beside it; over millions of logits
# it is a five-sigma draw and decides nothing). bfloat16 products and stored
# activations err by about 0.3% per operation, independently, so through
# 8 to 32 layers the logits are off by 1-3% of a standard deviation
# (measured: PERF.md section 6). Accumulating dot products of 4096 to 14336
# terms in bfloat16 errs by about 10% per matmul, and a wrong mask or a
# stale cache row by more: 5% separates the two.
LOGITS_RMS_ERR_OVER_STD = 0.05
# GRADIENTS of the adapters: for every leaf, the norm of the difference over
# the norm of the reference's gradient. The backward pass repeats the
# forward's bfloat16 products and stored activations and adds its own (the
# kernels' dq, dk, dv err by 0.4-0.7% alone, PR 21), and a gradient is itself
# rounded to bfloat16: a few percent (measured: PERF.md section 6). A sum
# over the sample's 1024 tokens kept in bfloat16 errs by about 6% in every
# matmul of the backward pass and by more than 15% through the layers; a
# missing term (a kernel's dk, an adapter's scale) by tens of percent.
GRADS_REL_ERR = 0.10
# TOKENS: greedy decoding picks the system's largest logit. Where the
# reference's two largest are closer than the system's error the pick may
# differ and neither is wrong (over 32768 random logits the first two are
# about 0.2 standard deviations apart, so one pick in ten is that close).
# So the reference must give the token the system chose a logit within 0.15
# standard deviations of its own first: three times the bound on the RMS
# error, for a difference of two logits that err independently. A token from
# a wrong slot, a stale row or a wrong mask is a random one of 32768 and
# sits about 4 standard deviations below the first.
TOKEN_SHORTFALL_OVER_STD = 0.15


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def rms_norm(x, weight, eps):
    variance = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(variance + eps) * weight


def rotary(x, positions, theta):
    """x [B, S, heads, D]; rotate-half layout: the first D/2 dimensions pair
    with the last D/2."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[:, :, None].astype(jnp.float32) * inv_freq  # [B,S,D/2]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[:, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return x * cos + rotated * sin


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "theta",
                                             "eps", "lora_scale"))
def block(x, layer, lora, positions, *, heads, kv_heads, theta, eps,
          lora_scale):
    """One decoder block on x [B, S, hidden], float32."""
    with jax.default_matmul_precision("highest"):
        layer, lora = _f32(layer), _f32(lora)
        b, s, _ = x.shape
        y = rms_norm(x, layer["ln_attn"], eps)
        q = jnp.einsum("bsh,hnd->bsnd", y, layer["wq"])
        k = jnp.einsum("bsh,hnd->bsnd", y, layer["wk"])
        v = jnp.einsum("bsh,hnd->bsnd", y, layer["wv"])
        if lora is not None:
            q = q + ((y @ lora["wq_a"]) @ lora["wq_b"] * lora_scale
                     ).reshape(q.shape)
            v = v + ((y @ lora["wv_a"]) @ lora["wv_b"] * lora_scale
                     ).reshape(v.shape)
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
        x = x + jnp.einsum("bsnd,ndh->bsh", attn, layer["wo"])
        y = rms_norm(x, layer["ln_mlp"], eps)
        gate = y @ layer["wi_gate"]
        if lora is not None:
            gate = gate + (y @ lora["wi_a"]) @ lora["wi_b"] * lora_scale
        return x + (jax.nn.silu(gate) * (y @ layer["wi_up"])) @ layer["wo_mlp"]


@functools.partial(jax.jit, static_argnames=("eps",))
def head(x, ln_f, unembed, *, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, _f32(ln_f), eps) @ _f32(unembed)


@functools.lru_cache(maxsize=None)
def _layer_at(mesh):
    """Layer ``i`` of the stacked weights, whole on every device of ``mesh``
    (None: wherever they are). One program for all layers, the index being
    an argument: a sharded 32-layer model is gathered in 32 dispatches, not
    in hundreds of eager slices."""
    def take(tree, i):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), tree)

    if mesh is None:
        return jax.jit(take)
    whole = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    return jax.jit(take, out_shardings=whole)


def _layers(params, config: dict, lora_alpha: float, place, order):
    """``(layer, adapters, arguments of block)`` for the layers in ``order``,
    brought to the reference's device one at a time (which also gathers a
    sharded model)."""
    lora = params.get("lora")
    rank = lora["wq_a"].shape[-1] if lora is not None else 0
    layer_at = _layer_at(getattr(params["embed"].sharding, "mesh", None))
    static = dict(heads=config["num_attention_heads"],
                  kv_heads=config["num_key_value_heads"],
                  theta=float(config["rope_theta"]),
                  eps=float(config["rms_norm_eps"]),
                  lora_scale=(lora_alpha / rank) if rank else 0.0)
    for i in order:
        layer, lo = place(layer_at((params["blocks"], lora), i))
        yield layer, lo, static


def _forward(params, tokens, config: dict, lora_alpha: float, device):
    """The hidden state after the last block, each block's input, and the
    function that places a tree where the reference runs."""
    def place(tree):
        return tree if device is None else jax.device_put(tree, device)

    tokens = place(jnp.asarray(tokens, jnp.int32))
    positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    x = jnp.asarray(place(params["embed"])[tokens], jnp.float32)
    inputs = []
    for layer, lo, static in _layers(params, config, lora_alpha, place,
                                     range(config["num_hidden_layers"])):
        inputs.append(x)
        x = block(x, layer, lo, positions, **static)
        # output buffers are allocated when a program is enqueued: a host
        # that runs layers ahead of the device holds that many float32
        # layers at once (2.7 GB more on the serve replica, my chip run,
        # PR 22), and the peak the benchmark reports would be the check's
        x.block_until_ready()
    return x, inputs, positions, place


def logits(params, tokens, config: dict, lora_alpha: float = 16.0,
           last: int = 0, device=None):
    """Full forward of ``tokens`` [B, S] -> float32 logits [B, S, vocab], or
    of the last ``last`` positions. ``device``: where to run (the layer
    weights are brought there one layer at a time)."""
    x, _, _, place = _forward(params, tokens, config, lora_alpha, device)
    if last:
        x = x[:, -last:]
    return head(x, place(params["ln_f"]), place(params["unembed"]),
                eps=float(config["rms_norm_eps"]))


def _mean_xent(lg, tokens):
    """Mean next-token cross-entropy of float32 logits [B, S, vocab]."""
    lg = lg[:, :-1]
    logz = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(logz - picked)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "theta",
                                             "eps", "lora_scale"))
def _block_back(x, layer, lora, positions, dy, **static):
    """(dx, d adapters) of one block for the cotangent ``dy`` of its output."""
    _, vjp = jax.vjp(lambda x, lo: block(x, layer, lo, positions, **static),
                     x, _f32(lora))  # float32 adapters: float32 gradients
    return vjp(dy)


def loss_and_lora_grads(params, tokens, config: dict, lora_alpha: float = 16.0,
                        last: int = 0, device=None):
    """The loss of ``tokens``, its gradient with respect to the adapters
    (float32, leaves stacked over the layers as the program's are) and the
    logits of the last ``last`` positions: forward keeping each block's
    input, then back through the blocks one at a time, so that one float32
    layer is held at a time here too."""
    x, inputs, positions, place = _forward(params, tokens, config, lora_alpha,
                                           device)
    ln_f, unembed = place(params["ln_f"]), place(params["unembed"])
    targets = place(jnp.asarray(tokens, jnp.int32))

    def tail(x):
        lg = head(x, ln_f, unembed, eps=float(config["rms_norm_eps"]))
        return _mean_xent(lg, targets), lg[:, -last:]

    (value, last_logits), dx = jax.value_and_grad(tail, has_aux=True)(x)
    per_layer = []
    order = reversed(range(config["num_hidden_layers"]))
    for layer, lo, static in _layers(params, config, lora_alpha, place, order):
        dx, d_lora = _block_back(inputs.pop(), layer, lo, positions, dx,
                                 **static)
        dx.block_until_ready()
        per_layer.append(d_lora)
    grads = jax.tree.map(lambda *leaves: jnp.stack(leaves),
                         *reversed(per_layer))
    return float(value), grads, last_logits


def compare_loss(system_loss: float, reference_loss: float) -> dict:
    rel = abs(system_loss - reference_loss) / abs(reference_loss)
    return {"system_loss": system_loss, "reference_loss": reference_loss,
            "rel_diff": rel, "tol": LOSS_REL_TOL,
            "ok": bool(rel <= LOSS_REL_TOL)}


def compare_logits(system_logits, reference_logits) -> dict:
    import numpy as np

    sys_l = np.asarray(system_logits, np.float32)
    ref_l = np.asarray(reference_logits, np.float32)
    diff, std = sys_l - ref_l, float(np.std(ref_l))
    rms = float(np.sqrt(np.mean(diff * diff)) / std)
    return {"rms_err_over_std": rms, "tol": LOGITS_RMS_ERR_OVER_STD,
            "max_err_over_std": float(np.max(np.abs(diff)) / std),
            "argmax_agree": float(np.mean(
                sys_l.argmax(-1) == ref_l.argmax(-1))),
            "ok": bool(np.isfinite(rms) and rms <= LOGITS_RMS_ERR_OVER_STD)}


def compare_tokens(chosen, reference_logits) -> dict:
    """``chosen[i]`` is the token the system picked where the reference's
    logits are ``reference_logits[i]``."""
    import numpy as np

    ref_l = np.asarray(reference_logits, np.float32)
    out = {"n": len(chosen), "tol": TOKEN_SHORTFALL_OVER_STD}
    if len(chosen) != len(ref_l):
        return dict(out, ok=False)
    picked = ref_l[np.arange(len(chosen)), np.asarray(chosen)]
    shortfall = float(np.max((ref_l.max(-1) - picked) / ref_l.std(-1)))
    return dict(out, max_shortfall_over_std=shortfall,
                argmax_agree=float(np.mean(ref_l.argmax(-1) == chosen)),
                ok=bool(shortfall <= TOKEN_SHORTFALL_OVER_STD))


def compare_grads(system_grads: dict, reference_grads: dict) -> dict:
    """Per-leaf relative error of the adapters' gradients; the worst decides."""
    import numpy as np

    rel = {}
    for name, ref_g in reference_grads.items():
        ref_g = np.asarray(ref_g, np.float32)
        diff = np.asarray(system_grads[name], np.float32) - ref_g
        rel[name] = float(np.linalg.norm(diff) / np.linalg.norm(ref_g))
    worst = max(rel.values())
    return {"rel_err": rel, "tol": GRADS_REL_ERR,
            "ok": bool(np.isfinite(worst) and worst <= GRADS_REL_ERR)}
