"""Plain reference of the Jamba block (ai21labs/AI21-Jamba2-3B, ``model_type``
jamba): ``jax.numpy``, float32, one sequence at a time, the selective
recurrence a position at a time, attention by the full score matrix of one
query head at a time, no cache, no chunk, no kernel, no batching, nothing from
``ray_tpu.models`` or ``ray_tpu.ops``.

Written from the keys of the model's ``config.json`` (the catalog's row) and
the layer equations of ISSUE 60's Motivation; what the keys do not fix is
listed under ``assumed`` in ``configs/jamba2-3b-serve-whole.json``, each item
with its reason. This sandbox has no network: where the published text
differs from an item there, the published text wins, and the difference is to
be written down HERE (none is known). Layer i of ``num_hidden_layers`` is an
attention layer iff ``i % attn_layer_period == attn_layer_offset``, else a
Mamba-1 mixer; ``num_experts`` 1 makes every layer's second sublayer the same
dense MLP. A layer is ``x <- x + mixer(norm(x))``, ``x <- x + mlp(norm(x))``
(RMSNorm, eps ``rms_norm_eps``, a weight each). With y the normed stream:

1. A Mamba-1 mixer (d_inner C = ``mamba_expand * hidden_size``, N =
   ``mamba_d_state``, R = ``mamba_dt_rank``, ``mamba_d_conv`` taps): ``[u ;
   z] = y W_in``; a causal depthwise convolution WITH a bias over u alone,
   then SiLU: ``x_t = silu(sum_j w_j u_{t-3+j} + b)``; ``[dt~ ; B~ ; C~] = x
   W_x`` (R, N, N columns), each through an RMSNorm with a weight of its own
   (eps ``rms_norm_eps``); ``dt = softplus(dt~ W_dt + b_dt)`` [C]; ``A =
   -exp(A_log)``; channel c's state h [N], zero at the start: ``h_t[n] =
   exp(dt_t[c] A[c, n]) h_{t-1}[n] + dt_t[c] x_t[c] B_t[n]``, ``o_t[c] =
   sum_n h_t[n] C_t[n] + D[c] x_t[c]``; ``o <- o * silu(z)``; ``x <- x + o
   W_out``. No norm behind the gate, no positional signal.
2. Attention: ``q = y Wq`` (``num_attention_heads`` of ``hidden_size /
   num_attention_heads``), ``k, v = y Wk, y Wv`` (``num_key_value_heads``);
   causal softmax of ``q k^T / sqrt(head_dim)``; ``x <- x + concat(o) Wo``.
   No rotation, no bias.
3. The MLP: ``W_down(silu(y W_gate) * (y W_up))``.
4. Final RMSNorm; the logits through the EMBEDDING's transpose
   (``tie_word_embeddings``).

**Departures**, each because it reads the program's parameter tree and not
a checkpoint: ``A_log`` is kept transposed, [N, C] (``blocks["ssm1"]
["a_log"]`` [L, N, C]: as the program's states lie), and so is read as
``A[c, n] = -exp(a_log[n, c])``; a published layer's two sublayers are two
entries of the program's ``layer_kinds`` and their parameters lie in two
stacks by kind (``blocks["ssm1" | "gqa"]`` then ``blocks["mlp"]``), each with
its norm as ``ln``. ``blocks["ssm1"]``: ``ln``, ``w_in`` [L, hidden, 2 C] (u
then z), ``conv_w`` [L, taps, C], ``conv_b``, ``w_x`` [L, C, R + 2 N],
``dt_norm``, ``b_norm``, ``c_norm``, ``w_dt`` [L, R, C], ``dt_bias``,
``a_log``, ``d`` [L, C], ``w_out``; ``blocks["gqa"]``: ``ln``, ``wq``,
``wk``, ``wv``, ``wo``; ``blocks["mlp"]``: ``ln``, ``wi_gate``, ``wi_up``,
``wo_mlp``.

Every matmul runs under ``default_matmul_precision("highest")``;
``precision="bfloat16"`` computes every matmul on bfloat16 operands with a
bfloat16 accumulator (the recurrence's own products stay float32);
``state="bfloat16"`` keeps the state in bfloat16 between positions;
``scan_sum="bfloat16"`` sums the read-out over the state index in bfloat16:
all three are what the check's limits must refuse. ``drop`` names a part to
leave out, which they must refuse too: "norms" (the three small norms),
"conv_bias", "d" (no ``D x``), "dt_bias".

``precision="stated"`` is the precision the configuration STATES and no
lower one (it sets no limit: it says how far that precision alone stands from
float32, so that what the system reads beyond it is the program's): every
matmul on bfloat16 operands with a float32 accumulator; the stream, what is
computed on it elementwise (a norm's value and its product with the weight,
the MLP's SiLU) and what the projections hand on (u, z, q, k, v, the
probabilities, the MLP's two halves) rounded to bfloat16; dt, the decay, the
state, the read-out, the gate, the three small norms and the logits float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference import (  # noqa: F401 — shared, model-free pieces
    _f32, compare_logits, compare_tokens, rms_norm)
from benchmarks.reference_laguna import _mm as _mm_lower, _take

BF16, F32 = jnp.bfloat16, jnp.float32


def _mm(a, b, precision):
    """``reference_laguna._mm``, and the stated precision's product:
    bfloat16 operands, a float32 accumulator."""
    if precision == "stated":
        return jnp.matmul(a.astype(BF16), b.astype(BF16),
                          preferred_element_type=F32)
    return _mm_lower(a, b, precision)


def _carried(v, precision):
    """What the stream and a projection's output are in the stated
    precision: bfloat16 values (``reduce_precision``: the chip's compiler
    takes a conversion there and back out as excess precision, and the
    stated reference then read 0.011 where the CPU's read 0.019)."""
    return jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7) \
        if precision == "stated" else v


def _norm(x, weight, eps, precision):
    """RMSNorm of the STREAM (the three small norms are float32 in every
    precision); in the stated precision the normed value is bfloat16 and so
    is its product with the weight."""
    if precision != "stated":
        return rms_norm(x, weight, eps)
    return _carried(_carried(rms_norm(x, 1.0, eps), precision) * weight,
                    precision)


def layer_types(config: dict) -> list:
    """"ssm1" or "gqa" for each of the ``num_hidden_layers`` layers."""
    return ["gqa" if i % config["attn_layer_period"]
            == config["attn_layer_offset"] else "ssm1"
            for i in range(config["num_hidden_layers"])]


def mamba(y, layer, *, eps, precision="highest", state="float32",
          scan_sum="float32", drop=()):
    """Step 1 on the normed stream y [S, hidden] -> [S, hidden], the
    recurrence a position at a time."""
    s, _ = y.shape
    taps, inner = layer["conv_w"].shape
    rank, n = layer["dt_norm"].shape[0], layer["b_norm"].shape[0]
    mm = functools.partial(_mm, precision=precision)
    uz = _carried(mm(y, layer["w_in"]), precision)
    u, z = uz[:, :inner], uz[:, inner:]
    seen = jnp.concatenate([jnp.zeros((taps - 1, inner), u.dtype), u])
    x = sum(layer["conv_w"][j] * seen[j:j + s] for j in range(taps))
    if "conv_bias" not in drop:
        x = x + layer["conv_b"]
    x = jax.nn.silu(x)
    low = mm(x, layer["w_x"])
    dt, b, c = low[:, :rank], low[:, rank:rank + n], low[:, rank + n:]
    if "norms" not in drop:
        dt, b, c = (rms_norm(v, layer[w], eps) for v, w in (
            (dt, "dt_norm"), (b, "b_norm"), (c, "c_norm")))
    dt = mm(dt, layer["w_dt"])
    if "dt_bias" not in drop:
        dt = dt + layer["dt_bias"]
    dt = jax.nn.softplus(dt)  # [S, C]
    _, o = recurrence(x, dt, b, c, -jnp.exp(layer["a_log"]).T, state=state,
                      scan_sum=scan_sum)
    if "d" not in drop:
        o = o + layer["d"] * x
    return mm(o * jax.nn.silu(z), layer["w_out"])


def recurrence(x, dt, b, c, a, *, state="float32", scan_sum="float32"):
    """The selective recurrence a position at a time from a zero state: x, dt
    [S, C]; b, c [S, N]; a [C, N] (negative rates). Returns (the state after
    the last position [C, N] float32, o [S, C] without ``D x``)."""
    kept, summed = jnp.dtype(state), jnp.dtype(scan_sum)

    def position(h, xs):  # h [C, N]
        x, b, c, dt = xs
        h = jnp.exp(dt[:, None] * a) * h.astype(F32) \
            + (dt * x)[:, None] * b[None, :]
        h = h.astype(kept)
        o = jnp.sum((h.astype(F32) * c[None, :]).astype(summed),
                    axis=1, dtype=summed)
        return h, o.astype(F32)

    h, o = jax.lax.scan(position, jnp.zeros(a.shape, kept), (x, b, c, dt))
    return h.astype(F32), o


def attention(y, layer, *, kv_heads, head_dim, precision="highest"):
    """Step 2 on the normed stream y [S, hidden] -> [S, hidden], one query
    head's score matrix at a time."""
    s, _ = y.shape
    mm = functools.partial(_mm, precision=precision)
    q, k, v = (
        _carried(mm(y, layer[w]), precision).reshape(s, heads, head_dim)
        for w, heads in (("wq", -1), ("wk", kv_heads), ("wv", kv_heads)))
    rep = q.shape[1] // kv_heads
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def one_head(h):
        qh = jax.lax.dynamic_index_in_dim(q, h, axis=1, keepdims=False)
        kh = jax.lax.dynamic_index_in_dim(k, h // rep, axis=1, keepdims=False)
        vh = jax.lax.dynamic_index_in_dim(v, h // rep, axis=1, keepdims=False)
        scores = jnp.einsum("qd,kd->qk", qh, kh) / head_dim ** 0.5
        probs = _carried(jax.nn.softmax(
            jnp.where(causal, scores, -jnp.inf), axis=-1), precision)
        return jnp.einsum("qk,kd->qd", probs, vh)

    o = jax.lax.map(one_head, jnp.arange(q.shape[1]))  # [H, S, D]
    return mm(jnp.moveaxis(o, 0, 1).reshape(s, -1), layer["wo"])


def swiglu(y, layer, precision="highest"):
    """Step 3 on the normed stream."""
    gate, up = (_carried(_mm(y, layer[w], precision), precision)
                for w in ("wi_gate", "wi_up"))
    return _mm(_carried(jax.nn.silu(gate), precision) * up, layer["wo_mlp"],
               precision)


@functools.partial(jax.jit, static_argnames=(
    "kind", "kv_heads", "head_dim", "eps", "precision", "state", "scan_sum",
    "drop"))
def sublayer(x, layer, *, kind, kv_heads, head_dim, eps, precision="highest",
             state="float32", scan_sum="float32", drop=()):
    """One sublayer, "ssm1", "gqa" or "mlp", on x [1, S, hidden]."""
    with jax.default_matmul_precision("highest"):
        layer = _f32(layer)
        y = _norm(x[0], layer["ln"], eps, precision)
        if kind == "ssm1":
            out = mamba(y, layer, eps=eps, precision=precision, state=state,
                        scan_sum=scan_sum, drop=drop)
        elif kind == "gqa":
            out = attention(y, layer, kv_heads=kv_heads, head_dim=head_dim,
                            precision=precision)
        else:
            out = swiglu(y, layer, precision)
        return _carried(x + _carried(out, precision)[None], precision)


def logits(params, tokens, config: dict, last: int = 0,
           precision: str = "highest", state: str = "float32",
           scan_sum: str = "float32", drop=()):
    """Full forward of ``tokens`` [1, S] -> float32 logits [1, S, vocab] or of
    the last ``last`` positions."""
    if config["num_experts"] != 1:
        raise ValueError("num_experts 1: every feed-forward is the dense MLP")
    tokens = jnp.asarray(tokens, jnp.int32)
    x = jnp.asarray(params["embed"][tokens], jnp.float32)
    blocks, take = params["blocks"], _take()
    static = dict(
        kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        eps=float(config["rms_norm_eps"]), precision=precision, state=state,
        scan_sum=scan_sum, drop=tuple(drop))
    seen = dict.fromkeys(("ssm1", "gqa", "mlp"), 0)
    for mixer in layer_types(config):
        for kind in (mixer, "mlp"):
            x = sublayer(x, take(blocks[kind], seen[kind]), kind=kind,
                         **static)
            seen[kind] += 1
            x.block_until_ready()  # one float32 sublayer at a time
    if last:
        x = x[:, -last:]
    vocab = params["embed"].shape[0]
    return head(x, params["ln_f"], params["embed"].T,
                eps=float(config["rms_norm_eps"]),
                pieces=8 if vocab % 8 == 0 and vocab > 32768 else 1,
                precision=precision)


def head(x, ln_f, unembed, *, eps, pieces=1, precision="highest"):
    """``reference_laguna.head`` through this module's ``_mm``: logits over
    the held rows, the vocabulary in ``pieces`` so that one float32 piece of
    the head is live."""
    with jax.default_matmul_precision("highest"):
        x = _norm(x, _f32(ln_f), eps, precision)
        cols = unembed.reshape(unembed.shape[0], pieces, -1)
        out = jax.lax.map(
            lambda i: _mm(x, _f32(jax.lax.dynamic_index_in_dim(
                cols, i, axis=1, keepdims=False)), precision),
            jnp.arange(pieces))  # [pieces, B, S, V / pieces]
        return jnp.moveaxis(out, 0, -2).reshape(*x.shape[:-1], -1)
