"""Plain reference of the Solar-Open2 block (upstage/Solar-Open2-250B,
``model_type`` solar_open2; the recurrence is Kimi-Linear's, arXiv:2510.26692;
the output gate on softmax attention is arXiv:2505.06708's "G1"):
``jax.numpy``, float32, one sequence at a time, the recurrence position by
position, full causal attention a query head at a time, no cache, no chunk,
no sort, no grouped matmul, no kernel, nothing from ``ray_tpu.models``.

Written from the keys of the model's ``config.json`` as the catalog row gives
them (ISSUE 67's Tentpole, section 1); what the keys do not fix is ASSUMED,
said so below and listed under ``assumed`` in
``configs/solar-open2-250b-serve-ep16-d8.json``, each item with its reason.
This sandbox has no network: where the published modeling code differs from
an item, the published code wins, and the difference is to be written down
HERE (none is known). Layers are numbered from 0 as ``gqa_layers`` numbers
them: layer l is a **gqa** layer if l is in ``gqa_layers`` (0, 4, ...:
``gqa_interval`` 3 delta-rule layers between two of them), else a **kda**
layer. EVERY layer's second sublayer is the sparse one
(``first_k_dense_replace`` 0; ``intermediate_size`` 10240 is read by no
layer). ``x`` the stream, ``y = RMSNorm(x)`` (eps ``rms_norm_eps``, one
weight a channel) before each sublayer, the residual sum behind each; a final
RMSNorm; an untied head; no bias anywhere.

1. gqa layer: ``q = y Wq`` (``num_attention_heads`` 64 heads of ``head_dim``
   128), ``k = y Wk``, ``v = y Wv`` (``num_key_value_heads`` 8 of 128); NO
   rotation (``use_rope`` false: ``rope_theta`` and ``partial_rotary_factor``
   are read by nothing) and no other position signal; causal ``softmax(q k^T
   / sqrt(128)) v``, query heads 8g .. 8g + 7 on KV head g (the heads of a
   group adjoin); the gate (``use_gqa_gate``): ``o <- o * sigmoid(y Wg)``,
   ``Wg`` hidden -> 64 x 128, elementwise, from the sublayer's own normed
   input, before ``Wo``; ``x <- x + o Wo``. ASSUMED: the gate's form
   (elementwise, sigmoid, no bias, a matrix of its own: the config gives the
   flag alone; the family convention of gated attention, the paper's G1
   position); no q/k norm (the config has no key for one).
2. kda layer, ``linear_attn_config``: ``num_heads`` 64 heads of ``head_dim``
   D = 128 for q, k and v alike (``num_kv_heads`` null): ``q~, k~, v~ = y
   Wq, y Wk, y Wv``; a causal depthwise convolution of
   ``short_conv_kernel_size`` 4 taps, then SiLU, on each: ``c_t = silu(sum_j
   w_j z_{t-3+j})``; ``q = l2norm(c_q) / sqrt(D)``, ``k = l2norm(c_k)`` (x /
   sqrt(sum x^2 + 1e-6)), ``v = c_v``; decay by KEY channel ``a_t =
   exp(-exp(A_log[h]) softplus((y Wfa) Wfb + dt_bias))``;
   ``kda_allow_neg_eigval``: ``beta_t = 2 sigmoid(y Wb)``, in (0, 2), so that
   ``I - beta k k^T`` has the eigenvalue ``1 - beta`` in (-1, 1) along k; the
   state S of a head [keys, values], float32, zero at the start: ``S_t = (I
   - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T
   q_t``; ``o <- RMSNorm(o; weight [D]) * sigmoid((y Wga) Wgb)``; ``x <- x +
   concat(o) Wo``. ``kda_use_full_proj`` false: the decay gate and the output
   gate are low-rank, hidden -> r -> 64 x 128. ASSUMED: r = ``head_dim``
   128, as Kimi-Linear's; the initialisers (``configs/...``, ``assumed``).
3. Experts: ``s = sigmoid(y Wr)`` over all ``n_routed_experts`` 320 in
   float32; chosen: the top ``num_experts_per_tok`` 8 of ``s + b``, b a
   stored selection bias; weights ``s[chosen]`` (without b) over their sum
   (``norm_topk_prob``), times ``routed_scaling_factor`` 1; ``x <- x + sum
   over chosen e HELD of w_e SwiGLU_e(y) + SwiGLU_shared(y)``
   (``n_shared_experts`` 1, ``moe_intermediate_size`` 1280 wide, ungated).
   ASSUMED (the config has no ``scoring_func``, ``topk_method`` or
   ``hidden_act``): sigmoid scores, the stored bias, the weights without it,
   SiLU: the convention of the ``glm4_moe`` line that Solar Open's first
   release follows.
4. Final RMSNorm, logits through ``unembed`` over the held rows.

**The share.** One chip's share of a layer that 16 chips hold
(``deployment``): the router has all its published outputs, the parameter
tree holds the experts ``experts_held_first ..`` of every layer (as many as
its expert stacks have) and the first ``vocab_size`` rows of the vocabulary.
What the absent experts would add is left out, here as in the program.
``routed_part(..., first, count)`` (``reference_laguna``'s) is one share's
part alone, so that a test can add the shares up to the uncut layer.

It reads the program's parameter tree (``blocks["gkv"]``: ``wq``, ``wg`` [L,
hidden, H * D], ``wk``, ``wv`` [L, hidden, kvH * D], ``wo`` [L, H * D,
hidden]; ``blocks["kda"]`` as ``reference_kimi_linear`` says; ``blocks[
"sparse"]`` as Laguna's plus ``router_bias`` [L, E]). Every matmul runs under
``default_matmul_precision("highest")``. What the check's limits must refuse
are keywords: ``precision="bfloat16"`` computes every matmul on bfloat16
operands with a bfloat16 accumulator (all but the routed experts' and the
recurrence's own products); ``state="bfloat16"`` keeps the matrix state in
bfloat16 between positions; ``drop`` names a part to leave out or to change:
"gate" (no output gate on the gqa layers), "beta2" (beta = sigmoid alone: the
factor 2 dropped), "groups" (KV groups interleaved: query head i on KV head i
mod 8, not i div 8), "conv" (no convolution: c = silu(z)), "shared" (no
shared expert); and, beyond the issue's seven, "decay", "kda_gate", "bias".

``precision="stated"`` is the precision the configuration STATES and no lower
one (it sets no limit: it says how far that precision alone stands from
float32): every matmul on bfloat16 operands with a float32 accumulator, the
stream and what the projections hand on rounded to bfloat16; the decay, beta,
the state, its read-out, both gates' sigmoids, the router and the logits
float32.

**Routes.** Eight of 320 experts a token: the 8th and 9th selection scores
lie close, and the system's bfloat16 stream flips them now and then.
``logits(follow=...)`` is given the sets the system took and takes the
system's set wherever ITS OWN scores call it a tie (``ROUTE_TIE_MARGIN``, in
units of the selection score); a set further off is ``refused``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference import (  # noqa: F401 — shared, model-free pieces
    _f32, compare_logits, compare_tokens, rms_norm)
# The router IS Kimi-Linear's (sigmoid scores in float32, the top k of score
# + a stored bias, the weights the scores alone over their sum, times the
# factor) and so is the margin under which the reference takes the system's
# set of experts as a tie (`reference_kimi_linear.ROUTE_TIE_MARGIN` has its
# readings; this configuration's are in `runners/serve_solar_open2.py`).
from benchmarks.reference_kimi_linear import (  # noqa: F401
    ROUTE_TIE_MARGIN, _l2norm, router_weights)
from benchmarks.reference_laguna import (  # noqa: F401
    EXPERT_LEAVES, _mm as _mm_lower, _routes, _take, head, routed_part)

BF16, F32 = jnp.bfloat16, jnp.float32


def _mm(a, b, precision):
    """``reference_laguna._mm``, and the stated precision's product: bfloat16
    operands, a float32 accumulator."""
    if precision == "stated":
        return jnp.matmul(a.astype(BF16), b.astype(BF16),
                          preferred_element_type=F32)
    return _mm_lower(a, b, precision)


def _carried(v, precision):
    """What the stream and a projection's output are in the stated precision:
    bfloat16 values (``reduce_precision``: the chip's compiler takes a
    conversion there and back out as excess precision)."""
    return jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7) \
        if precision == "stated" else v


def swiglu(y, gate, up, down, precision="highest"):
    act = _carried(jax.nn.silu(_mm(y, gate, precision))
                   * _mm(y, up, precision), precision)
    return _mm(act, down, precision)


def gqa(y, layer, *, kv_heads, precision="highest", drop=()):
    """Step 1 on the normed stream y [S, hidden] -> [S, hidden], full causal
    attention, one query head at a time."""
    s, _ = y.shape
    d = layer["wk"].shape[-1] // kv_heads
    nh = layer["wq"].shape[-1] // d
    mm = functools.partial(_mm, precision=precision)
    q = _carried(mm(y, layer["wq"]), precision).reshape(s, nh, d)
    k = _carried(mm(y, layer["wk"]), precision).reshape(s, kv_heads, d)
    v = _carried(mm(y, layer["wv"]), precision).reshape(s, kv_heads, d)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def one_head(i):
        g = i % kv_heads if "groups" in drop else i // (nh // kv_heads)
        qi = jax.lax.dynamic_index_in_dim(q, i, axis=1, keepdims=False)
        kg = jax.lax.dynamic_index_in_dim(k, g, axis=1, keepdims=False)
        vg = jax.lax.dynamic_index_in_dim(v, g, axis=1, keepdims=False)
        scores = mm(qi, kg.T) * d ** -0.5
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        # whole eights of keys: the bfloat16 accumulator sums 8 products at
        # a time (`reference_zaya._mm`), and a zero key adds nothing
        probs = jnp.pad(_carried(probs, precision), ((0, 0), (0, -s % 8)))
        return mm(probs, jnp.pad(vg, ((0, -s % 8), (0, 0))))

    o = jax.lax.map(one_head, jnp.arange(nh))  # [H, S, D]
    o = jnp.moveaxis(o, 0, 1).reshape(s, -1)
    if "gate" not in drop:
        o = o * jax.nn.sigmoid(mm(y, layer["wg"]))
    return mm(_carried(o, precision), layer["wo"])


def recur(q, k, v, log_a, beta, state="float32"):
    """The delta rule ALONE, a position at a time from a zero state: q, k, v,
    log_a [S, H, D], beta [S, H] -> (the last state [H, K, V], o [S, H, V]);
    ``state``: the dtype the state is kept in between positions."""
    kept = jnp.dtype(state)

    def position(mat, xs):  # mat [H, K, V]
        q, k, v, log_a, beta = xs
        mat = mat.astype(F32) * jnp.exp(log_a)[..., None]
        u = beta[:, None] * (v - jnp.einsum("hk,hkv->hv", k, mat))
        mat = (mat + k[..., None] * u[:, None, :]).astype(kept)
        return mat, jnp.einsum("hk,hkv->hv", q, mat.astype(F32))

    with jax.default_matmul_precision("highest"):
        mat, o = jax.lax.scan(
            position, jnp.zeros((*q.shape[1:], v.shape[-1]), kept),
            (q, k, v, log_a, beta))
    return mat.astype(F32), o


def kda(y, layer, *, eps, precision="highest", state="float32", drop=()):
    """Step 2 on the normed stream y [S, hidden] -> [S, hidden], the
    recurrence a position at a time."""
    s, hidden = y.shape
    taps, nh, d = layer["conv_q"].shape
    mm = functools.partial(_mm, precision=precision)

    def conv(z, w):  # z [S, H, D], w [taps, H, D]
        if "conv" in drop:
            return jax.nn.silu(z)
        seen = jnp.concatenate([jnp.zeros((taps - 1, nh, d), z.dtype), z])
        return jax.nn.silu(sum(w[j] * seen[j:j + s] for j in range(taps)))

    c = [conv(_carried(mm(y, layer[w]), precision).reshape(s, nh, d),
              layer["conv_" + w[1]]) for w in ("wq", "wk", "wv")]
    q, k, v = _l2norm(c[0]) / d ** 0.5, _l2norm(c[1]), c[2]
    f = mm(_carried(mm(y, layer["w_fa"]), precision),
           layer["w_fb"]).reshape(s, nh, d)
    log_a = -jnp.exp(layer["a_log"])[:, None] * jax.nn.softplus(
        f + layer["dt_bias"])
    if "decay" in drop:
        log_a = jnp.zeros_like(log_a)
    beta = jax.nn.sigmoid(mm(y, layer["w_b"]))  # [S, H]
    if "beta2" not in drop:
        beta = 2.0 * beta  # kda_allow_neg_eigval
    _, o = recur(q, k, v, log_a, beta, state)
    o = rms_norm(o, layer["o_norm"], eps)
    if "kda_gate" not in drop:
        o = o * jax.nn.sigmoid(mm(_carried(mm(y, layer["w_ga"]), precision),
                                  layer["w_gb"])).reshape(s, nh, d)
    return mm(_carried(o, precision).reshape(s, -1),
              layer["wo"].reshape(-1, hidden))


@functools.partial(jax.jit, static_argnames=(
    "kind", "kv_heads", "eps", "precision", "state", "drop"))
def attention_block(x, layer, *, kind, kv_heads, eps, precision="highest",
                    state="float32", drop=()):
    with jax.default_matmul_precision("highest"):
        layer = _f32(layer)
        y = _carried(rms_norm(x[0], layer["ln_attn"], eps), precision)
        out = kda(y, layer, eps=eps, precision=precision, state=state,
                  drop=drop) if kind == "kda" else gqa(
            y, layer, kv_heads=kv_heads, precision=precision, drop=drop)
        return _carried(x + out[None], precision)


@functools.partial(jax.jit, static_argnames=(
    "count", "top_k", "renormalize", "scale", "first", "eps", "precision",
    "drop"))
def sparse_block(x, small, experts, layer, follow, *, count, top_k,
                 renormalize, scale, first, eps, precision="highest",
                 drop=()):
    """``experts`` are the WHOLE stacks [L, count, ...] and ``layer`` the
    sparse layer (``reference_laguna.sparse_block``'s way)."""
    with jax.default_matmul_precision("highest"):
        b, s, h = x.shape
        small = _f32(small)
        stacks = {n: a.reshape(-1, *a.shape[2:]) for n, a in experts.items()}
        y = _carried(rms_norm(x, small["ln_mlp"], eps).reshape(b * s, h),
                     precision)
        w, chosen, gap = router_weights(
            y, small, top_k=top_k, renormalize=renormalize, scale=scale,
            follow=follow, drop=drop)
        # the routed experts stay at the highest precision, as Laguna's
        out = routed_part(y, w, stacks, layer * count, first, count)
        if "shared" not in drop:
            out = out + swiglu(y, small["shared_gate"], small["shared_up"],
                               small["shared_down"], precision)
        return _carried(x + out.reshape(b, s, h), precision), chosen, gap


def kinds_of(config: dict) -> list:
    """Every layer's kind ("gkv" as the program's tree names a gqa layer, or
    "kda"), layer 0 first, from ``gqa_layers``: ``gqa_interval`` delta-rule
    layers lie between two of them."""
    n, gqa_layers = config["num_hidden_layers"], config["gqa_layers"]
    every = config["gqa_interval"] + 1
    if [l for l in gqa_layers if l < n] != list(range(0, n, every)):
        raise ValueError(f"gqa_layers {gqa_layers} are not every "
                         f"{every}th of the {n} layers from layer 0")
    return ["gkv" if l in gqa_layers else "kda" for l in range(n)]


def logits(params, tokens, config: dict, last: int = 0, follow=None,
           precision: str = "highest", state: str = "float32", drop=()):
    """Full forward of ``tokens`` [1, S] -> (float32 logits [1, S, vocab] or
    of the last ``last`` positions, routes), as ``reference_laguna.logits``:
    ``routes`` has ``chosen`` [layers, S, k] and, with ``follow``, how many
    (layer, token) pairs were ``followed`` as ties and how many
    ``refused``."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = _carried(jnp.asarray(params["embed"][tokens], F32), precision)
    blocks, take = params["blocks"], _take()
    eps = float(config["rms_norm_eps"])
    sparse = {n: a for n, a in blocks["sparse"].items()
              if n not in EXPERT_LEAVES}
    experts = {n: blocks["sparse"][n] for n in EXPERT_LEAVES}
    seen = {"kda": 0, "gkv": 0}
    routing, gaps = [], []
    for l, kind in enumerate(kinds_of(config)):
        x = attention_block(x, take(blocks[kind], seen[kind]), kind=kind,
                            kv_heads=config["num_key_value_heads"], eps=eps,
                            precision=precision, state=state,
                            drop=tuple(drop))
        seen[kind] += 1
        told = None if follow is None else jnp.asarray(follow[l], jnp.int32)
        x, chosen, gap = sparse_block(
            x, take(sparse, l), experts, l, told,
            count=experts["wi_gate"].shape[1],
            top_k=config["num_experts_per_tok"],
            renormalize=bool(config["norm_topk_prob"]),
            scale=float(config["routed_scaling_factor"]),
            first=int(config.get("experts_held_first", 0)), eps=eps,
            precision=precision, drop=tuple(drop))
        routing.append(chosen)
        gaps.append(gap)
        x.block_until_ready()  # one float32 layer at a time (reference.py)
    if last:
        x = x[:, -last:]
    vocab = params["unembed"].shape[-1]
    out = head(x, params["ln_f"], params["unembed"], eps=eps,
               pieces=8 if vocab % 8 == 0 and vocab > 32768 else 1,
               precision="highest" if precision == "stated" else precision)
    routes = _routes(jnp.stack(routing), jnp.stack(gaps))
    routes["margin"] = ROUTE_TIE_MARGIN
    return out, routes
