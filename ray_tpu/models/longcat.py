"""A pattern of DOUBLE layers with the expert layer on a shortcut
(meituan-longcat/LongCat-Flash-Chat, `model_type` longcat_flash). Imported
only where a configuration has one (`TransformerConfig.pattern_module`, kind
"scmoe"); the latent attention, the expert matmuls, the router, sampling, the
scheduler and the drawing of weights are the other models'
(`kimi_linear.mla_attention` / `router`, `transformer.moe_dropless`,
`laguna._draw`).

**A double layer** holds two latent-attention sublayers `A_0`, `A_1`, two
dense SwiGLU MLPs `D_0`, `D_1` of `dense_mlp_hidden`, four RMSNorms `N_0..N_3`
and ONE expert layer `M` whose input is the first attention's output and whose
result joins the stream only at the END of the double layer:

    a = x + A_0(N_0(x));   y = N_1(a);   m = M(y)          (the shortcut)
    b = a + D_0(y);   c = b + A_1(N_2(b));   out = c + D_1(N_3(c)) + m

so the expert layer is no layer half but a branch beside the second attention
and the second MLP (where a layer's chips exchange tokens, that is what its
exchange hides behind; one chip runs it in program order). Parameters are
stacked BY KIND over (double layer, sublayer): `blocks["mla"]` and
`blocks["dense"]` [layers, 2, ...], `blocks["sparse"]` [layers, ...] (router,
its stored bias, the held experts' stacks, read in place by
`_grouped_matmul(layer=...)`); `forward_cached` is ONE `lax.scan` over the
double layers.

**Latent attention** is `kimi_linear.mla_attention` with the three fields
this family sets: a low-rank query (`mla_q_rank`), `q_r` and the one shared
`k_r` rotated by position with the pairs interleaved (`mla_rotate`,
`rope_theta`), and the two factors `mla_scales`. A sequence keeps one row `[c
; rot(k_r)]` a position and SUBLAYER: `KVCache.latent` has `2 x layers`
layers (`cfg.latent_layers`), sublayer j of double layer i at `2 i + j`.

**The expert layer.** `kimi_linear.router`: a float32 softmax over
`num_experts + zero_experts` outputs, the top k of score + the stored bias,
the weights the scores without it, not renormalised, times `routed_scale`.
`moe_dropless` then knows three classes: the experts HELD here (computed),
the other routed ones (the layer's other chips': exactly nothing here), and
the ZERO-COMPUTE outputs behind them, which return their input: `(the sum of
a token's weights on them) x y`, computed here in full. A token so does the
work of 0 to k real experts.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.decoding import KVCache, _write_stack
from ray_tpu.models.kimi_linear import mla_attention, mla_leaves, router
from ray_tpu.models.laguna import (
    EXPERT_LEAVES, _draw, _swiglu, _take, _tree,
)
from ray_tpu.models.transformer import (
    TransformerConfig, _rms_norm, moe_dropless, rows_gathered,
)

# The stored selection bias is seeded normal of this over the router's
# outputs: a tenth of a score's mean (1 / outputs). Kimi-Linear's 0.01 would
# be eight times a score here and choose the same k outputs for every token.
ROUTER_BIAS_OVER_MEAN = 0.1
EXPERT_ROWS = 1024  # rows of a prompt a call of the expert layer


# -- parameters --------------------------------------------------------------

def leaves(cfg: TransformerConfig) -> dict:
    """{(group, ..., name): (shape, init, logical axes)} of every parameter
    leaf. `init` is a fan-in (normal over its root), None (a norm's weight:
    ones) or "router_bias"."""
    h, n = cfg.hidden, cfg.layers
    out = {("embed",): ((cfg.vocab_size, h), h, ("vocab", "embed")),
           ("unembed",): ((h, cfg.vocab_size), h, ("embed", "vocab")),
           ("ln_f",): ((h,), None, ("norm",))}
    out.update(mla_leaves(cfg, (n, 2), ("layers", None)))
    m, at = cfg.dense_mlp_hidden, ("blocks", "dense")
    out[at + ("ln_mlp",)] = ((n, 2, h), None, ("layers", None, "norm"))
    out[at + ("wi_gate",)] = ((n, 2, h, m), h,
                              ("layers", None, "embed", "mlp"))
    out[at + ("wi_up",)] = ((n, 2, h, m), h, ("layers", None, "embed", "mlp"))
    out[at + ("wo_mlp",)] = ((n, 2, m, h), m,
                             ("layers", None, "mlp", "embed"))
    m, at = cfg.mlp_hidden, ("blocks", "sparse")
    held = cfg.experts_held[1] if cfg.experts_held else cfg.num_experts
    out[at + ("router",)] = ((n, h, cfg.router_outputs), h,
                             ("layers", "embed", None))
    out[at + ("router_bias",)] = ((n, cfg.router_outputs), "router_bias",
                                  ("layers", None))
    out[at + ("wi_gate",)] = ((n, held, h, m), h,
                              ("layers", "expert", "embed", "mlp"))
    out[at + ("wi_up",)] = ((n, held, h, m), h,
                            ("layers", "expert", "embed", "mlp"))
    out[at + ("wo_mlp",)] = ((n, held, m, h), m,
                             ("layers", "expert", "mlp", "embed"))
    return out


def num_params(cfg: TransformerConfig) -> int:
    """What is HELD here: `experts_held` experts a layer, not `num_experts`."""
    return sum(math.prod(shape) for shape, _, _ in leaves(cfg).values())


def param_axes(cfg: TransformerConfig) -> dict:
    return _tree({path: axes for path, (_, _, axes) in leaves(cfg).items()})


def init_params(cfg: TransformerConfig, key: jax.Array) -> dict:
    out = {}
    for i, (path, (shape, init, _)) in enumerate(leaves(cfg).items()):
        k = jax.random.fold_in(key, i)
        if init is None:
            out[path] = jnp.ones(shape, cfg.param_dtype)
        elif init == "router_bias":
            out[path] = (ROUTER_BIAS_OVER_MEAN / cfg.router_outputs
                         * jax.random.normal(k, shape, jnp.float32)
                         ).astype(cfg.param_dtype)
        else:
            out[path] = _draw(k, shape, init, cfg.param_dtype)
    return _tree(out)


# -- the double layer ------------------------------------------------------------

def expert_branch(cfg: TransformerConfig, y, p, row_mask, layer):
    """The shortcut's expert layer on the normed stream y [B, S, h]: `p` is
    double layer `layer`'s router and bias and the WHOLE expert stacks.
    Returns (m [B, S, h], load [router outputs] from the real rows, the
    outputs every row chose [B*S, k], how many of the experts held here the
    real rows reached, the most routed (not zero-compute) experts any real
    row chose, the rows that were gathered for the grouped matmuls:
    `transformer.rows_gathered`). A long prompt's rows go through
    `moe_dropless` `EXPERT_ROWS` at a time: the layout it falls back to is
    k gathered rows a row whatever is held (at 4,096 rows x 12 the three
    float32 [rows x k, h] arrays are 3.6 GB, where 4 GB are free)."""
    b, s, h = y.shape
    t, k = b * s, cfg.experts_per_token
    weights, experts = router(cfg, y.reshape(t, h), p)
    if t > EXPERT_ROWS and t % EXPERT_ROWS == 0:
        def some(_, xs):
            rows, real, w, e = xs
            out, load = moe_dropless(cfg, rows[None], p, real[None], layer,
                                     (w, e))
            return None, (out[0], load, rows_gathered(cfg, e))

        n = t // EXPERT_ROWS
        _, (m, load, gathered) = lax.scan(some, None, (
            y.reshape(n, EXPERT_ROWS, h), row_mask.reshape(n, EXPERT_ROWS),
            weights.reshape(n, EXPERT_ROWS, k),
            experts.reshape(n, EXPERT_ROWS, k)))
        m, load, gathered = m.reshape(b, s, h), load.sum(0), gathered.sum()
    else:
        m, load = moe_dropless(cfg, y, p, row_mask, layer, (weights, experts))
        gathered = rows_gathered(cfg, experts)
    first, count = cfg.experts_held or (0, cfg.num_experts)
    reached = (load[first:first + count] > 0).sum().astype(jnp.int32)
    routed = (experts < cfg.num_experts).sum(-1)  # [B*S]
    most = jnp.where(row_mask.reshape(-1), routed, 0).max().astype(jnp.int32)
    return m, load.astype(jnp.int32), experts, reached, most, gathered


def double_layer(cfg: TransformerConfig, x, mla, mlp, sparse, positions,
                 latent, kv_len_mask, row_mask, i, rows=None):
    """Double layer `i` on the stream x [B, S, h]: `mla` / `mlp` are PAIRS,
    its two attention sublayers' and its two dense MLPs' parameters (the
    second MLP's `ln_mlp` is `N_3`, the first's `N_1`), `sparse` its router
    and bias and the WHOLE expert stacks. Returns (x, latent, what
    `expert_branch` counted)."""
    def dense(x, y, p):
        with jax.named_scope("scmoe.dense"):
            return x + _swiglu(y, p["wi_gate"], p["wi_up"], p["wo_mlp"])

    a, latent = mla_attention(cfg, x, mla[0], positions, latent,
                              kv_len_mask, row_mask, 2 * i, rows)
    y = _rms_norm(a, mlp[0]["ln_mlp"], cfg.norm_eps)
    m, *counted = expert_branch(cfg, y, sparse, row_mask, i)
    b = dense(a, y, mlp[0])
    c, latent = mla_attention(cfg, b, mla[1], positions, latent,
                              kv_len_mask, row_mask, 2 * i + 1, rows)
    out = dense(c, _rms_norm(c, mlp[1]["ln_mlp"], cfg.norm_eps), mlp[1]) + m
    return out, latent, tuple(counted)


def sublayers(tree, i):
    """The pair of sublayers of double layer `i` (a traced index) out of a
    kind's parameters [layers, 2, ...]: each leaf of each sublayer ONE
    dynamic slice of the stack viewed as [layers x 2, ...], which a product
    reads where it lies. Sliced in two steps (the double layer, then the
    sublayer) the chip's compiler copied a double layer's [2, ...] part out
    of every stack in every decode step: 1.1 GB of dense and output
    matrices a layer, 13.5 of a step's 27.0 ms (my chip run, PR 44)."""
    flat = jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:]), tree)
    return [_take(flat, 2 * i + j) for j in (0, 1)]


def forward_cached(cfg: TransformerConfig, params, tokens, positions,
                   cache: KVCache, kv_len_mask, row_mask, access=_write_stack,
                   rows=None):
    """`decoding.forward_cached` for this pattern: the same arguments and
    results, the carry being the residual stream and the latent rows, written
    in place at [2 x double layer + sublayer]. `aux` as `laguna.
    forward_cached`'s ("expert_load" over the router's outputs, the
    zero-compute ones behind the routed; "expert_choice" [layers, B*S, k];
    "experts_reached"), then "routed_most": the most routed experts one real
    row chose in one layer, a row's largest share of real expert work, and
    "rows_gathered": the rows the expert layers gathered, over the layers."""
    if access is not _write_stack:
        raise ValueError(
            "a pattern of double layers keeps one latent row a position and "
            "sublayer: no other cache access (pages) holds it")
    blocks = params["blocks"]
    small = {n: a for n, a in blocks["sparse"].items()
             if n not in EXPERT_LEAVES}
    experts = {n: blocks["sparse"][n] for n in EXPERT_LEAVES}
    x = params["embed"].astype(cfg.dtype)[tokens]

    def one(carry, i):
        x, latent, counted = double_layer(
            cfg, carry[0], sublayers(blocks["mla"], i),
            sublayers(blocks["dense"], i), dict(_take(small, i), **experts),
            positions, carry[1], kv_len_mask, row_mask, i, rows)
        return (x, latent), counted

    (x, latent), (load, choice, reached, most, gathered) = lax.scan(
        one, (x, cache.latent), jnp.arange(cfg.layers))
    aux = {"expert_load": load.sum(0), "expert_choice": choice,
           "experts_reached": reached.sum(), "routed_most": most.max(),
           "rows_gathered": gathered.sum()}
    x = _rms_norm(x, params["ln_f"], cfg.norm_eps)
    with jax.named_scope("lm_head"):
        logits = jnp.einsum("bsh,hv->bsv", x,
                            params["unembed"].astype(x.dtype))
    return logits, cache._replace(latent=latent), aux
