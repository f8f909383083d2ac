"""A pattern of DOUBLE layers with the expert layer on a shortcut
(meituan-longcat/LongCat-Flash-Chat, `model_type` longcat_flash). Imported
only where a configuration has one (`families.PATTERNS`, kind "scmoe"); the
latent attention, the expert matmuls, the router, sampling, the scheduler
and the drawing of weights are the other models'
(`kimi_linear.mla_attention` / `router`, `transformer.moe_dropless`,
`pattern._draw`).

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


import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.decoding import KVCache, _write_stack, lm_head
from ray_tpu.models.families import Kept
from ray_tpu.models.kimi_linear import mla_attention, mla_leaves, router
from ray_tpu.models.pattern import (  # noqa: F401 (the family's three)
    EXPERT_LEAVES, _swiglu, _take, expert_leaves, init_params, num_params,
    only_the_stack, param_axes,
)
from ray_tpu.models.transformer import (
    TransformerConfig, _rms_norm, layout_counted, moe_dropless,
    rows_gathered,
)

# The stored selection bias is seeded normal of this over the router's
# outputs: a tenth of a score's mean (1 / outputs). Kimi-Linear's 0.01 would
# be eight times a score here and choose the same k outputs for every token.
ROUTER_BIAS_OVER_MEAN = 0.1
EXPERT_ROWS = 1024  # rows of a prompt a call of the expert layer

# -- the family (`families.py`) ---------------------------------------------------
FIELDS = frozenset({
    "layer_kinds", "lead_kind", "mla_latent", "mla_rope_dim", "mla_q_rank",
    "mla_rotate", "mla_scales", "zero_experts", "dense_mlp_hidden",
    "experts_held"})


def check(cfg: TransformerConfig) -> None:
    if cfg.layer_kinds != ("scmoe",) or cfg.lead_kind or cfg.layers < 1:
        raise ValueError(
            "a pattern of double layers is layer_kinds ('scmoe',) with "
            "lead_kind '': `layers` of them and nothing else")
    if not (cfg.mla_latent and cfg.mla_rope_dim and cfg.num_experts
            and cfg.dense_mlp_hidden) or cfg.mla_rope_dim % 2:
        raise ValueError("a double layer needs mla_latent, an even "
                         "mla_rope_dim, num_experts and dense_mlp_hidden")
    if cfg.zero_experts < 0:
        raise ValueError("a double layer's router is a softmax over "
                         "num_experts + zero_experts outputs")
    if cfg.kv_heads != cfg.heads:
        raise ValueError("a double layer's heads are all alike: kv_heads "
                         f"{cfg.kv_heads} is not heads {cfg.heads}")


def kept(cfg: TransformerConfig, max_len: int):
    """One latent row a position and SUBLAYER: two layers a double layer."""
    return (Kept(("latent",), 2 * cfg.layers, max_len, (cfg.latent_row,)),)


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
    at = ("blocks", "sparse")
    out[at + ("router",)] = ((n, h, cfg.router_outputs), h,
                             ("layers", "embed", None))
    out[at + ("router_bias",)] = ((n, cfg.router_outputs), "router_bias",
                                  ("layers", None))
    out.update(expert_leaves(cfg, n))
    return out


def special(cfg: TransformerConfig, key, shape, init: str):
    """The stored selection bias (`ROUTER_BIAS_OVER_MEAN`)."""
    return (ROUTER_BIAS_OVER_MEAN / cfg.router_outputs
            * jax.random.normal(key, shape, jnp.float32)
            ).astype(cfg.param_dtype)


# -- the double layer ------------------------------------------------------------

def expert_branch(cfg: TransformerConfig, y, p, row_mask, layer):
    """The shortcut's expert layer on the normed stream y [B, S, h]: `p` is
    double layer `layer`'s router and bias and the WHOLE expert stacks.
    Returns (m [B, S, h], load [router outputs] from the real rows, the
    outputs every row chose [B*S, k], `counted` as `pattern.sparse_mlp`'s:
    how many of the experts held here the real rows reached and, where a
    cap stands, the rows gathered and the calls that took the whole layout
    (`transformer.layout_counted`), the most routed (not zero-compute)
    experts any real row chose, the rows that were gathered for the grouped
    matmuls: `transformer.rows_gathered`). A long prompt's rows go through
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
            return None, (out[0], load, rows_gathered(cfg, e),
                          layout_counted(cfg, e))

        n = t // EXPERT_ROWS
        _, (m, load, gathered, layout) = lax.scan(some, None, (
            y.reshape(n, EXPERT_ROWS, h), row_mask.reshape(n, EXPERT_ROWS),
            weights.reshape(n, EXPERT_ROWS, k),
            experts.reshape(n, EXPERT_ROWS, k)))
        m, load, gathered = m.reshape(b, s, h), load.sum(0), gathered.sum()
        layout = layout if layout is None else layout.sum(0)
    else:
        m, load = moe_dropless(cfg, y, p, row_mask, layer, (weights, experts))
        gathered = rows_gathered(cfg, experts)
        layout = layout_counted(cfg, experts)
    first, count = cfg.experts_held or (0, cfg.num_experts)
    reached = (load[first:first + count] > 0).sum().astype(jnp.int32)
    routed = (experts < cfg.num_experts).sum(-1)  # [B*S]
    most = jnp.where(row_mask.reshape(-1), routed, 0).max().astype(jnp.int32)
    counted = reached if layout is None \
        else jnp.concatenate([reached[None], layout])
    return m, load.astype(jnp.int32), experts, counted, most, gathered


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
    in place at [2 x double layer + sublayer]. `aux` as `pattern.
    forward_cached`'s ("expert_load" over the router's outputs, the
    zero-compute ones behind the routed; "expert_choice" [layers, B*S, k];
    "experts_counted"), then "routed_most": the most routed experts one real
    row chose in one layer, a row's largest share of real expert work, and
    "rows_gathered": the rows the expert layers gathered, over the layers
    (the program's last output, where `benchmarks/runners/serve_longcat.py`
    counts five from the end; the engine reads `experts_counted`)."""
    only_the_stack(cfg, access)
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
           "experts_counted": reached.sum(0), "routed_most": most.max(),
           "rows_gathered": gathered.sum()}
    return lm_head(cfg, params, x), cache._replace(latent=latent), aux
