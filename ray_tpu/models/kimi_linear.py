"""A pattern of delta-rule linear-attention layers beside full-attention
layers of one of two kinds, latent rows or grouped K/V rows
(moonshotai/Kimi-Linear-48B-A3B-Instruct, `model_type` kimi_linear;
upstage/Solar-Open2-250B, `model_type` solar_open2; the recurrence is
arXiv:2510.26692). Imported only where a configuration has one
(`families.PATTERNS`); the expert matmuls, the cache's slots and the grouped
attention over them, sampling, the scheduler and the drawing of weights are
the other models' (`transformer.moe_dropless`, `decoding._write_stack` /
`attend_held`, `pattern._draw`).

**Layers.** Three kinds, "kda", "mla" and "gkv", in two forms of pattern
(`check`). With a LEAD (`lead_kind` "kda", Kimi-Linear): a leading "kda"
layer with a dense SwiGLU MLP, whole periods of `layer_kinds` (kda, kda, mla,
kda) and the trailing layers `tail_kinds` (kda, mla), every layer behind the
first with sparse experts. WITHOUT one (`lead_kind` "", Solar-Open2): whole
periods of `layer_kinds` (gkv, kda, kda, kda) and nothing before or behind
them, EVERY layer with sparse experts. Parameters are stacked BY KIND
(`blocks["kda" | "mla" | "gkv" | "sparse"]`, `blocks["dense"]` the leading
MLP alone); `pattern.forward_cached` runs `layer` here as the leading layer,
if any, ONE `lax.scan` over the periods, then the trailing layers. With y the
RMS-normed stream:

**A "kda" layer** (`heads` heads of `hd`, keys and values alike): `q~, k~,
v~ = y Wq, y Wk, y Wv`; a causal depthwise convolution of `kda_conv` taps and
SiLU on each; `q = l2norm(c_q) / sqrt(hd)`, `k = l2norm(c_k)`, `v = c_v`; a
decay by CHANNEL `a = exp(-exp(A_log) softplus((y Wfa) Wfb + dt_bias))`, a
step `beta = sigmoid(y Wb)` a head (`kda_neg_eigval`: `2 sigmoid(y Wb)`, so
that `I - beta k k^T` has the eigenvalue `1 - beta` in (-1, 1) along k and a
state can change its sign); the state S [keys, values] of a head,
float32, zero at a sequence's start:
`S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T`,
`o_t = S_t^T q_t`; `o <- RMSNorm_head(o) * sigmoid((y Wga) Wgb)`, then `Wo`.
A sequence keeps S (`KVCache.mat`) and the last `kda_conv - 1` inputs of the
three convolutions (`KVCache.conv`). A decode step (S == 1) is one update of
S, every product into it exact in float32 (on a TPU `ops.delta_rule.
state_update`: the stack read once and written once, in place). A call with S > 1 is a prefill
FROM POSITION 0 (every engine's) and runs the recurrence a CHUNK at a time
(`kda_chunks`: on a TPU the kernel `ops.delta_rule.chunk_scan`, a block of
heads' states in fast memory across the chunks); the state and the window it
leaves are those at each sequence's TRUE last position (`row_mask`): a pad
position has beta 0 and decay 1.

**An "mla" layer** (`heads` heads; here no rotation is applied: the
`mla_rope_dim` "rope" dimensions are one key part shared by all heads): `[q_n
; q_r]_i = y Wq_i`; `[c~ ; k_r] = y Wkva`, `c = RMSNorm(c~)`; a sequence keeps
`[c ; k_r]` a position (`KVCache.latent`, in whole lanes: `cfg.latent_row`).
Two programs for one mathematics: a prefill EXPANDS `[k_n ; v]_i = c Wkvb_i`
and attends over heads of `hd + mla_rope_dim`; a decode step ABSORBS, `q'_i =
Wkvb_i[:, :hd] q_n_i`, scores `(q'_i . c_j + q_r_i . k_r_j) / sqrt(hd +
mla_rope_dim)` against the held rows, `u_i = sum_j p_j c_j`, `o_i = u_i
Wkvb_i[:, hd:]`: each held row read once, where it lies
(`ops.attention.latent_decode_attention` on a TPU). `mla_attention` also runs
the sublayer as DeepSeek-V3's family publishes it, by three fields that are
off in this model (`models/longcat.py` sets them): a low-rank query
(`mla_q_rank`), `q_r` and the shared `k_r` rotated by position
(`mla_rotate`), two factors (`mla_scales`). A prefill's expanded rows go to
the flash forward kernel where it takes their shape (`decoding.
attend_fresh`: a head's `[q_n ; q_r]` against `[k_n ; k_r]` in whole lanes,
values of `hd`; on a TPU, past `ops.attention.DENSE_SCORES_BYTES`), no
logits through HBM; elsewhere `_attend_expanded`, which past
`PREFILL_LOGITS_MAX` of float32 logits [heads, S, S] attends a block of
queries at a time.

**A "gkv" layer** (`heads` query heads in groups of `heads / kv_heads` that
adjoin, group g reading K/V head g): `q = y Wq`, `k, v = y Wk, y Wv`
(`kv_heads` of `hd`), NO rotation and no other position signal, causal
softmax of `q k^T / sqrt(hd)`; `gqa_gate`: `o <- o * sigmoid(y Wg)`,
elementwise, `Wg` a matrix of `wq`'s shape, from the sublayer's own normed
input; then `Wo`. Rows in `KVCache.k` / `.v` beside the "kda" layers' states
(`_write_stack`, `attend_held`: a decode step reads the rows a slot holds
with `ops.attention.decode_attention` on a TPU, a prefill attends its fresh
rows with the flash forward where `attend_fresh` takes them).

**Experts.** `router`: sigmoid scores over all `num_experts` in float32, the
top k of score + a stored bias, the weights the scores alone, renormalised,
times `routed_scale`; then `pattern.sparse_mlp`: `moe_dropless` with
`experts_held`, and the shared expert. What the absent experts would add is left out here, and nothing
stands in for the other chips or for their exchange.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.decoding import (
    FreshRows, _write_stack, attend_fresh, attend_held,
)
from ray_tpu.models.families import Kept
from ray_tpu.models.pattern import (  # noqa: F401 (the family's three)
    _swiglu, _take, init_params, mlp_leaves, num_params, param_axes,
    sparse_mlp,
)
from ray_tpu.models.transformer import TransformerConfig, _rms_norm
from ray_tpu.ops import attention as attention_ops
from ray_tpu.ops import delta_rule
from ray_tpu.ops.attention import NEG_INF

KDA_CHUNK = 32  # positions a chunk of the prefill's scan (`kda_chunks`)
# The rule of the shapes the flash forward does not take (off the chip,
# heads or a length not in whole 128s, more keys than its VMEM holds:
# `ops.attention.flash_attention_takes`; both cells' buckets go to it since
# PR 61). There a latent prefill's float32 logits [heads, S, S] up to this
# many bytes are one array; past it the queries go `PREFILL_QUERY_BLOCK` at a
# time (`_attend_expanded`). 32 heads x 2,048^2 are 0.5 GiB, 64 x 4,096^2 4.
PREFILL_LOGITS_MAX = 1 << 30
PREFILL_QUERY_BLOCK = 512
F32 = jnp.float32
HI = lax.Precision.HIGHEST

# -- the family (`families.py`) ---------------------------------------------------
FIELDS = frozenset({
    "layer_kinds", "lead_kind", "tail_kinds", "kda_conv", "mla_latent",
    "mla_rope_dim", "mla_q_rank", "mla_rotate", "mla_scales", "router_score",
    "dense_mlp_hidden", "shared_expert_hidden", "experts_held", "gqa_gate",
    "kda_neg_eigval"})


def check(cfg: TransformerConfig) -> None:
    """The two forms. With a lead: one leading layer with the dense MLP
    (`dense_mlp_hidden`), whole periods, the tail. Without (`lead_kind` ""):
    whole periods alone, every layer sparse, so no `tail_kinds` and no
    `dense_mlp_hidden`. Then what each kind that has a layer needs."""
    body = cfg.layers - bool(cfg.lead_kind) - len(cfg.tail_kinds)
    if cfg.lead_kind:
        if body < 0 or body % len(cfg.layer_kinds):
            raise ValueError(
                f"layers {cfg.layers} is not one leading {cfg.lead_kind!r} "
                f"layer, whole periods of {cfg.layer_kinds!r} and the "
                f"trailing layers {cfg.tail_kinds!r}")
        if not cfg.dense_mlp_hidden:
            raise ValueError(
                f"the leading {cfg.lead_kind!r} layer has the dense MLP: "
                "dense_mlp_hidden is its width (lead_kind '' for a pattern "
                "whose every layer routes)")
    else:
        if cfg.tail_kinds or body <= 0 or body % len(cfg.layer_kinds):
            raise ValueError(
                f"without a leading layer (lead_kind '') layers "
                f"{cfg.layers} is whole periods of layer_kinds "
                f"{cfg.layer_kinds!r} and nothing behind them (tail_kinds "
                f"{cfg.tail_kinds!r})")
        if cfg.dense_mlp_hidden:
            raise ValueError(
                f"dense_mlp_hidden {cfg.dense_mlp_hidden} is the leading "
                "layer's dense MLP, and lead_kind '' states no leading "
                "layer: every layer routes")
    if not cfg.num_experts:
        raise ValueError("a pattern of kda, mla and gkv layers routes behind "
                         "every layer but a leading one: num_experts")
    if cfg.layers_of("kda") and cfg.kda_conv < 2:
        raise ValueError(f"a kda layer convolves its inputs: kda_conv "
                         f"{cfg.kda_conv} is its taps, >= 2")
    if cfg.layers_of("mla") and not (cfg.mla_latent and cfg.mla_rope_dim):
        raise ValueError("an mla layer keeps mla_latent + mla_rope_dim "
                         f"values a position: they are {cfg.mla_latent} and "
                         f"{cfg.mla_rope_dim}")
    if cfg.layers_of("gkv"):
        if cfg.heads % cfg.kv_heads:
            raise ValueError(
                f"a gkv layer's {cfg.heads} query heads are whole groups of "
                f"kv_heads {cfg.kv_heads}")
    else:
        if cfg.kv_heads != cfg.heads:
            raise ValueError(
                "a pattern of kda and mla layers has heads that are all "
                f"alike: kv_heads {cfg.kv_heads} is not heads {cfg.heads} "
                "(a gkv layer alone reads kv_heads)")
        if cfg.gqa_gate:
            raise ValueError("gqa_gate is a gkv layer's output gate, and no "
                             "layer of this pattern is one")
    if cfg.kda_neg_eigval and not cfg.layers_of("kda"):
        raise ValueError("kda_neg_eigval doubles a kda layer's step, and no "
                         "layer of this pattern is one")


def kept(cfg: TransformerConfig, max_len: int):
    """A "gkv" layer's K/V rows of `kv_heads` heads in slots of `max_len`.
    A "kda" layer's matrix states, float32 whatever the stream's dtype,
    and its convolutions' windows, the `kda_conv - 1` last inputs of q, k
    and v flat in one row a sequence (positions, then q | k | v, heads,
    head_dim): a slot is one row of whole lanes, where [taps - 1, 3, heads,
    D] a slot made the chip's compiler transpose the stack in and out of
    every step. An "mla" layer's one latent row a position."""
    kda, nh, d = cfg.layers_of("kda"), cfg.heads, cfg.hd
    return (Kept(("k", "v"), cfg.layers_of("gkv"), max_len,
                 (cfg.kv_heads, d)),
            Kept(("mat",), kda, None, (nh, d, d), F32),
            Kept(("conv",), kda, None, ((cfg.kda_conv - 1) * 3 * nh * d,)),
            Kept(("latent",), cfg.layers_of("mla"), max_len,
                 (cfg.latent_row,)))


# -- parameters --------------------------------------------------------------

def leaves(cfg: TransformerConfig) -> dict:
    """{(group, ..., name): (shape, init, logical axes)} of every parameter
    leaf. `init` is a fan-in (normal over its root), None (a norm's weight:
    ones), or the name of one of the family's initialisers (`special`)."""
    h, d, nh, taps = cfg.hidden, cfg.hd, cfg.heads, cfg.kda_conv
    out = {("embed",): ((cfg.vocab_size, h), h, ("vocab", "embed")),
           ("unembed",): ((h, cfg.vocab_size), h, ("embed", "vocab")),
           ("ln_f",): ((h,), None, ("norm",))}
    n, at = cfg.layers_of("kda"), ("blocks", "kda")
    heads = ("layers", "embed", "heads")  # [in, heads * D]: `_to_heads`
    out[at + ("ln_attn",)] = ((n, h), None, ("layers", "norm"))
    for name in ("wq", "wk", "wv"):
        out[at + (name,)] = ((n, h, nh * d), h, heads)
        out[at + ("conv_" + name[1],)] = (
            (n, taps, nh, d), "taps", ("layers", None, "heads", "head_dim"))
    for name in ("w_fa", "w_ga"):  # the low-rank decay and output gates
        out[at + (name,)] = ((n, h, d), h, ("layers", "embed", None))
    for name in ("w_fb", "w_gb"):
        out[at + (name,)] = ((n, d, nh * d), d, ("layers", None, "heads"))
    out[at + ("a_log",)] = ((n, nh), "a_log", ("layers", "heads"))
    out[at + ("dt_bias",)] = ((n, nh, d), "dt_bias",
                              ("layers", "heads", "head_dim"))
    out[at + ("w_b",)] = ((n, h, nh), h, ("layers", "embed", "heads"))
    out[at + ("o_norm",)] = ((n, d), None, ("layers", "norm"))
    out[at + ("wo",)] = ((n, nh, d, h), nh * d,
                         ("layers", "heads", "head_dim", "embed"))
    if cfg.layers_of("mla"):
        out.update(mla_leaves(cfg, (cfg.layers_of("mla"),), ("layers",)))
    n, at = cfg.layers_of("gkv"), ("blocks", "gkv")
    if n:  # plain matrices [in, heads * D], as the projections above
        wide = ((n, h, nh * d), h, heads)
        narrow = ((n, h, cfg.kv_heads * d), h,
                  ("layers", "embed", "kv_heads"))
        out[at + ("ln_attn",)] = ((n, h), None, ("layers", "norm"))
        out.update({at + ("wq",): wide, at + ("wk",): narrow,
                    at + ("wv",): narrow})
        if cfg.gqa_gate:
            out[at + ("wg",)] = wide
        out[at + ("wo",)] = ((n, nh * d, h), nh * d,
                             ("layers", "heads", "embed"))
    out.update(mlp_leaves(cfg))
    return out


def mla_leaves(cfg: TransformerConfig, lead: tuple, axes: tuple) -> dict:
    """`leaves`' entries of the latent-attention sublayers `blocks["mla"]`,
    stacked over the leading axes `lead` (named `axes`): `wq` whole, or with
    `mla_q_rank` its two factors and the norm between them. The two matrices
    whose INPUT `mla_scales` multiplies (`wq` behind the query's norm,
    `wkv_b`) are drawn over the root of their fan-in times that factor: the
    published factors are sqrt(hidden / rank), what gives a query, a key and
    a value the variance of a full-rank projection when the matrix behind
    the low rank is drawn over the root of `hidden`. Drawn over the root of
    the rank alone the factors make attention logits of standard deviation
    6, a softmax that is one key on seeded weights, and one bfloat16
    rounding of a near-tie then moves the logits by half their spread (0.53
    - 0.62 of it at the published widths on the chip, PERF.md PR 44)."""
    h, d, nh = cfg.hidden, cfg.hd, cfg.heads
    lat, rope, rank = cfg.mla_latent, cfg.mla_rope_dim, cfg.mla_q_rank
    scale_q, scale_kv = cfg.mla_scales
    at = ("blocks", "mla")
    out = {at + ("ln_attn",): ((*lead, h), None, (*axes, "norm"))}
    if rank:
        out[at + ("wq_a",)] = ((*lead, h, rank), h, (*axes, "embed", None))
        out[at + ("q_norm",)] = ((*lead, rank), None, (*axes, "norm"))
    out[at + ("wq",)] = ((*lead, rank or h, nh * (d + rope)),
                         round((rank or h) * scale_q ** 2),
                         (*axes, None if rank else "embed", "heads"))
    out[at + ("wkv_a",)] = ((*lead, h, lat + rope), h, (*axes, "embed", None))
    out[at + ("kv_norm",)] = ((*lead, lat), None, (*axes, "norm"))
    out[at + ("wkv_b",)] = ((*lead, lat, nh, 2 * d),
                            round(lat * scale_kv ** 2),
                            (*axes, None, "heads", "head_dim"))
    out[at + ("wo",)] = ((*lead, nh, d, h), nh * d,
                         (*axes, "heads", "head_dim", "embed"))
    return out


def special(cfg: TransformerConfig, key, shape, init: str):
    """The leaves a normal draw would leave degenerate, by the family's
    initialisers (the linear-attention library's, as remembered): the taps
    uniform in +-1/sqrt(taps); `a_log` the log of a rate uniform in [1, 16);
    `dt_bias` the inverse softplus of a step log-uniform in [0.001, 0.1);
    the selection bias normal of 0.01 about zero (a trained one is stored;
    zeros would leave it unexercised). Decays then lie strictly between 0
    and 1."""
    if init == "taps":
        bound = 1 / math.sqrt(shape[1])
        out = jax.random.uniform(key, shape, F32, -bound, bound)
    elif init == "a_log":
        out = jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))
    elif init == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, F32, math.log(1e-3),
                                        math.log(1e-1)))
        out = dt + jnp.log(-jnp.expm1(-dt))
    elif init == "router_bias":
        out = 0.01 * jax.random.normal(key, shape, F32)
    else:
        raise ValueError(f"unknown initialiser {init!r}")
    return out.astype(cfg.param_dtype)


# -- the linear-attention layer -------------------------------------------------

def _to_heads(y, w, heads: int, out=None):
    """y [B, S, in] times w [in, heads * D] -> [B, S, heads, D]. The
    projections into heads are kept as plain matrices [in, heads * D]: as
    [in, heads, D] the chip tiles them over (heads, D), another order than a
    product over `in` reads, and its compiler copied them every decode step,
    whole stacks hoisted out of the layer loop or a layer's matrix before its
    product (1.1 GB a step; `benchmarks/rehearse_kimi_linear.py --text`, PR
    38)."""
    flat = jnp.einsum("bsi,im->bsm", y, w.astype(y.dtype),
                      preferred_element_type=out)
    return flat.reshape(*y.shape[:2], heads, -1)


def _from_heads(o, w):
    """o [B, S, heads, D] times w [heads, D, out] -> [B, S, out]."""
    return jnp.einsum("bsnd,ndh->bsh", o, w.astype(o.dtype))


def _l2norm(x):
    return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def kda_step(state, q, k, v, log_a, beta):
    """One position of the recurrence for every sequence and head: state
    [B, H, K, V] float32; q, k, v, log_a [B, H, D] float32; beta [B, H].
    Returns (state, o [B, H, V]). Elementwise products and sums, so that
    every product into the state is exact in float32 (a float32 matmul at
    the TPU's default precision rounds its operands to bfloat16)."""
    state = state * jnp.exp(log_a)[..., None]
    u = beta[..., None] * (v - (k[..., None] * state).sum(-2))
    state = state + k[..., None] * u[..., None, :]
    return state, (q[..., None] * state).sum(-2)


def kda_chunks(state, q, k, v, log_a, beta, chunk: int = None):
    """The recurrence over S positions a chunk at a time: state [B, H, K, V]
    float32 entering; q, k, v, log_a [B, S, H, D] float32; beta [B, S, H].
    Returns (state after position S - 1, o [B, S, H, V]).

    Two spellings of the one algebra below, chosen by what the call shows
    (`delta_rule.chunk_scan_takes`: on a TPU, float32, keys and values in
    whole lanes, at least one chunk of positions): the kernel `ops.
    delta_rule.chunk_scan`, the heads an extent of its grid (32 and 64 are
    the same code); else the scan of XLA operations here, which is the CPU's
    path, the toy configurations' and the tests' second reference. The pick
    is booked (`traced.TOLD["delta_rule"]`: `engine_stats()["kda_path"]`
    says "scan:kernel" or "scan:plain" by program). `chunk`: positions a
    chunk, by default the spelling's own (`KDA_CHUNK`, `delta_rule.
    SCAN_CHUNK`).

    Exact, derived from the recurrence. With g_t the running sum of log_a
    inside a chunk (G_t = exp(g_t)) and S_0 the state entering it, `S_t =
    Diag(a_t) S_{t-1} + k_t u_t^T` with `u_t = beta_t (v_t - S_0^T (G_t *
    k_t) - sum_{s<t} u_s A_kk[t, s])`, `A_kk[t, s] = sum_d k_t[d] k_s[d] G_t[d]
    / G_s[d]`: a unit lower-triangular system of the chunk's size for U; then
    `o_t = S_0^T (G_t * q_t) + sum_{s<=t} u_s A_qk[t, s]` and `S_C = Diag(G_C)
    S_0 + sum_s (G_C / G_s * k_s) u_s^T`. Every ratio G_t / G_s is formed as
    exp(g_t - g_s) with s <= t, at most 1: `1 / G_s` alone overflows where a
    channel decays fast (the seeded rates reach e^-13 a position). The
    products with the state run at the highest precision.

    The plain spelling's chunks of 32: on the v5e a layer's 2,048 positions
    at 32 heads take 5.0 ms at 32, 7.6 at 64 and 12.3 at 128 (the [C, C, D]
    ratios are elementwise work, the scan's steps about 40 us each; at 64
    heads about 120 us, 31.6 ms a layer of 8,192 positions: PERF.md, PR 67).
    XLA spellings tried and slower or no faster (my chip runs, PR 38): the
    ratios in sub-chunks of 16 with matmuls between them (5.0 at 32), the
    system inverted by halves in place of the triangular solve (5.9), and
    everything that does not depend on the state computed for all chunks at
    once ahead of a scan of four matmuls (7.1): each kept a step a list of
    XLA operations with the state and the [C, C] matrices through HBM
    between them. The kernel (PR 68: chunks of 64 in sub-chunks of 16, four
    heads a grid step; my chip runs, PR 68): the call alone 1.1 ms a layer
    of 2,048 positions at 32 heads and 7.8 of 8,192 at 64; under this scope
    in the cells' prefill programs 1.2 (for 3.9) and 10.3 (for 31.6)."""
    kernel = delta_rule.chunk_scan_takes(state, q)
    delta_rule.book("scan", kernel)
    if kernel:
        return delta_rule.chunk_scan(state, q, k, v, log_a, beta, chunk)
    b, s, h, d = q.shape
    chunk = min(chunk or KDA_CHUNK, s)
    pad = -s % chunk
    if pad:  # beta 0 and decay 1: the state passes a pad position unchanged
        q, k, v, log_a = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                          for a in (q, k, v, log_a))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    n = (s + pad) // chunk

    def by_chunk(a):  # [B, S, H, ...] -> [n, B, H, C, ...]
        a = a.reshape(b, n, chunk, *a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 1, 0), 2, 3)

    t = jnp.arange(chunk)
    below, upto = t[:, None] > t[None, :], t[:, None] >= t[None, :]
    eye = jnp.eye(chunk, dtype=F32)

    def one(state, xs):
        q, k, v, log_a, beta = xs  # [B, H, C, D], beta [B, H, C]
        g = jnp.cumsum(log_a, axis=2)
        # exp(g_t - g_s) for s <= t, 0 above the diagonal: [B, H, C, C, D]
        ratio = jnp.exp(jnp.where(
            upto[..., None], g[:, :, :, None] - g[:, :, None, :], -jnp.inf))
        a_kk = (k[:, :, :, None] * k[:, :, None, :] * ratio).sum(-1)
        a_qk = (q[:, :, :, None] * k[:, :, None, :] * ratio).sum(-1)
        decayed = jnp.exp(g)
        rhs = beta[..., None] * (v - jnp.einsum(
            "bhck,bhkv->bhcv", decayed * k, state, precision=HI))
        system = eye + beta[..., None] * jnp.where(below, a_kk, 0.0)
        u = jax.scipy.linalg.solve_triangular(
            system, rhs, lower=True, unit_diagonal=True)
        o = jnp.einsum("bhck,bhkv->bhcv", decayed * q, state, precision=HI) \
            + jnp.einsum("bhcs,bhsv->bhcv", a_qk, u, precision=HI)
        to_end = jnp.exp(g[:, :, -1:] - g)  # G_C / G_s
        state = decayed[:, :, -1, :, None] * state + jnp.einsum(
            "bhsk,bhsv->bhkv", to_end * k, u, precision=HI)
        return state, o

    state, o = lax.scan(one, state, tuple(
        by_chunk(a) for a in (q, k, v, log_a, beta)))
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1)  # [B, n, C, H, V]
    return state, o.reshape(b, n * chunk, h, d)[:, :s]


def kda_attention(cfg: TransformerConfig, x, p, mat, conv, row_mask, layer):
    """The attention half of "kda" layer `layer` (its index within its
    kind): `mat` / `conv` are the stacks, read and rewritten at `layer` in
    place. Returns (x, mat, conv)."""
    b, s, _ = x.shape
    nh, d, taps = cfg.heads, cfg.hd, cfg.kda_conv
    y = _rms_norm(x, p["ln_attn"], cfg.norm_eps)
    real = row_mask.astype(F32)  # [B, S]
    n_real = row_mask.sum(1).astype(jnp.int32)
    with jax.named_scope("kda.project"):
        # the three projections' channels side by side, flat: [B, S, 3 H D]
        z = jnp.concatenate([jnp.einsum(
            "bsh,hm->bsm", y, p[w].astype(y.dtype))
            for w in ("wq", "wk", "wv")], axis=-1)
    with jax.named_scope("kda.conv"):
        before = lax.dynamic_index_in_dim(conv, layer, keepdims=False)
        seen = jnp.concatenate(
            [before.reshape(b, taps - 1, -1).astype(z.dtype), z], axis=1)
        w = jnp.concatenate([p[c].reshape(taps, -1) for c in (
            "conv_q", "conv_k", "conv_v")], axis=-1).astype(F32)
        c = jax.nn.silu(sum(w[j] * seen[:, j:j + s].astype(F32)
                            for j in range(taps)))
        # the window after this call: the inputs of each sequence's last
        # `taps - 1` real positions; what it was for a row that has none
        at = n_real[:, None] + jnp.arange(taps - 1)  # into `seen`
        window = jnp.take_along_axis(seen, at[:, :, None], axis=1)
        conv = lax.dynamic_update_index_in_dim(
            conv, window.reshape(b, -1).astype(conv.dtype), layer, 0)
        c = c.reshape(b, s, 3, nh, d)
        q = _l2norm(c[:, :, 0]) * d ** -0.5
        k, v = _l2norm(c[:, :, 1]), c[:, :, 2]
    with jax.named_scope("kda.gate"):
        f = jnp.einsum("bsh,hr->bsr", y, p["w_fa"].astype(y.dtype))
        f = _to_heads(f, p["w_fb"], nh, F32)
        log_a = -jnp.exp(p["a_log"].astype(F32))[:, None] * jax.nn.softplus(
            f + p["dt_bias"].astype(F32))
        beta = jax.nn.sigmoid(jnp.einsum(
            "bsh,hn->bsn", y, p["w_b"].astype(y.dtype),
            preferred_element_type=F32))
        if cfg.kda_neg_eigval:  # steps in (0, 2): eigenvalues in (-1, 1)
            beta = 2.0 * beta
        # a position that is no sequence's leaves the state as it is
        log_a = log_a * real[:, :, None, None]
        beta = beta * real[:, :, None]
        gate = jnp.einsum("bsh,hr->bsr", y, p["w_ga"].astype(y.dtype))
        gate = jax.nn.sigmoid(_to_heads(gate, p["w_gb"], nh, F32))
    # the state's read and write are the sublayer's, under its scope
    with jax.named_scope("kda.state" if s == 1 else "kda.prefill_scan"):
        kernel = s == 1 and delta_rule.state_update_takes(mat)
        if s == 1:
            delta_rule.book("state", kernel)
        if kernel:
            mat, o = delta_rule.state_update(  # read once, written once
                mat, layer, q[:, 0], k[:, 0], v[:, 0], log_a[:, 0],
                beta[:, 0])
            o = o[:, None]
        else:
            before = lax.dynamic_index_in_dim(mat, layer, keepdims=False)
            if s == 1:
                after, o = kda_step(before, q[:, 0], k[:, 0], v[:, 0],
                                    log_a[:, 0], beta[:, 0])
                o = o[:, None]
            else:
                after, o = kda_chunks(before, q, k, v, log_a, beta)
            mat = lax.dynamic_update_index_in_dim(mat, after, layer, 0)
    with jax.named_scope("kda.out"):
        o = _rms_norm(o, p["o_norm"].astype(F32), cfg.norm_eps) * gate
        out = _from_heads(o.astype(x.dtype), p["wo"])
    return x + out, mat, conv


# -- the latent-attention layer ----------------------------------------------------

def latent_attend(q, latent, layer, rows, kv_len_mask, value_dim: int,
                  sm_scale: float):
    """A decode step's absorbed attention: q [B, H, latent_row] against the
    rows of layer `layer` of the stack `latent` [N, B, T, latent_row]; the
    first `value_dim` of a row are its value too. `rows` [B]: how
    many each slot holds (0: it takes no part). On a TPU the kernel that
    reads those rows where they lie, once; elsewhere the same mathematics
    over the layer under `kv_len_mask` [B, T]. Returns [B, H, value_dim]
    float32."""
    if rows is not None and attention_ops.latent_decode_attention_takes(
            latent, value_dim):
        return attention_ops.latent_decode_attention(
            q, latent, layer, rows, value_dim, sm_scale)
    held = lax.dynamic_index_in_dim(latent, layer, keepdims=False)
    logits = jnp.einsum("bhc,btc->bht", q.astype(held.dtype), held,
                        preferred_element_type=F32) * sm_scale
    mask = kv_len_mask if rows is None else (
        jnp.arange(held.shape[1])[None] < rows[:, None])
    probs = jax.nn.softmax(jnp.where(mask[:, None], logits, NEG_INF), -1)
    probs = jnp.where(mask.any(-1)[:, None, None], probs, 0.0)
    return jnp.einsum("bht,btc->bhc", probs.astype(held.dtype),
                      held[..., :value_dim], preferred_element_type=F32)


def rotate_interleaved(x, positions, theta: float):
    """x [B, S, ..., R] with its last axis rotated by position, the pairs
    INTERLEAVED (dimensions 2i and 2i + 1 turn together by `positions *
    theta ** (-2i / R)`: DeepSeek-V3's layout, where `transformer._rope`
    pairs i with i + R / 2). Float32 inside, x's dtype out."""
    r = x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=F32) / r)
    ang = positions.astype(F32)[..., None] * inv  # [B, S, R / 2]
    ang = ang.reshape(*ang.shape[:2], *(1,) * (x.ndim - 3), r // 2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.astype(F32).reshape(*x.shape[:-1], r // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(
        x.shape).astype(x.dtype)


def _attend_expanded(q_n, q_r, k_n, k_r, v, positions, row_mask, sm_scale,
                     block=None):
    """A prefill's causal attention over its own fresh rows, expanded, where
    the flash forward does not take them (`mla_attention`): q_n
    [B, S, H, D] and q_r [B, S, H, R] against k_n [B, S, H, D] and the one
    shared k_r [B, S, R], values v [B, S, H, Dv]; float32 logits and softmax.
    `block`: queries go that many at a time, each block against the keys up
    to its own end (a prefill starts at position 0 and its positions ascend,
    so later keys are masked for every query of the block): the largest
    logits array is [H, block, S], not [H, S, S], and what lies wholly above
    the diagonal is never computed. A row's result is the unblocked one's."""
    s = q_n.shape[1]

    def attend(lo, hi):
        logits = jnp.einsum("bsnd,btnd->bnst", q_n[:, lo:hi], k_n[:, :hi],
                            preferred_element_type=F32)
        logits = (logits + jnp.einsum(
            "bsnr,btr->bnst", q_r[:, lo:hi], k_r[:, :hi],
            preferred_element_type=F32)) * sm_scale
        seen = (positions[:, lo:hi, None] >= positions[:, None, :hi]) \
            & row_mask[:, None, :hi]
        probs = jax.nn.softmax(
            jnp.where(seen[:, None], logits, NEG_INF), axis=-1)
        return jnp.einsum("bnst,btnd->bsnd", probs.astype(v.dtype),
                          v[:, :hi])

    if not block or block >= s:
        return attend(0, s)
    return jnp.concatenate([attend(lo, min(lo + block, s))
                            for lo in range(0, s, block)], axis=1)


def mla_attention(cfg: TransformerConfig, x, p, positions, latent,
                  kv_len_mask, row_mask, layer, rows=None):
    """The attention half of latent-attention sublayer `layer` (its index
    in `KVCache.latent`): `latent` is the stack, this call's rows written at
    [layer, sequence, position] in place. Returns (x, latent).

    By the configuration's fields: `mla_q_rank` (the query through `wq_a`,
    an RMSNorm and `wq`, else through `wq` alone); `mla_rotate` (`q_r` and
    the one shared `k_r` rotated by position under `mla.rotate`, the key
    BEFORE it is cached, so a cached row is `[c ; rot(k_r)]` and a decode
    step rotates its own query alone); `mla_scales` = (on the query: folded
    into the softmax's scale, which both of its parts share; on the normed
    latent: applied in float32 before the row is rounded, so on the keys'
    unrotated part and on the values, not on `k_r`).

    A prefill from position 0 attends its own fresh rows, expanded, with the
    flash forward kernel where `decoding.attend_fresh` takes them (by the
    call's shape, no option and no model's name: LongCat's 4,096 bucket and
    this model's 2,048 on the chip; the causal rule is the whole mask there,
    for `attend_held`'s reasons: a real query sees real keys alone, a pad
    row's result is nobody's), and with `_attend_expanded` elsewhere,
    `PREFILL_QUERY_BLOCK` queries at a time where the float32 logits [heads,
    S, S] would pass `PREFILL_LOGITS_MAX`. Both multiply bfloat16 operands
    into float32 scores, take the softmax in float32 and cast the
    probabilities to the values' dtype before the weighted sum; which one a
    program was traced with is booked (`engine_stats()
    ["prefill_attention_path"]`)."""
    b, s, _ = x.shape
    nh, d, lat, rope = cfg.heads, cfg.hd, cfg.mla_latent, cfg.mla_rope_dim
    scale_q, scale_kv = cfg.mla_scales
    sm_scale = scale_q * (d + rope) ** -0.5
    y = _rms_norm(x, p["ln_attn"], cfg.norm_eps)
    wkv_b = p["wkv_b"].astype(y.dtype)  # [latent, H, 2 D]
    with jax.named_scope("mla.project"):
        # wq's columns: every head's `d` unrotated dimensions, then every
        # head's `rope` shared-key dimensions, so that each part is a plain
        # split of the product (heads of d + rope side by side made the
        # chip's compiler copy the matrix every step)
        q_in = y
        if cfg.mla_q_rank:
            q_in = _rms_norm(jnp.einsum("bsh,hr->bsr", y,
                                        p["wq_a"].astype(y.dtype)),
                             p["q_norm"], cfg.norm_eps)
        q = jnp.einsum("bsh,hm->bsm", q_in, p["wq"].astype(y.dtype))
        q_n = q[..., :nh * d].reshape(b, s, nh, d)
        q_r = q[..., nh * d:].reshape(b, s, nh, rope)
        kv = jnp.einsum("bsh,hc->bsc", y, p["wkv_a"].astype(y.dtype))
        c, k_r = kv[..., :lat], kv[..., lat:]
        if scale_kv == 1.0:
            c = _rms_norm(c, p["kv_norm"], cfg.norm_eps)
        else:  # the factor in float32, one rounding into the row
            c32 = c.astype(F32)
            c = (c32 * lax.rsqrt(jnp.mean(c32 * c32, -1, keepdims=True)
                                 + cfg.norm_eps)
                 * (p["kv_norm"].astype(F32) * scale_kv)).astype(c.dtype)
    if cfg.mla_rotate:
        with jax.named_scope("mla.rotate"):
            q_r = rotate_interleaved(q_r, positions, cfg.rope_theta)
            k_r = rotate_interleaved(k_r, positions, cfg.rope_theta)
    with jax.named_scope("mla.project"):
        row = jnp.concatenate(
            [c, k_r, jnp.zeros((b, s, cfg.latent_row - lat - rope),
                               kv.dtype)], axis=-1).astype(latent.dtype)
        if s == latent.shape[2]:  # a prefill into a row cache of its bucket
            latent = lax.dynamic_update_index_in_dim(latent, row, layer, 0)
        else:
            latent = latent.at[layer, jnp.arange(b)[:, None], positions].set(
                row)
    if s == 1:  # a decode step: absorbed, over the rows held
        with jax.named_scope("mla.project"):
            absorbed = jnp.einsum("bnd,cnd->bnc", q_n[:, 0],
                                  wkv_b[..., :d])
            q_row = jnp.concatenate(
                [absorbed, q_r[:, 0],
                 jnp.zeros((b, nh, cfg.latent_row - lat - rope), q.dtype)],
                axis=-1)
        with jax.named_scope("mla.attend"):
            u = latent_attend(q_row, latent, layer, rows, kv_len_mask, lat,
                              sm_scale)
        with jax.named_scope("mla.out"):
            o = jnp.einsum("bnc,cnd->bnd", u.astype(y.dtype),
                           wkv_b[..., d:])[:, None]
    else:  # a prefill from position 0: expanded, over its own fresh rows
        with jax.named_scope("mla.attend"):
            expanded = jnp.einsum("bsc,cnd->bsnd", row[..., :lat].astype(
                y.dtype), wkv_b)
            k_n, v = expanded[..., :d], expanded[..., d:]
            k_r = row[..., lat:lat + rope].astype(y.dtype)
            # the expanded rows as the flash forward reads them: a head's
            # query [q_n ; q_r] and key [k_n ; the shared k_r] in whole
            # lanes, zeros behind both (they add nothing to a score)
            zeros = jnp.zeros((b, s, nh, -(d + rope) % 128), y.dtype)
            o = attend_fresh(
                jnp.concatenate([q_n, q_r, zeros], -1),
                FreshRows(jnp.concatenate([k_n, jnp.broadcast_to(
                    k_r[:, :, None], (b, s, nh, rope)), zeros], -1), v),
                sm_scale=sm_scale)
            if o is None:  # a shape the kernel does not take
                o = _attend_expanded(
                    q_n, q_r, k_n, k_r, v, positions, row_mask, sm_scale,
                    PREFILL_QUERY_BLOCK
                    if nh * s * s * 4 > PREFILL_LOGITS_MAX else None)
    with jax.named_scope("mla.out"):
        out = _from_heads(o.astype(x.dtype), p["wo"])
    return x + out, latent


# -- the grouped-attention layer ---------------------------------------------------

def gkv_attention(cfg: TransformerConfig, x, p, positions, k_cache, v_cache,
                  kv_len_mask, layer, rows=None):
    """The attention half of "gkv" layer `layer` (its index in `KVCache.k`):
    grouped attention over the slots' rows, no rotation, the output gated
    where `gqa_gate`. Returns (x, k_cache, v_cache). The scopes are cut
    where the bytes change: the four matrices read (`gqa.project`: the
    gate's among them), the rows written and read (`gqa.attend`), the gate's
    elementwise pass, `wo`."""
    b, s, _ = x.shape
    y = _rms_norm(x, p["ln_attn"], cfg.norm_eps)
    with jax.named_scope("gqa.project"):
        q, k, v = (_to_heads(y, p[w], n) for w, n in (
            ("wq", cfg.heads), ("wk", cfg.kv_heads), ("wv", cfg.kv_heads)))
        if cfg.gqa_gate:
            gate = jnp.einsum("bsh,hm->bsm", y, p["wg"].astype(y.dtype),
                              preferred_element_type=F32)
    with jax.named_scope("gqa.attend"):
        k_cache, v_cache, held = _write_stack(layer)(
            k_cache, v_cache, k, v, positions)
        o = attend_held(q, held, positions, kv_len_mask, rows).reshape(
            b, s, -1)
    if cfg.gqa_gate:
        with jax.named_scope("gqa.gate"):
            o = o.astype(F32) * jax.nn.sigmoid(gate)
    with jax.named_scope("gqa.out"):
        out = jnp.einsum("bsm,mh->bsh", o.astype(x.dtype),
                         p["wo"].astype(x.dtype))
    return x + out, k_cache, v_cache


# -- the MLP halves ---------------------------------------------------------------

@jax.named_scope("moe_router")
def router(cfg: TransformerConfig, x, p):
    """x [T, h] -> (weights [T, k] float32, experts [T, k] int32),
    `transformer.moe_router`'s pair: sigmoid scores over all experts, the k
    chosen by score + the stored bias, the weights the scores WITHOUT it,
    divided by their sum if `norm_topk_prob`, times `routed_scale`. One
    group of experts: no grouping."""
    logits = jnp.einsum("th,he->te", x, p["router"].astype(x.dtype),
                        preferred_element_type=F32)
    scores = jax.nn.sigmoid(logits) if cfg.router_score == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    choose = scores
    if "router_bias" in p:
        choose = scores + p["router_bias"].astype(F32)
    _, experts = lax.top_k(choose, cfg.experts_per_token)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if cfg.norm_topk_prob:
        weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-20)
    return weights * cfg.routed_scale, experts


# beside the stream, in `layer`'s carry
CARRIED = ("k", "v", "mat", "conv", "latent")


def layer(cfg: TransformerConfig, call, kind: str, i, n, carry):
    """`pattern.forward_cached`'s one layer: the attention of `kind` at
    layer `i` of its kind over the matrix states and convolution windows,
    the latent rows or the K/V rows, then the leading layer's dense MLP (`n`
    None) or sparse layer `n`'s experts."""
    x, k, v, mat, conv, latent = carry
    p = _take(call.blocks[kind], i)
    if kind == "kda":
        x, mat, conv = kda_attention(cfg, x, p, mat, conv, call.row_mask, i)
    elif kind == "mla":
        x, latent = mla_attention(cfg, x, p, call.positions, latent,
                                  call.kv_len_mask, call.row_mask, i,
                                  call.rows)
    else:
        x, k, v = gkv_attention(cfg, x, p, call.positions, k, v,
                                call.kv_len_mask, i, call.rows)
    counted = None
    if n is None:
        dense = call.blocks["dense"]
        with jax.named_scope("mlp"):
            x = x + _swiglu(_rms_norm(x, dense["ln_mlp"], cfg.norm_eps),
                            dense["wi_gate"], dense["wi_up"], dense["wo_mlp"])
    else:
        x, *counted = sparse_mlp(cfg, x, call.sparse(n), call.row_mask, n,
                                 router)
    return (x, k, v, mat, conv, latent), counted
