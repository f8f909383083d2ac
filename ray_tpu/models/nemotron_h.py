"""A pattern of layers that are ONE sublayer each: Mamba-2 state-space
mixers, attentions without rotation and expert layers whose experts work in a
latent (nvidia/NVIDIA-Nemotron-3-Super-120B-A12B, `model_type` nemotron_h);
and Mamba-1 mixers and dense SwiGLU sublayers beside the same attention
(ai21labs/AI21-Jamba2-3B, `model_type` jamba: a published layer is two of
these, a mixer or an attention and then an MLP, each behind a norm of its
own). Imported only where a configuration has one (`families.PATTERNS`); the
cache's slots, the grouped attention, the expert matmuls, the sigmoid router,
sampling, the scheduler and the drawing of weights are the other models'
(`decoding._write_stack` / `attend_held`, `transformer.moe_dropless`,
`kimi_linear.router`, `pattern._draw`).

**Layers.** `cfg.layer_kinds` is EVERY layer's kind, in the order of the
published `hybrid_override_pattern` (M "ssm", * "gqa", E "lmoe"): it is not
periodic, so no lead, period or tail is read into it. Every layer is `x <- x
+ f(RMSNorm(x))`. Parameters are stacked BY KIND (`blocks["ssm" | "ssm1" |
"gqa" | "mlp" | "sparse"]`); `pattern.forward_cached` reads the loop over
`layer` here off the string: a run of kinds that repeats (`pattern.cut`: the
`M E` between two attentions) is ONE `lax.scan`, what is left is unrolled.
With y the normed stream:

**An "ssm" layer** (`ssm_heads` heads of `ssm_head_dim`, `ssm_groups` groups,
a state of `ssm_state`): `[z ; u ; dt~] = y W_in`, u the convolution's
channels `[x~ ; B~ ; C~]`; a causal depthwise convolution of `ssm_conv` taps
WITH a bias over u, then SiLU: `[x ; B ; C]`; `dt = softplus(dt~ +
dt_bias)`, `a = exp(-exp(A_log) dt)` a head, float32; the state S of head h
(group g = h // (heads / groups)), float32, zero at a sequence's start: `S_t
= a_t S_{t-1} + dt_t x_t B_{g,t}^T`, `o_t = S_t C_{g,t} + D_h x_t`; the gate
FIRST and the norm second: `o <- o * SiLU(z)`, an RMSNorm over each GROUP's
channels with one weight a channel, then `W_out`. A sequence keeps S
(`KVCache.mat`, one matrix [state, heads * head_dim] a layer: `ops/ssd.py`
says why it lies so) and the last `ssm_conv - 1` inputs u (`KVCache.conv`,
flat). A decode step (S == 1) is one update of S, every product into it exact
in float32 (on a TPU `ops.ssd.ssm_state_update`: the stack read once and
written once, in place). A call with S > 1 is a prefill FROM POSITION 0
(every engine's) and runs the recurrence `ssm_chunk` positions at a time
(`ops.ssd.ssm_chunks`, exact); the state and the window it leaves are those
at each sequence's TRUE last position (`row_mask`): a pad position has dt 0.

**An "ssm1" layer** (Mamba-1: `channels` = `ssm_heads * ssm_head_dim`
channels, each a head of its own; a state of `ssm_state`; `ssm_dt_rank`):
`[u ; z] = y W_in`; the same convolution, over u ALONE (B and C are not
convolved), then SiLU: x; `[dt~ ; B~ ; C~] = x W_x` (`ssm_dt_rank`, state,
state columns), an RMSNorm with a weight on each of the three (the family's
own addition); `dt = softplus(dt~ W_dt + dt_bias)` a CHANNEL; `A =
-exp(A_log)` [state, channels]; channel c's state h [state] float32: `h_t[n]
= exp(dt_t[c] A[n, c]) h_{t-1}[n] + dt_t[c] x_t[c] B_t[n]`, `o_t[c] = sum_n
h_t[n] C_t[n] + D[c] x_t[c]`; `o <- o * SiLU(z)`, `W_out`: no norm behind the
gate. The decay differs by channel AND by state index, so a chunk of
positions has no matrix form: a prefill is a true scan (`ops.ssd.
selective_scan`, on a TPU a kernel that keeps a block of channels' states in
fast memory) and a decode step forms the decay inside its kernel
(`selective_state_update`). What a sequence keeps has the "ssm" layer's
layout: `KVCache.mat` [state, channels] float32 and the window of u in
`KVCache.conv`; a configuration has mixers of ONE of the two kinds.

**A "gqa" layer**: `q = y Wq` (`heads` of `hd`), `k, v = y Wk, y Wv`
(`kv_heads`), causal softmax of `q k^T / sqrt(hd)`, `Wo`. NO rotation and no
other position signal. Rows in `KVCache.k` / `.v` (`_write_stack`,
`attend_held`).

**An "mlp" layer**: the dense SwiGLU `W_down(SiLU(y W_gate) * (y W_up))`,
`mlp_hidden` wide (`pattern._swiglu`).

**An "lmoe" layer**: `pattern.sparse_mlp` with the family's statement: the
router (`kimi_linear.router`: sigmoid scores, the top k of score + a stored
bias, renormalised, times `routed_scale`) and the shared expert read the
stream; the experts are two matrices with ReLU squared between them
(`expert_act`) and work on `y W_dn`, `moe_latent` wide, their weighted sum
going back through `W_up`. `moe_dropless` with `experts_held`: what the
absent experts would add is left out here, and nothing stands in for the
other chips or for their exchange.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.decoding import _write_stack, attend_held
from ray_tpu.models.families import Kept
from ray_tpu.models.kimi_linear import router
from ray_tpu.models.pattern import (  # noqa: F401 (the family's three, `runs`)
    _swiglu, _take, init_params, mlp_leaves, num_params, param_axes, runs,
    sparse_mlp,
)
from ray_tpu.models.transformer import TransformerConfig, _rms_norm
from ray_tpu.ops import ssd

F32 = jnp.float32
# the published steps (`time_step_min`, `time_step_max`, `time_step_floor`)
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4

# -- the family (`families.py`) ---------------------------------------------------
FIELDS = frozenset({
    "layer_kinds", "lead_kind", "ssm_heads", "ssm_head_dim", "ssm_groups",
    "ssm_state", "ssm_conv", "ssm_chunk", "ssm_dt_rank", "moe_latent",
    "expert_act", "router_score", "shared_expert_hidden", "experts_held",
    "tie_embeddings"})


def check(cfg: TransformerConfig) -> None:
    if cfg.lead_kind or cfg.layers != len(cfg.layer_kinds):
        raise ValueError(
            f"layer_kinds names every one of the {cfg.layers} layers, in "
            f"order, and there is no leading layer (lead_kind ''): it has "
            f"{len(cfg.layer_kinds)} and lead_kind {cfg.lead_kind!r}")
    if cfg.layers_of("ssm") and not (
            cfg.ssm_heads and cfg.ssm_head_dim and cfg.ssm_state
            and cfg.ssm_conv >= 2 and cfg.ssm_groups
            and cfg.ssm_heads % cfg.ssm_groups == 0):
        raise ValueError("an ssm layer needs ssm_heads in whole ssm_groups, "
                         "ssm_head_dim, ssm_state and ssm_conv (taps, >= 2)")
    if cfg.layers_of("ssm1") and (cfg.layers_of("ssm") or not (
            channels(cfg) and cfg.ssm_state and cfg.ssm_conv >= 2
            and cfg.ssm_dt_rank)):
        raise ValueError(
            "an ssm1 layer needs its channels (ssm_heads x ssm_head_dim), "
            "ssm_state, ssm_conv (taps, >= 2) and ssm_dt_rank (the low rank "
            "its steps are projected through), and no ssm layer beside it "
            "(a sequence's KVCache.mat has one shape)")
    if cfg.layers_of("gqa") and cfg.heads % cfg.kv_heads:
        raise ValueError("the query heads are whole groups of kv_heads")
    if cfg.layers_of("lmoe") and not cfg.num_experts:
        raise ValueError("an lmoe layer needs num_experts")


def sparse_layers(cfg: TransformerConfig) -> int:
    """The layers that route: the "lmoe" ones alone."""
    return cfg.layers_of("lmoe")


def channels(cfg: TransformerConfig) -> int:
    """A mixer's inner width: its heads' channels, side by side."""
    return cfg.ssm_heads * cfg.ssm_head_dim


def conv_channels(cfg: TransformerConfig) -> int:
    """What the convolution runs over: x~ and, in an "ssm" layer, a B~ and a
    C~ a group (an "ssm1" layer projects B and C from the convolved x)."""
    return channels(cfg) + (0 if cfg.layers_of("ssm1") else
                            2 * cfg.ssm_groups * cfg.ssm_state)


def kept(cfg: TransformerConfig, max_len: int):
    """A "gqa" layer's K/V rows in slots of `max_len`; an "ssm" or "ssm1"
    layer's states, float32 whatever the stream's dtype, one matrix [state,
    channels] a sequence, and its convolution's window, the `ssm_conv - 1`
    last inputs flat in one row a sequence (positions, then channels)."""
    ssm = cfg.layers_of("ssm") + cfg.layers_of("ssm1")
    return (Kept(("k", "v"), cfg.layers_of("gqa"), max_len,
                 (cfg.kv_heads, cfg.hd)),
            Kept(("mat",), ssm, None, (cfg.ssm_state, channels(cfg)), F32),
            Kept(("conv",), ssm, None,
                 ((cfg.ssm_conv - 1) * conv_channels(cfg),)))


# -- parameters --------------------------------------------------------------

def leaves(cfg: TransformerConfig) -> dict:
    """{(group, ..., name): (shape, init, logical axes)} of every parameter
    leaf. `init` is a fan-in (normal over its root), None (ones: a norm's
    weight, D), or the name of one of the family's initialisers (`special`).
    Projections into heads are plain matrices [in, heads * D] (the chip tiles
    [in, heads, D] another way than a product over `in` reads: PERF.md, PR
    38)."""
    h, d, nh, nkv = cfg.hidden, cfg.hd, cfg.heads, cfg.kv_heads
    out = {("embed",): ((cfg.vocab_size, h), h, ("vocab", "embed")),
           ("unembed",): ((h, cfg.vocab_size), h, ("embed", "vocab")),
           ("ln_f",): ((h,), None, ("norm",))}
    if cfg.tie_embeddings:  # `lm_head` reads the embedding's transpose
        del out["unembed",]
    n, at = cfg.layers_of("ssm"), ("blocks", "ssm")
    if n:
        inner, chans = cfg.ssm_heads * cfg.ssm_head_dim, conv_channels(cfg)
        out[at + ("ln",)] = ((n, h), None, ("layers", "norm"))
        # the columns in the published order: z, then u = [x~ ; B~ ; C~],
        # then dt~
        out[at + ("w_in",)] = ((n, h, inner + chans + cfg.ssm_heads), h,
                               ("layers", "embed", "heads"))
        out[at + ("conv_w",)] = ((n, cfg.ssm_conv, chans), "taps",
                                 ("layers", None, "heads"))
        out[at + ("conv_b",)] = ((n, chans), "zeros", ("layers", "heads"))
        out[at + ("dt_bias",)] = ((n, cfg.ssm_heads), "dt_bias",
                                  ("layers", "heads"))
        out[at + ("a_log",)] = ((n, cfg.ssm_heads), "a_log",
                                ("layers", "heads"))
        out[at + ("d",)] = ((n, cfg.ssm_heads), None, ("layers", "heads"))
        out[at + ("norm",)] = ((n, inner), None, ("layers", "norm"))
        out[at + ("w_out",)] = ((n, inner, h), inner,
                                ("layers", "heads", "embed"))
    n, at = cfg.layers_of("ssm1"), ("blocks", "ssm1")
    if n:
        inner, state, rank = channels(cfg), cfg.ssm_state, cfg.ssm_dt_rank
        out[at + ("ln",)] = ((n, h), None, ("layers", "norm"))
        # the columns: u, then the gate z
        out[at + ("w_in",)] = ((n, h, 2 * inner), h,
                               ("layers", "embed", "heads"))
        out[at + ("conv_w",)] = ((n, cfg.ssm_conv, inner), "taps",
                                 ("layers", None, "heads"))
        out[at + ("conv_b",)] = ((n, inner), "conv_bias", ("layers", "heads"))
        # the columns: dt~, then B~, then C~
        out[at + ("w_x",)] = ((n, inner, rank + 2 * state), inner,
                              ("layers", "heads", None))
        for name, width in (("dt_norm", rank), ("b_norm", state),
                            ("c_norm", state)):
            out[at + (name,)] = ((n, width), None, ("layers", "norm"))
        out[at + ("w_dt",)] = ((n, rank, inner), rank,
                               ("layers", None, "heads"))
        out[at + ("dt_bias",)] = ((n, inner), "dt_bias", ("layers", "heads"))
        # [state, channels], as the states lie: row n, lane c (`ops/ssd.py`)
        out[at + ("a_log",)] = ((n, state, inner), "a_log_by_state",
                                ("layers", None, "heads"))
        out[at + ("d",)] = ((n, inner), None, ("layers", "heads"))
        out[at + ("w_out",)] = ((n, inner, h), inner,
                                ("layers", "heads", "embed"))
    n, at = cfg.layers_of("mlp"), ("blocks", "mlp")
    if n:
        m = cfg.mlp_hidden
        out[at + ("ln",)] = ((n, h), None, ("layers", "norm"))
        for name in ("wi_gate", "wi_up"):
            out[at + (name,)] = ((n, h, m), h, ("layers", "embed", "mlp"))
        out[at + ("wo_mlp",)] = ((n, m, h), m, ("layers", "mlp", "embed"))
    n, at = cfg.layers_of("gqa"), ("blocks", "gqa")
    if n:
        out[at + ("ln",)] = ((n, h), None, ("layers", "norm"))
        out[at + ("wq",)] = ((n, h, nh * d), h, ("layers", "embed", "heads"))
        for name in ("wk", "wv"):
            out[at + (name,)] = ((n, h, nkv * d), h,
                                 ("layers", "embed", "kv_heads"))
        out[at + ("wo",)] = ((n, nh * d, h), nh * d,
                             ("layers", "heads", "embed"))
    if cfg.layers_of("lmoe"):
        out.update(mlp_leaves(cfg))
    return out


def special(cfg: TransformerConfig, key, shape, init: str):
    """The leaves a normal draw would leave degenerate, by the family's
    initialisers (the state-space library's, as remembered): the taps uniform
    in +-1/2 (one over the root of `ssm_conv` 4), their bias zero in an "ssm"
    layer and drawn as the taps in an "ssm1" layer (a convolution's own
    default; a zero bias could not be missed); `a_log` the log of a rate
    uniform in [1, 16), or, where the decay is by state index too, of 1 ..
    `ssm_state` down a channel's states; `dt_bias` the inverse softplus of
    a step log-uniform in [`DT_MIN`, `DT_MAX`), floored at `DT_FLOOR`; the
    selection bias normal of 0.01 about zero (a trained one is stored; zeros
    would leave it unexercised). Decays then lie strictly between 0 and 1."""
    if init in ("taps", "conv_bias"):
        bound = 1 / math.sqrt(cfg.ssm_conv)
        out = jax.random.uniform(key, shape, F32, -bound, bound)
    elif init == "a_log_by_state":
        out = jnp.broadcast_to(jnp.log(jnp.arange(
            1, shape[1] + 1, dtype=F32))[None, :, None], shape)
    elif init == "zeros":
        out = jnp.zeros(shape, F32)
    elif init == "a_log":
        out = jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))
    elif init == "dt_bias":
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, F32, math.log(DT_MIN), math.log(DT_MAX))), DT_FLOOR)
        out = dt + jnp.log(-jnp.expm1(-dt))
    elif init == "router_bias":
        out = 0.01 * jax.random.normal(key, shape, F32)
    else:
        raise ValueError(f"unknown initialiser {init!r}")
    return out.astype(cfg.param_dtype)


# -- the state-space layers --------------------------------------------------------

def convolve(u, p, conv, n_real, layer):
    """A mixer's causal depthwise convolution with its bias over u [B, S,
    channels], then SiLU, float32; `conv` is the stack of windows, read and
    rewritten at `layer`; `n_real` [B] how many of the S positions are each
    sequence's. Returns (the convolved channels, conv)."""
    b, s, chans = u.shape
    taps = p["conv_w"].shape[0]
    before = lax.dynamic_index_in_dim(conv, layer, keepdims=False)
    seen = jnp.concatenate(
        [before.reshape(b, taps - 1, chans).astype(u.dtype), u], axis=1)
    w = p["conv_w"].astype(F32)
    out = jax.nn.silu(sum(w[j] * seen[:, j:j + s].astype(F32)
                          for j in range(taps)) + p["conv_b"].astype(F32))
    # the window after this call: the inputs of each sequence's last
    # `taps - 1` real positions; what it was for a row that has none
    at = n_real[:, None] + jnp.arange(taps - 1)  # into `seen`
    window = jnp.take_along_axis(seen, at[:, :, None], axis=1)
    return out, lax.dynamic_update_index_in_dim(
        conv, window.reshape(b, -1).astype(conv.dtype), layer, 0)


def ssm_mixer(cfg: TransformerConfig, x, p, mat, conv, row_mask, layer):
    """"ssm" layer `layer` (its index within its kind): `mat` / `conv` are
    the stacks, read and rewritten at `layer` in place. Returns (x, mat,
    conv)."""
    b, s, _ = x.shape
    nh, hd = cfg.ssm_heads, cfg.ssm_head_dim
    groups, n = cfg.ssm_groups, cfg.ssm_state
    inner, chans = nh * hd, conv_channels(cfg)
    y = _rms_norm(x, p["ln"], cfg.norm_eps)
    real = row_mask.astype(F32)  # [B, S]
    n_real = row_mask.sum(1).astype(jnp.int32)
    with jax.named_scope("ssm.project"):
        zxbcdt = jnp.einsum("bsh,hm->bsm", y, p["w_in"].astype(y.dtype))
        z, u = zxbcdt[..., :inner], zxbcdt[..., inner:inner + chans]
        # a position that is no sequence's has dt 0: decay 1, nothing added
        dt = jax.nn.softplus(zxbcdt[..., inner + chans:].astype(F32)
                             + p["dt_bias"].astype(F32)) * real[..., None]
        log_a = -jnp.exp(p["a_log"].astype(F32)) * dt  # [B, S, heads]
    with jax.named_scope("ssm.conv"):
        xbc, conv = convolve(u, p, conv, n_real, layer)
        xs = xbc[..., :inner]  # [B, S, heads * head_dim], float32
        bs = xbc[..., inner:inner + groups * n].reshape(b, s, groups, n)
        cs = xbc[..., inner + groups * n:].reshape(b, s, groups, n)
    # the state's read and write are the sublayer's, under its scope
    with jax.named_scope("ssm.state" if s == 1 else "ssm.prefill_scan"):
        if s == 1:
            a = jnp.repeat(jnp.exp(log_a[:, 0]), hd, axis=-1)
            dtx = jnp.repeat(dt[:, 0], hd, axis=-1) * xs[:, 0]
            kernel = ssd.ssm_state_update_takes(mat)
            ssd.book("state", kernel)
            if kernel:  # read once, written once
                mat, o = ssd.ssm_state_update(mat, layer, a, dtx, bs[:, 0],
                                              cs[:, 0])
            else:
                after, o = ssd.ssm_step(
                    lax.dynamic_index_in_dim(mat, layer, keepdims=False), a,
                    dtx, bs[:, 0], cs[:, 0])
                mat = lax.dynamic_update_index_in_dim(mat, after, layer, 0)
            o = o[:, None]
        else:
            after, o = ssd.ssm_chunks(
                lax.dynamic_index_in_dim(mat, layer, keepdims=False),
                xs.reshape(b, s, nh, hd), dt, log_a, bs, cs, cfg.ssm_chunk)
            mat = lax.dynamic_update_index_in_dim(mat, after, layer, 0)
            o = o.reshape(b, s, inner)
        o = o + jnp.repeat(p["d"].astype(F32), hd) * xs
    with jax.named_scope("ssm.norm"):
        o = o * jax.nn.silu(z.astype(F32))  # the gate first, the norm second
        by_group = o.reshape(b, s, groups, inner // groups)
        by_group = by_group * lax.rsqrt(
            jnp.mean(by_group * by_group, -1, keepdims=True) + cfg.norm_eps)
        o = by_group.reshape(b, s, inner) * p["norm"].astype(F32)
    with jax.named_scope("ssm.out"):
        out = jnp.einsum("bsm,mh->bsh", o.astype(x.dtype),
                         p["w_out"].astype(x.dtype))
    return x + out, mat, conv


def ssm1_mixer(cfg: TransformerConfig, x, p, mat, conv, row_mask, layer):
    """"ssm1" layer `layer` (its index within its kind), as `ssm_mixer`:
    `mat` / `conv` are the stacks, read and rewritten at `layer` in place.
    The stream and the four projections in its dtype; the three small norms,
    dt, the decay, the state and the read-out float32. Returns (x, mat,
    conv)."""
    b, s, _ = x.shape
    inner, n, rank = channels(cfg), cfg.ssm_state, cfg.ssm_dt_rank
    y = _rms_norm(x, p["ln"], cfg.norm_eps)
    real = row_mask.astype(F32)  # [B, S]
    n_real = row_mask.sum(1).astype(jnp.int32)
    with jax.named_scope("ssm1.project"):
        uz = jnp.einsum("bsh,hm->bsm", y, p["w_in"].astype(y.dtype))
        u, z = uz[..., :inner], uz[..., inner:]
    with jax.named_scope("ssm1.conv"):
        xs, conv = convolve(u, p, conv, n_real, layer)  # float32
    with jax.named_scope("ssm1.project"):
        low = jnp.einsum("bsm,mr->bsr", xs.astype(x.dtype),
                         p["w_x"].astype(x.dtype),
                         preferred_element_type=F32)
        dt_low, bs, cs = (
            _rms_norm(v, p[name].astype(F32), cfg.norm_eps)
            for v, name in ((low[..., :rank], "dt_norm"),
                            (low[..., rank:rank + n], "b_norm"),
                            (low[..., rank + n:], "c_norm")))
        # a position that is no sequence's has dt 0: decay 1, nothing added
        dt = jax.nn.softplus(
            jnp.einsum("bsr,rm->bsm", dt_low.astype(x.dtype),
                       p["w_dt"].astype(x.dtype), preferred_element_type=F32)
            + p["dt_bias"].astype(F32)) * real[..., None]
    # the state's read and write are the sublayer's, under its scope
    with jax.named_scope("ssm1.state" if s == 1 else "ssm1.prefill_scan"):
        a = -jnp.exp(p["a_log"].astype(F32))  # [state, channels]
        dtx = dt * xs
        if s == 1:
            kernel = ssd.ssm_state_update_takes(mat)
            ssd.book("state", kernel)
            if kernel:  # read once, written once, the decay formed inside
                mat, o = ssd.selective_state_update(
                    mat, layer, a, dt[:, 0], dtx[:, 0], bs[:, 0], cs[:, 0])
            else:
                after, o = ssd.selective_step(
                    lax.dynamic_index_in_dim(mat, layer, keepdims=False), a,
                    dt[:, 0], dtx[:, 0], bs[:, 0], cs[:, 0])
                mat = lax.dynamic_update_index_in_dim(mat, after, layer, 0)
            o = o[:, None]
        else:
            before = lax.dynamic_index_in_dim(mat, layer, keepdims=False)
            kernel = ssd.selective_scan_takes(before, s)
            ssd.book("scan", kernel)
            after, o = ssd.selective_scan(before, a, dt, dtx, bs, cs) \
                if kernel else ssd.selective_scan_plain(
                    before, a, dt, dtx, bs, cs, cfg.ssm_chunk)
            mat = lax.dynamic_update_index_in_dim(mat, after, layer, 0)
        o = o + p["d"].astype(F32) * xs
    with jax.named_scope("ssm1.gate"):
        o = o * jax.nn.silu(z.astype(F32))
    with jax.named_scope("ssm1.out"):
        out = jnp.einsum("bsm,mh->bsh", o.astype(x.dtype),
                         p["w_out"].astype(x.dtype))
    return x + out, mat, conv


def dense_mlp(cfg: TransformerConfig, x, p):
    """"mlp" layer: the dense SwiGLU on the normed stream."""
    with jax.named_scope("mlp"):
        return x + _swiglu(_rms_norm(x, p["ln"], cfg.norm_eps), p["wi_gate"],
                           p["wi_up"], p["wo_mlp"])


# -- the attention layer -----------------------------------------------------------

def attention(cfg: TransformerConfig, x, p, positions, k_cache, v_cache,
              kv_len_mask, layer, rows=None):
    """"gqa" layer `layer` (its index in `KVCache.k`): grouped attention
    over the slots' rows, no rotation. Returns (x, k_cache, v_cache)."""
    b, s, _ = x.shape
    with jax.named_scope("attn.gqa"):
        y = _rms_norm(x, p["ln"], cfg.norm_eps)
        q, k, v = (jnp.einsum("bsh,hm->bsm", y, p[w].astype(y.dtype)).reshape(
            b, s, -1, cfg.hd) for w in ("wq", "wk", "wv"))
        k_cache, v_cache, held = _write_stack(layer)(
            k_cache, v_cache, k, v, positions)
        attn = attend_held(q, held, positions, kv_len_mask, rows)
        out = jnp.einsum("bsm,mh->bsh", attn.reshape(b, s, -1),
                         p["wo"].astype(attn.dtype))
    return x + out, k_cache, v_cache


# -- one layer (`pattern.forward_cached` walks them) ------------------------------

CARRIED = ("k", "v", "mat", "conv")  # beside the stream, in `layer`'s carry


def layer(cfg: TransformerConfig, call, kind: str, i, n, carry):
    """`pattern.forward_cached`'s one layer, the ONE sublayer of `kind` at
    layer `i` of its kind: a mixer over the states and convolution windows,
    the attention over the K/V stacks, a dense MLP, or sparse layer `i`'s
    experts (every "lmoe" layer routes and no other: `n` is not read)."""
    x, k, v, mat, conv = carry
    if kind == "lmoe":
        x, *counted = sparse_mlp(cfg, x, call.sparse(i), call.row_mask, i,
                                 router)
        return (x, k, v, mat, conv), counted
    p = _take(call.blocks[kind], i)
    if kind in ("ssm", "ssm1"):
        mixer = ssm_mixer if kind == "ssm" else ssm1_mixer
        x, mat, conv = mixer(cfg, x, p, mat, conv, call.row_mask, i)
    elif kind == "mlp":
        x = dense_mlp(cfg, x, p)
    else:
        x, k, v = attention(cfg, x, p, call.positions, k, v,
                            call.kv_len_mask, i, call.rows)
    return (x, k, v, mat, conv), None
