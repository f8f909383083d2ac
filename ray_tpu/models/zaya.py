"""ZAYA1's two sublayers (Zyphra/ZAYA1-8B, `model_type` zaya): compressed
convolutional attention and the router that carries its representation from
layer to layer. Imported only where a configuration asks for them
(`families.SUBLAYERS`: `attention == "cca"`, `router == "zaya_mlp"`); the block
around them, the cache, the expert matmuls and the layer loop are the other
models' (`decoding.forward_cached`, `transformer.moe_dropless`).

**Attention (CCA, arXiv:2510.04476).** Everything happens inside the latent
`heads x head_dim` for queries and `kv_heads x head_dim` for keys and values
(1024 and 256 of a 2048-wide stream). With x the normed stream at position t:

1. `q~ = Wq x`, `k~ = Wk x`; `v = [Wv[:half] x_t ; Wv[half:] x_{t-1}]`: the
   second half of the KV heads sees the token before.
2. `c = [q~ ; k~]` (G = heads + kv_heads heads of head_dim); `u_t = a0 * c_t +
   a1 * c_{t-1}` (`conv0`: causal, depthwise, two taps); `w_t[g] = u_t[g] B0[g]
   + u_{t-1}[g] B1[g]` (`conv1`: causal, two taps, one group a head).
3. q-k mean: `q[h] = w[h] + (q~[h] + k~[h // rep]) / 2`, `k[j] = w[heads + j] +
   (mean over j's rep query heads of q~ + k~[j]) / 2`.
4. `q <- sqrt(d) q / |q|`, `k <- sqrt(d) k / |k| * tau[j]`; RoPE on the first
   `partial_rotary` of each head.
5. Causal softmax attention over the cached k and v, `Wo` back to the stream.

**State.** Steps 1 and 2 read position t-1: `c_{t-1}`, `u_{t-1}` and the
shifted half of v, `2 G + kv_heads / 2` heads of head_dim for each sequence
and layer (`KVCache.state`, [L, B, heads, D]; 2,688 values at the published
widths). One spelling serves prefill and decode: this call's S positions are
shifted by one with the state in front (zero for a sequence's first token),
and the new state is this call's LAST REAL position, which `row_mask` gives:
a prompt's true last token, not its bucket's; a decode step's one token; and
for a row with no real position (a free slot) the state stays as it was.

**Router (ZAYA1 report, arXiv:2511.17127).** On the expert sublayer's normed
input y: `r = Wd y + gamma * r_prev` (`router_hidden` wide; `r_prev` is the
layer before's r, zero for the first layer, and r is what the next layer
gets: depth, not time, so nothing is cached), `p = softmax(W3 gelu(W2
gelu(W1 r)))`, expert `argmax(p + b)` with `b` a stored balancing bias,
weight `p[expert]`. In float32 at the highest matmul precision: the matrices
are 256 wide and a top-1 choice decides a whole expert.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import transformer
from ray_tpu.models.decoding import attend_held
from ray_tpu.models.families import Kept
from ray_tpu.models.transformer import TransformerConfig, _rms_norm, _rope

# -- the family (`families.py`): the one block's fields, and the sublayers' ---------
FIELDS = transformer.SHARED | {"attention", "router", "partial_rotary"}


def check(cfg: TransformerConfig) -> None:
    if cfg.router == "zaya_mlp" and not (cfg.num_experts
                                         and cfg.router_hidden):
        raise ValueError("router 'zaya_mlp' needs num_experts and "
                         "router_hidden")
    if cfg.attention != "cca" and cfg.partial_rotary != 1.0:
        raise ValueError("partial_rotary is read by attention 'cca' alone")


def kept(cfg: TransformerConfig, max_len: int):
    """The one block's K/V rows, and beside them what "cca" reads of the
    position before (`state_heads`)."""
    rows = transformer.kept(cfg, max_len)
    if cfg.attention != "cca":
        return rows
    if cfg.kv_heads % 2:
        raise ValueError("attention 'cca' shifts half of the KV heads: "
                         f"kv_heads {cfg.kv_heads} is odd")
    return rows + (Kept(("state",), cfg.layers, None,
                        (state_heads(cfg), cfg.hd)),)


def conv_heads(cfg: TransformerConfig) -> int:
    """G: the heads the convolutions run over, queries then keys."""
    return cfg.heads + cfg.kv_heads


def state_heads(cfg: TransformerConfig) -> int:
    """Heads of head_dim a sequence keeps per layer: c, u, the shifted v."""
    return 2 * conv_heads(cfg) + cfg.kv_heads // 2


def extra_params(cfg: TransformerConfig) -> int:
    """A layer's parameters beyond what `TransformerConfig.num_params`
    counts for a "gqa" attention and a "linear" router: the convolutions'
    taps and the temperature; the router's MLP in place of one matrix."""
    extra = 0
    if cfg.attention == "cca":
        g, d = conv_heads(cfg), cfg.hd
        extra += 2 * g * d + 2 * g * d * d + cfg.kv_heads
    if cfg.router == "zaya_mlp":
        r, e = cfg.router_hidden, cfg.num_experts
        extra += cfg.hidden * r + 2 * r * r + r * e + 1 + e - cfg.hidden * e
    return extra


def init_block_params(cfg: TransformerConfig, blocks, stack, key) -> None:
    """Add the two sublayers' stacked parameters to `blocks`. The taps, the
    temperature, gamma and the bias start away from the identity so that a
    seeded model exercises them (a trained one stores its own)."""
    l, pd = cfg.layers, cfg.param_dtype
    ks = jax.random.split(key, 9)

    def uniform(k, shape, lo, hi):
        return jax.random.uniform(k, (l, *shape), jnp.float32, lo, hi
                                  ).astype(pd)

    if cfg.attention == "cca":
        g, d = conv_heads(cfg), cfg.hd
        blocks["conv0"] = uniform(ks[0], (2, g, d), -1.0, 1.0)
        blocks["conv1"] = stack(ks[1], (2, g, d, d), d)
        blocks["tau"] = uniform(ks[2], (cfg.kv_heads,), 0.5, 1.5)
    if cfg.router == "zaya_mlp":
        r, e = cfg.router_hidden, cfg.num_experts
        blocks["router_down"] = stack(ks[3], (cfg.hidden, r), cfg.hidden)
        # A seeded router has to spread its tokens as a trained, balanced
        # one does, or a decode step reaches (and reads) fewer experts than
        # a deployment's: wider than 1/sqrt(r) so that the logits spread
        # (std 1.5: a first probability near 0.4, not sixteen of 1/16), and
        # the second and third matrices' columns centred, because a GELU's
        # output has a positive mean that would give every token the same
        # favourite experts (fullest expert 5-9 times the mean without,
        # 1.2-1.5 with, at the published widths)
        def centred(k, shape, fan_in):
            w = stack(k, shape, fan_in).astype(jnp.float32)
            return (w - w.mean(1, keepdims=True)).astype(pd)

        blocks["router_w1"] = stack(ks[4], (r, r), r / 2)
        blocks["router_w2"] = centred(ks[5], (r, r), r / 2)
        blocks["router_w3"] = centred(ks[6], (r, e), r / 4)
        blocks["router_gamma"] = uniform(ks[7], (), 0.25, 0.75)
        blocks["router_bias"] = uniform(ks[8], (e,), -0.01, 0.01)


def update_block_axes(cfg: TransformerConfig, axes) -> None:
    if cfg.attention == "cca":
        axes.update(conv0=("layers", None, None, "head_dim"),
                    conv1=("layers", None, None, "head_dim", None),
                    tau=("layers", "kv_heads"))
    if cfg.router == "zaya_mlp":  # replicated, like the linear router
        axes.update(router_down=("layers", "embed", None),
                    router_w1=("layers", None, None),
                    router_w2=("layers", None, None),
                    router_w3=("layers", None, None),
                    router_gamma=("layers",),
                    router_bias=("layers", None))


def _shifted(seq, before):
    """seq [B, S, ...] one position later, `before` [B, ...] in front: what
    each of this call's positions sees at t-1."""
    return jnp.concatenate([before[:, None].astype(seq.dtype), seq[:, :-1]],
                           axis=1)


def _unit(x, scale):
    """x [..., D] float32 at length `scale`."""
    return x * (scale * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-12))


def _partial_rope(cfg: TransformerConfig, x, positions):
    rot = int(cfg.hd * cfg.partial_rotary)
    if rot == cfg.hd:
        return _rope(x, positions, cfg.rope_theta)
    return jnp.concatenate(
        [_rope(x[..., :rot], positions, cfg.rope_theta), x[..., rot:]], -1)


def attention_cached(cfg: TransformerConfig, x, p, positions, k_cache,
                     v_cache, state, kv_len_mask, row_mask, layer, access,
                     rows=None):
    """The attention half of a decoder block for "cca", against cached k/v
    and the carried state stack [L, B, heads, D]. Returns (x, k_cache,
    v_cache, state). `access` writes the K/V rows and `rows` states what a
    decode step's slots hold, as for any attention
    (`decoding._attention_cached`); the state is this function's."""
    b, s, _ = x.shape
    nh, nkv, d = cfg.heads, cfg.kv_heads, cfg.hd
    g, half, rep = nh + nkv, nkv // 2, nh // nkv
    f32 = jnp.float32
    y = _rms_norm(x, p["ln_attn"], cfg.norm_eps)
    with jax.named_scope("cca.project"):
        q_lat = jnp.einsum("bsh,hnd->bsnd", y, p["wq"].astype(y.dtype))
        k_lat = jnp.einsum("bsh,hnd->bsnd", y, p["wk"].astype(y.dtype))
        v_own = jnp.einsum("bsh,hnd->bsnd", y, p["wv"].astype(y.dtype))
    with jax.named_scope("cca.conv"):
        before = lax.dynamic_index_in_dim(state, layer, keepdims=False)
        c = jnp.concatenate([q_lat, k_lat], axis=2)  # [B, S, G, D]
        taps0, taps1 = p["conv0"].astype(f32), p["conv1"].astype(f32)
        u = (taps0[0] * c + taps0[1] * _shifted(c, before[:, :g])
             ).astype(c.dtype)
        # float32 operands that hold bfloat16 values: the TPU's default
        # precision multiplies them as bfloat16 and sums in float32, and the
        # CPU has no batched bfloat16 product with a float32 sum
        w = jnp.einsum("bsgd,gde->bsge", u.astype(f32), taps1[0]) + jnp.einsum(
            "bsgd,gde->bsge", _shifted(u, before[:, g:2 * g]).astype(f32),
            taps1[1])
        v = jnp.concatenate(
            [v_own[:, :, :half],
             _shifted(v_own[:, :, half:], before[:, 2 * g:])], axis=2)
        q32, k32 = q_lat.astype(f32), k_lat.astype(f32)
        q = w[:, :, :nh] + (q32 + jnp.repeat(k32, rep, axis=2)) / 2
        k = w[:, :, nh:] + (
            q32.reshape(b, s, nkv, rep, d).mean(3) + k32) / 2
        # the state after this call: its last real position, and what it was
        # for a row that has none
        now = jnp.concatenate([c, u, v_own[:, :, half:]], axis=2)
        n_real = row_mask.sum(1)
        last = jnp.take_along_axis(
            now, jnp.maximum(n_real - 1, 0)[:, None, None, None], axis=1)[:, 0]
        state = lax.dynamic_update_index_in_dim(
            state, jnp.where((n_real > 0)[:, None, None],
                             last.astype(state.dtype), before), layer, 0)
    with jax.named_scope("cca.attend"):
        q = _unit(q, math.sqrt(d))
        k = _unit(k, math.sqrt(d)) * p["tau"].astype(f32)[:, None]
        q = _partial_rope(cfg, q, positions).astype(x.dtype)
        k = _partial_rope(cfg, k, positions).astype(x.dtype)
        k_cache, v_cache, held = access(k_cache, v_cache, k, v, positions)
        attn = attend_held(q, held, positions, kv_len_mask, rows)
        attn = jnp.einsum("bsnd,ndh->bsh", attn, p["wo"].astype(attn.dtype))
    return x + attn, k_cache, v_cache, state


@jax.named_scope("zaya.router")
def router(cfg: TransformerConfig, y, p, r_prev):
    """y [T, h] -> ((weights [T, 1] float32, experts [T, 1] int32), r [T,
    router_hidden] float32): `transformer.moe_router`'s pair for one expert
    a token, and the representation the next layer's router adds to its
    own. `r_prev` is the layer before's, zeros for the first layer."""
    f32, hi = jnp.float32, lax.Precision.HIGHEST
    r = jnp.einsum("th,hr->tr", y, p["router_down"].astype(y.dtype),
                   preferred_element_type=f32)
    r = r + p["router_gamma"].astype(f32) * r_prev
    hidden = jax.nn.gelu(jnp.dot(r, p["router_w1"].astype(f32), precision=hi),
                         approximate=False)
    hidden = jax.nn.gelu(
        jnp.dot(hidden, p["router_w2"].astype(f32), precision=hi),
        approximate=False)
    probs = jax.nn.softmax(
        jnp.dot(hidden, p["router_w3"].astype(f32), precision=hi), axis=-1)
    experts = jnp.argmax(probs + p["router_bias"].astype(f32), axis=-1,
                         keepdims=True).astype(jnp.int32)
    return (jnp.take_along_axis(probs, experts, axis=-1), experts), r
