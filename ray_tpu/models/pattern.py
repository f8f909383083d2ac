"""What the layer patterns share (`families.PATTERNS`): their parameters
from their `leaves`, a kind's layer out of its stack, the dense SwiGLU, a
layer's sparse half (an expert's matrices and the width it works in are the
family's to state: `cfg.expert_act`, `cfg.moe_latent`) and the one cache
access their loops know. Each keeps
its own `forward_cached`: a double layer is no attention + sparse MLP.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import families
from ray_tpu.models.decoding import _write_stack
from ray_tpu.models.transformer import (
    TransformerConfig, _rms_norm, moe_dropless, moe_router,
)

EXPERT_LEAVES = ("wi_gate", "wi_up", "wo_mlp")  # a SwiGLU expert's stacks
# A leaf larger than this many elements is drawn a piece at a time
# (`_draw`): its float32 draw would not fit beside the leaves before it.
WHOLE_DRAW_MAX = 1 << 28


def only_the_stack(cfg: TransformerConfig, access) -> None:
    """A pattern's layers write what they keep (`cfg.keeps`) into the
    carried stacks themselves: `forward_cached`'s `access` is the default's
    or it is refused."""
    if access is not _write_stack:
        raise ValueError(
            f"a layer pattern {cfg.layer_kinds!r} keeps "
            f"{', '.join(cfg.keeps)} a sequence, each written in place in "
            "its stack: no other cache access (pages) holds them")


# -- parameters: a pattern states `leaves(cfg)`, {(group, ..., name): (shape,
# init, axes)}, `init` a fan-in, None (ones) or a name its `special(cfg, key,
# shape, init)` knows, and imports these three --------------------------------

def num_params(cfg: TransformerConfig) -> int:
    """What is HELD here: `experts_held` experts a layer, not `num_experts`."""
    return sum(math.prod(shape)
               for shape, _, _ in families.of(cfg).leaves(cfg).values())


def param_axes(cfg: TransformerConfig) -> dict:
    return _tree({path: axes for path, (_, _, axes)
                  in families.of(cfg).leaves(cfg).items()})


def init_params(cfg: TransformerConfig, key: jax.Array) -> dict:
    family, out = families.of(cfg), {}
    for i, (path, (shape, init, _)) in enumerate(family.leaves(cfg).items()):
        k = jax.random.fold_in(key, i)
        if init is None:
            out[path] = jnp.ones(shape, cfg.param_dtype)
        elif isinstance(init, str):
            out[path] = family.special(cfg, k, shape, init)
        else:
            out[path] = _draw(k, shape, init, cfg.param_dtype)
    return _tree(out)


def expert_names(cfg: TransformerConfig) -> tuple:
    """The expert stacks of `blocks["sparse"]`, by what the family states an
    expert is (`cfg.expert_act`): `EXPERT_LEAVES`, or without the gate."""
    return EXPERT_LEAVES if cfg.expert_act == "swiglu" else EXPERT_LEAVES[1:]


def expert_leaves(cfg: TransformerConfig, n: int) -> dict:
    """`leaves`' entries of `n` sparse layers' HELD experts' stacks: an
    expert's matrices (`expert_names`) on the width the family's experts
    work in, `cfg.moe_latent` or the stream's."""
    h, m, at = cfg.moe_latent or cfg.hidden, cfg.mlp_hidden, \
        ("blocks", "sparse")
    held = cfg.experts_held[1] if cfg.experts_held else cfg.num_experts
    up = ((n, held, h, m), h, ("layers", "expert", "embed", "mlp"))
    down = ((n, held, m, h), m, ("layers", "expert", "mlp", "embed"))
    return {at + (name,): down if name == "wo_mlp" else up
            for name in expert_names(cfg)}


def mlp_leaves(cfg: TransformerConfig) -> dict:
    """`leaves`' entries of ONE leading dense SwiGLU (`blocks["dense"]`, if
    the family has one: `cfg.dense_mlp_hidden`) and the sparse layers: norm,
    router (a stored selection bias where it scores by sigmoid), the two
    projections of a latent the experts work in (`cfg.moe_latent`), held
    experts and, if any, the shared expert, a unit of the experts' kind."""
    h, m, out = cfg.hidden, cfg.dense_mlp_hidden, {}
    if m:
        out = {("blocks", "dense", "ln_mlp"): ((h,), None, ("norm",)),
               ("blocks", "dense", "wi_gate"): ((h, m), h, ("embed", "mlp")),
               ("blocks", "dense", "wi_up"): ((h, m), h, ("embed", "mlp")),
               ("blocks", "dense", "wo_mlp"): ((m, h), m, ("mlp", "embed"))}
    n, at = cfg.sparse_layers, ("blocks", "sparse")
    out[at + ("ln_mlp",)] = ((n, h), None, ("layers", "norm"))
    out[at + ("router",)] = ((n, h, cfg.num_experts), h,
                             ("layers", "embed", None))
    if cfg.router_score == "sigmoid":
        out[at + ("router_bias",)] = ((n, cfg.num_experts), "router_bias",
                                      ("layers", None))
    if cfg.moe_latent:
        lat = cfg.moe_latent
        out[at + ("latent_down",)] = ((n, h, lat), h,
                                      ("layers", "embed", None))
        out[at + ("latent_up",)] = ((n, lat, h), lat,
                                    ("layers", None, "embed"))
    out.update(expert_leaves(cfg, n))
    if cfg.shared_expert_hidden:
        ms = cfg.shared_expert_hidden
        up = ((n, h, ms), h, ("layers", "embed", "mlp"))
        if cfg.expert_act == "swiglu":
            out[at + ("shared_gate",)] = up
        out.update({at + ("shared_up",): up,
                    at + ("shared_down",): ((n, ms, h), ms,
                                            ("layers", "mlp", "embed"))})
    return out


def _tree(flat: dict) -> dict:
    out: dict = {}
    for path, value in flat.items():
        node = out
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = value
    return out


@functools.partial(jax.jit, static_argnames=("shape", "fan_in", "dtype"))
def _draw(key, shape, fan_in, dtype):
    """A leaf at its stacked shape, never held twice or whole in float32: a
    large one is drawn over its leading axes a piece at a time and cast
    inside (the one block's `stack()` holds a Python list of layers AND
    their `jnp.stack`, each drawn in float32: 14.2 GB for OLMoE's 7.1)."""
    lead = 0
    while math.prod(shape[lead:]) > WHOLE_DRAW_MAX and lead < len(shape) - 1:
        lead += 1

    def piece(k):
        return (jax.random.normal(k, shape[lead:], jnp.float32)
                / math.sqrt(fan_in)).astype(dtype)

    if not lead:
        return piece(key)
    keys = jax.random.split(key, math.prod(shape[:lead]))
    return lax.map(piece, keys).reshape(shape)


# -- the MLP halves ---------------------------------------------------------------

def _swiglu(y, gate, up, down):
    act = jax.nn.silu(jnp.einsum("bsh,hm->bsm", y, gate.astype(y.dtype))) \
        * jnp.einsum("bsh,hm->bsm", y, up.astype(y.dtype))
    return jnp.einsum("bsm,mh->bsh", act, down.astype(act.dtype))


def _relu2(y, up, down):
    act = jnp.square(jax.nn.relu(
        jnp.einsum("bsh,hm->bsm", y, up.astype(y.dtype))))
    return jnp.einsum("bsm,mh->bsh", act, down.astype(act.dtype))


def sparse_mlp(cfg: TransformerConfig, x, p, row_mask, layer,
               router=moe_router):
    """The expert half of sparse layer `layer`: `p` is that layer's small
    parameters and the WHOLE expert stacks (`_grouped_matmul` reads its
    layer in place); `router(cfg, rows, p)` gives `moe_router`'s pair.
    With `cfg.moe_latent` the experts work in a latent: the normed stream
    goes down through `latent_down` before them (`lmoe.down`) and their
    weighted sum up through `latent_up` behind them (`lmoe.up`); the router
    and the shared expert read the stream itself.
    Returns (x, load [num_experts] from the real rows, the experts every row
    chose [B*S, k], how many of the experts held here the real rows
    reached)."""
    y = _rms_norm(x, p["ln_mlp"], cfg.norm_eps)
    routing = router(cfg, y.reshape(-1, y.shape[-1]), p)
    into = y
    if cfg.moe_latent:
        with jax.named_scope("lmoe.down"):
            into = jnp.einsum("bsh,hl->bsl", y,
                              p["latent_down"].astype(y.dtype))
    routed, load = moe_dropless(cfg, into, p, row_mask, layer, routing)
    if cfg.moe_latent:
        with jax.named_scope("lmoe.up"):
            routed = jnp.einsum("bsl,lh->bsh", routed,
                                p["latent_up"].astype(routed.dtype))
    x = x + routed
    if cfg.shared_expert_hidden:
        with jax.named_scope("moe.shared"):
            x = x + (_swiglu(y, p["shared_gate"], p["shared_up"],
                             p["shared_down"])
                     if cfg.expert_act == "swiglu" else
                     _relu2(y, p["shared_up"], p["shared_down"]))
    first, count = cfg.experts_held or (0, cfg.num_experts)
    reached = (load[first:first + count] > 0).sum().astype(jnp.int32)
    return x, load.astype(jnp.int32), routing[1], reached


def _take(tree, i):
    """Layer `i` of a kind's stacked parameters."""
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, i, keepdims=False), tree)
