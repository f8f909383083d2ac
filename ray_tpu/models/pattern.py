"""What the layer patterns share (`families.PATTERNS`): their parameters
from their `leaves`, a kind's layer out of its stack, the dense SwiGLU, a
layer's sparse half (an expert's matrices and the width it works in are the
family's to state: `cfg.expert_act`, `cfg.moe_latent`) and the ONE loop over
a pattern's layers (`forward_cached`: the cut of a configuration's layers
into scans, the counters by kind, what the expert layers counted). A family
states one layer of a kind (`layer`, `CARRIED`). LongCat keeps a
`forward_cached` of its own: one scan of one kind of double layer, no
counter and no cut, and five things counted a layer where these count three.
"""

from __future__ import annotations

import functools
import math
import operator
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import families
from ray_tpu.models.decoding import _write_stack, lm_head
from ray_tpu.models.transformer import (
    TransformerConfig, _rms_norm, held_rows_cap, layout_counted,
    moe_dropless, moe_router,
)

EXPERT_LEAVES = ("wi_gate", "wi_up", "wo_mlp")  # a SwiGLU expert's stacks
# layers of one repeating unit at most (`runs`); a family whose units are
# longer states its own (`laguna.RUN_MAX`)
RUN_MAX = 4
# A call of more rows than `WHOLE_ROWS_MAX` runs its MLPs a piece at a time
# (`rows_at_a_time`), because `moe_dropless` gathers its rows in float32 and
# a dense SwiGLU holds [rows, mlp] three times: a dense MLP `MLP_ROWS` rows a
# call, an expert layer by the rows it GATHERS (`expert_rows`). Every cell's
# bucket but the 8,192 one is below it (the readings: PERF.md section 6, PR 59).
WHOLE_ROWS_MAX, MLP_ROWS = 4096, 1024
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


def runs(kinds: tuple, longest: int = RUN_MAX) -> list:
    """`kinds` cut into [(unit, repeats)]: at each layer the unit of at most
    `longest` kinds whose repeats from there cover the most layers, if it
    repeats at all (one scan over its repeats), else the one layer. What a
    family whose `layer_kinds` name every layer reads its loop off."""
    out, i = [], 0
    while i < len(kinds):
        unit, repeats = kinds[i:i + 1], 1
        for size in range(1, longest + 1):
            r = 1
            while kinds[i + r * size:i + (r + 1) * size] == kinds[i:i + size]:
                r += 1
            if r > 1 and r * size > repeats * len(unit):
                unit, repeats = kinds[i:i + size], r
        out.append((unit, repeats))
        i += len(unit) * repeats
    return out


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

def long_prompt(x) -> bool:
    """Whether x [B, S, ...] is one sequence of more rows than
    `WHOLE_ROWS_MAX`, in whole pieces of `MLP_ROWS`: `rows_at_a_time`'s."""
    return x.shape[0] == 1 and x.shape[1] > WHOLE_ROWS_MAX \
        and x.shape[1] % MLP_ROWS == 0


def rows_at_a_time(fn, *arrays, rows=None):
    """`fn(*arrays)` -> (out [1, rows, h], counted) for a `long_prompt`'s
    arrays [1, S, ...], `rows` (`MLP_ROWS`) rows a call: one `lax.scan` over
    the pieces, the outputs put together again and `counted` (a tree of each
    call's sums over its rows) summed."""
    s, rows = arrays[0].shape[1], rows or MLP_ROWS

    def piece(_, xs):
        return None, fn(*(a[None] for a in xs))

    _, (out, counted) = lax.scan(piece, None, tuple(
        a.reshape(s // rows, rows, *a.shape[2:]) for a in arrays))
    return out.reshape(1, s, *out.shape[3:]), \
        jax.tree.map(lambda c: c.sum(0), counted)


def expert_rows(cfg: TransformerConfig, s: int) -> int:
    """How many of a `long_prompt`'s `s` rows one call of `moe_dropless`
    takes: a piece is cut by the rows it GATHERS, `MLP_ROWS` x k a call.
    Where `held_rows_cap` answers, a call gathers its cap and so takes more
    of the prompt's rows (at a sixteenth held a quarter of its assignments:
    4,096 rows, and the held experts' weights are read twice an 8,192-row
    prompt, not eight times); never more than `WHOLE_ROWS_MAX`, because the
    whole-layout branch of its `lax.cond` is compiled, and its temporaries
    held, whether or not it ever runs. Without a cap: `MLP_ROWS`."""
    k, rows = cfg.experts_per_token, MLP_ROWS
    while 2 * rows <= WHOLE_ROWS_MAX and s % (2 * rows) == 0 and (
            held_rows_cap(cfg, 2 * rows * k) or 2 * rows * k) <= MLP_ROWS * k:
        rows *= 2
    return rows


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
    chose [B*S, k], `counted`: how many of the experts held here the real
    rows reached, int32; where `held_rows_cap` gives the layer's calls a cap
    int32 [3], behind it the rows `moe_dropless` gathered and how many of
    its calls took the whole layout, `transformer.layout_counted`). A
    `long_prompt` goes through `moe_dropless` `expert_rows` rows a call."""
    y = _rms_norm(x, p["ln_mlp"], cfg.norm_eps)
    routing = router(cfg, y.reshape(-1, y.shape[-1]), p)
    into = y
    if cfg.moe_latent:
        with jax.named_scope("lmoe.down"):
            into = jnp.einsum("bsh,hl->bsl", y,
                              p["latent_down"].astype(y.dtype))

    def experts(rows, real, w, e):
        """One call's (out, (load, `layout_counted`))."""
        out, load = moe_dropless(cfg, rows, p, real, layer, (w, e))
        return out, (load, layout_counted(cfg, e))

    if long_prompt(into):  # `moe_dropless` gathers float32 rows: `expert_rows`
        k = routing[0].shape[-1]
        routed, (load, layout) = rows_at_a_time(
            lambda rows, real, w, e: experts(rows, real, w[0], e[0]),
            into, row_mask, *(r.reshape(1, -1, k) for r in routing),
            rows=expert_rows(cfg, into.shape[1]))
    else:
        routed, (load, layout) = experts(into, row_mask, *routing)
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
    return x, load.astype(jnp.int32), routing[1], \
        reached if layout is None else jnp.concatenate([reached[None], layout])


def _take(tree, i):
    """Layer `i` of a kind's stacked parameters."""
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, i, keepdims=False), tree)


# -- the layer loop -------------------------------------------------------------

def cut(cfg: TransformerConfig) -> list:
    """`cfg`'s layers as the loop runs them, [(unit, repeats, scanned)]: the
    unit's kinds `repeats` times over, as ONE `lax.scan` or in a row. The
    period form (`lead_kind`) is its leading layer, one scan over the
    `periods` (of one, too) and the trailing layers as one unit in a row;
    the list form (`lead_kind` "": `layer_kinds` names every layer, or a
    period that `cfg.kinds` repeats) is read off the list by `runs`, behind
    the leading layer where the family has one (the one with the dense MLP,
    `cfg.dense_mlp_hidden`), and a unit that repeats is scanned. The period
    form does not go through `runs`: it would scan a tail and could cut the
    lead into the first unit, another program."""
    if cfg.lead_kind:
        tail = [(cfg.tail_kinds, 1, False)] if cfg.tail_kinds else []
        return [((cfg.lead_kind,), 1, False),
                (cfg.layer_kinds, cfg.periods, True), *tail]
    kinds = cfg.kinds  # `layer_kinds` itself, or its whole periods
    lead = 1 if cfg.dense_mlp_hidden else 0
    longest = getattr(families.of(cfg), "RUN_MAX", RUN_MAX)
    return [(kinds[:1], 1, False)] * lead + [
        (unit, n, n > 1) for unit, n in runs(kinds[lead:], longest)]


class Call(NamedTuple):
    """What the layers of one `forward_cached` share: `blocks`, the
    parameters stacked by kind; the call's `positions`, `kv_len_mask`,
    `row_mask` and `rows` (`decoding.forward_cached`'s); `sparse(n)`, sparse
    layer `n`'s parameters as `sparse_mlp` takes them."""
    blocks: dict
    positions: Any
    kv_len_mask: Any
    row_mask: Any
    rows: Any
    sparse: Callable


def forward_cached(cfg: TransformerConfig, params, tokens, positions, cache,
                   kv_len_mask, row_mask, access=_write_stack, rows=None):
    """`decoding.forward_cached` for a layer pattern whose family states one
    layer: the same arguments and results. The carry is the residual stream
    and the `KVCache` fields the family names (`CARRIED`), each written in
    place at [layer of its kind]; `family.layer(cfg, call, kind, i, n,
    carry)` is the layer of `kind`, the `i`-th of its kind and the `n`-th
    behind the leading dense layer (None AT it, 0 on without one), and
    returns (carry, what `sparse_mlp` counted or None).

    `aux` is {} without an expert layer, else {"expert_load": int32
    [num_experts], every routed assignment of the real rows summed over the
    sparse layers; "expert_choice": int32 [sparse layers, B*S, k], every
    row's experts in every layer (the k-th and (k+1)-th probability of 256
    lie close, rounding flips them, and a flipped expert moves a logit by a
    third of the logits' spread: a comparison with a reference has to know
    the sets that were taken, as with ZAYA1's one expert);
    "experts_counted": `sparse_mlp`'s summed over the layers: how many
    experts held here the real rows reached (what a step's grouped matmuls
    read) and, in int32 [3] where `held_rows_cap` caps the layers' calls,
    the rows they gathered and the calls that took the whole layout}, in
    that order (a prefill program's results are unpacked by position)."""
    only_the_stack(cfg, access)
    family, blocks = families.of(cfg), params["blocks"]
    names = expert_names(cfg)
    small = {n: a for n, a in blocks.get("sparse", {}).items()
             if n not in names}
    experts = {n: blocks["sparse"][n] for n in names} if small else {}
    call = Call(blocks, positions, kv_len_mask, row_mask, rows,
                lambda n: dict(_take(small, n), **experts))

    def unit(kinds, carry, at, n):
        """The layers `kinds` in a row, each the `at[kind]`-th of its kind
        (counted on) and the first the `n`-th behind the leading dense
        layer: (carry, None or what its expert layers counted: loads
        summed, choices stacked, `counted` summed)."""
        counted = []
        for j, kind in enumerate(kinds):
            i = at.get(kind, 0)
            at = {**at, kind: i + 1}
            carry, c = family.layer(cfg, call, kind, i,
                                    None if n is None else n + j, carry)
            if c is not None:
                counted.append(c)
        if not counted:
            return carry, None
        load, chosen, reached = zip(*counted)
        return carry, (functools.reduce(operator.add, load),
                       jnp.stack(chosen),
                       functools.reduce(operator.add, reached))

    carry = (params["embed"].astype(cfg.dtype)[tokens],
             *(getattr(cache, field) for field in family.CARRIED))
    at, n, runs_counted = {}, None if cfg.dense_mlp_hidden else 0, []
    for kinds, repeats, scanned in cut(cfg):
        if scanned:
            def repeat(carry, r, kinds=kinds, at=at, n=n):
                def from_(start, step):  # `start + r * step`, and no `0 +`
                    return start + r * step if start else r * step

                here = {kind: from_(at.get(kind, 0), kinds.count(kind))
                        for kind in dict.fromkeys(kinds)}
                return unit(kinds, carry, here, from_(n, len(kinds)))

            carry, counted = lax.scan(repeat, carry, jnp.arange(repeats))
            if counted is not None:
                # a scan stacks what its unit counted over the repeats: in
                # the layers' order the choices are repeat-major
                load, choice, reached = counted
                counted = (load.sum(0), choice.reshape(-1, *choice.shape[2:]),
                           reached.sum(0))
        else:
            carry, counted = unit(kinds, carry, at, n)
        for kind in dict.fromkeys(kinds):
            at = {**at, kind: at.get(kind, 0) + repeats * kinds.count(kind)}
        n = 0 if n is None else n + repeats * len(kinds)
        if counted is not None:
            runs_counted.append(counted)
    aux = {}
    if runs_counted:
        loads, choices, reached = zip(*runs_counted)
        aux = {"expert_load": functools.reduce(operator.add, loads),
               "expert_choice": jnp.concatenate(choices),
               "experts_counted": functools.reduce(operator.add, reached)}
    return (lm_head(cfg, params, carry[0]),
            cache._replace(**dict(zip(family.CARRIED, carry[1:]))), aux)
