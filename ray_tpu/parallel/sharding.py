"""Logical-axis sharding rules → physical NamedShardings.

The design (per the public scaling-book recipe): model code annotates
arrays with *logical* axis names ("batch", "embed", "mlp", "heads",
"seq", ...); a rule table maps logical names to mesh axes; we derive
`PartitionSpec`s / `NamedSharding`s mechanically and let XLA's GSPMD
insert the collectives.

Parameters and activations have axes of their own. A parameter's "embed"
is cut over `fsdp`; the residual stream of a training block is
``("batch", "act_rows", "act_embed")``: the batch over the data axes, the
ROWS of the sequence over ``("sequence", "tensor")`` and the hidden
dimension whole (Megatron's sequence parallelism: norms, residual adds and
what a checkpoint keeps of the stream work on a chip's share of the rows).
Where a mesh's `tensor` axis is larger than one, the projections that meet
the stream gather or scatter those rows in rings (`parallel/ring.py`).

The reference has no equivalent (its parallelism lives in torch DDP /
FSDP wrappers, SURVEY.md §2.3) — this module is what replaces all of it.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# One rule entry: logical axis name → mesh axis, tuple of mesh axes, or None.
Rules = Dict[str, Union[str, Tuple[str, ...], None]]

# Default rule table for transformer LMs. Batch is split over every
# data-like axis; parameters shard over (fsdp, tensor); sequence over the
# sequence axis (ring attention); experts over expert. "act_rows" and
# "act_embed" are the residual stream's: its rows go over `sequence`
# (outermost, ring attention's split) and `tensor`, its hidden dimension is
# whole on every chip, whatever cuts a parameter's "embed".
DEFAULT_RULES: Rules = {
    "batch": ("replica", "data", "fsdp"),
    "seq": "sequence",
    "embed": "fsdp",
    "heads": "tensor",
    "kv_heads": "tensor",
    "head_dim": None,
    "mlp": "tensor",
    "vocab": "tensor",
    "expert": "expert",
    "stage": "stage",
    "norm": None,
    "lora_rank": None,
    "act_rows": ("sequence", "tensor"),
    "act_embed": None,
}


def spec_for(logical_axes: Sequence[Optional[str]], rules: Optional[Rules] = None,
             mesh: Optional[Mesh] = None) -> P:
    """Map a tuple of logical axis names (one per array dim, None = replicated)
    to a PartitionSpec. If `mesh` is given, mesh axes of size 1 are dropped
    (XLA treats them as replicated anyway, but smaller specs compile faster
    and read better in debug output)."""
    rules = DEFAULT_RULES if rules is None else rules
    out = []
    for name in logical_axes:
        if name is None:
            out.append(None)
            continue
        target = rules.get(name)
        if target is None:
            out.append(None)
            continue
        if isinstance(target, str):
            target = (target,)
        if mesh is not None:
            target = tuple(a for a in target if mesh.shape.get(a, 1) > 1)
        if not target:
            out.append(None)
        elif len(target) == 1:
            out.append(target[0])
        else:
            out.append(tuple(target))
    # trim trailing Nones for tidier specs
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def named_sharding(mesh: Mesh, logical_axes: Sequence[Optional[str]],
                   rules: Optional[Rules] = None) -> NamedSharding:
    return NamedSharding(mesh, spec_for(logical_axes, rules, mesh))


def tree_shardings(mesh: Mesh, logical_tree: Any, rules: Optional[Rules] = None):
    """Map a pytree of logical-axis tuples to a pytree of NamedShardings.

    `logical_tree` mirrors the param pytree, with each leaf a tuple of
    logical axis names (e.g. ("embed", "mlp")).
    """
    return jax.tree.map(
        lambda axes: named_sharding(mesh, axes, rules),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x
        ),
    )


def constrain(x: jax.Array, logical_axes: Sequence[Optional[str]],
              rules: Optional[Rules] = None) -> jax.Array:
    """`with_sharding_constraint` by logical names — inside jit, under a
    Mesh context this pins intermediate activations so GSPMD doesn't
    make bad layout choices on the hot path. Outside a mesh context it
    returns `x`; a spec no mesh can take (one mesh axis on two dimensions,
    whatever its size here) raises, as `NamedSharding` does."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:  # not under a mesh context
        return x
    spec = spec_for(logical_axes, rules)
    # Inside a (partial-)manual shard_map region, constraints may only
    # reference auto axes — drop mesh axes the context binds as manual.
    manual = set(mesh.manual_axes)
    if manual:
        def _keep(entry):
            if entry is None:
                return None
            if isinstance(entry, tuple):
                kept = tuple(a for a in entry if a not in manual)
                return kept if len(kept) > 1 else (kept[0] if kept else None)
            return None if entry in manual else entry
        spec = P(*[_keep(e) for e in spec])
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def shard_batch(mesh: Mesh, batch: Any, rules: Optional[Rules] = None) -> Any:
    """Device_put a host batch (pytree of arrays, leading dim = batch)
    with the batch sharding — the input side of the data-parallel loop."""
    def _one(x):
        sh = named_sharding(mesh, ("batch",) + (None,) * (x.ndim - 1), rules)
        return jax.device_put(x, sh)
    return jax.tree.map(_one, batch)
