"""Rings over one mesh axis: a gather or a sum of an activation's rows taken
apart into per-chip transfers that run beside the products they feed.

Where a block's rows are cut over `tensor` between its sublayers (the rule
table's "act_rows"), a projection INTO a sublayer needs every chip's rows
(an all-gather, then a product with this chip's columns) and a projection
OUT of it a sum of every chip's partial product (a product, then a
reduce-scatter). Left to the partitioner each is one whole collective the
product waits for, or that waits for the product. Here each is `n` turns,
`n` the axis' size: a chip multiplies what it holds while `lax.ppermute`
carries it, or the partial sum so far, to the next chip. A transfer and the
product beside it do not depend on each other, so the compiler's scheduler
runs the one under the other (`collective-permute-start` / `-done` around the
fusions). JAX transposes `ppermute`, so the backward of a gather ring is a
scatter ring the other way round, and the reverse.

Everything here is called INSIDE a `shard_map` region that is manual over
`axis`; a turn is named by how many hops its rows have made: turn `t` holds
the rows of chip ``(index - t) % n``. `index` is this chip's place on the
axis, handed INTO the region as data (`chip_indices`, cut over `axis`):
`lax.axis_index` in a region nested in another (the pipeline's) lowers to a
region over every other axis, the outer one's among them, which Shardy
refuses (JAX 0.9.0).
"""

from __future__ import annotations

from typing import Any, Callable, Iterator, Sequence

import jax
import jax.numpy as jnp
from jax import lax


def chip_indices(n: int) -> jax.Array:
    """[n] int32 to hand a region with the spec ``P(axis)``: inside, its one
    element is the chip's place on the axis."""
    return jnp.arange(n, dtype=jnp.int32)


def _next_chip(axis: str):
    n = lax.axis_size(axis)
    return [(i, (i + 1) % n) for i in range(n)]


def gather_turns(mine: Any, axis: str) -> Iterator[Any]:
    """Turn by turn what each chip of `axis` holds of `mine` (a pytree of
    this chip's arrays), this chip's own first: the all-gather, one chip's
    share a turn. The transfer to the next chip is asked for before a turn is
    handed out, and nothing the caller does with the turn depends on it."""
    n = lax.axis_size(axis)
    for turn in range(n):
        held = mine
        if turn + 1 < n:
            mine = jax.tree.map(
                lambda a: lax.ppermute(a, axis, _next_chip(axis)), held)
        yield held


def scatter_sum(partial: Callable[[int], jax.Array], axis: str) -> jax.Array:
    """This chip's rows of the sum over `axis` of every chip's partial
    products: the reduce-scatter, one chip's share a turn. ``partial(turn)``
    is this chip's partial for the rows `gather_turns` held at `turn`. The
    rows of the farthest chip come first; each later partial is added to
    what arrived from the chip before, and the last, for this chip's own
    rows, ends the sum here."""
    n = lax.axis_size(axis)
    total = partial(1 % n)
    for step in range(1, n):
        total = partial((step + 1) % n) + lax.ppermute(
            total, axis, _next_chip(axis))
    return total


def _at_place(index, axis: str, arranged: Callable[[int], jax.Array]):
    """``arranged(place)`` for this chip's own place on the axis. The place
    is data (`index`), so this is a select among the `n` static
    arrangements, which the compiler fuses into whatever reads the result: a
    dynamic offset into the rows' dimension cost a copy of the array a turn
    (my chip run, PR 55)."""
    whole = arranged(0)
    for place in range(1, lax.axis_size(axis)):
        whole = jnp.where(index == place, arranged(place), whole)
    return whole


def rows_of_turn(whole: jax.Array, turn: int, axis: str, index):
    """The rows of `whole` [B, rows, ...] (every chip's, in sequence order)
    that `gather_turns` held at `turn`."""
    n = lax.axis_size(axis)
    rows = whole.shape[1] // n
    return _at_place(index, axis, lambda place: lax.slice_in_dim(
        whole, (place - turn) % n * rows, ((place - turn) % n + 1) * rows,
        axis=1))


def in_sequence_order(by_turn: Sequence[jax.Array], axis: str, index):
    """What was made of each turn's rows [B, rows, ...], put where those
    rows lie in the sequence: `index` says whose rows a turn held (chip
    `place` held at turn t the rows of chip ``(place - t) % n``)."""
    n = len(by_turn)
    return _at_place(index, axis, lambda place: jnp.concatenate(
        [by_turn[(place - owner) % n] for owner in range(n)], axis=1))
