"""A sparse layer's grouped expert matmuls as kernels
(`ops/grouped_matmul.py`), through the Pallas interpreter on the CPU, against
`lax.ragged_dot`: the four serve cells' decode shapes at toy widths (the
kernel that keeps the rows in fast memory), a prefill's many rows a group
(the one that passes them a tile at a time) and the group layouts a router
can produce."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ray_tpu.ops import grouped_matmul as G


@pytest.fixture
def kernel_through_the_interpreter(monkeypatch):
    """The chip's path on the CPU: `_on_tpu` says yes (steered here, not by
    an option of the program), the Pallas call runs interpreted, and a block
    is 384 rows of a 256-wide float32 matrix, so that a toy matrix has
    several; a call of many rows a group passes them 32 at a time, and a
    gated float32 pair of 256 x 256 comes in two column blocks."""
    import jax.experimental.pallas as pl

    from ray_tpu.ops import attention as A

    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    monkeypatch.setattr(G, "BLOCK_BYTES", 384 * 256 * 4)
    monkeypatch.setattr(G, "ROWS_TILE", 32)
    monkeypatch.setattr(G, "ROWS_PIECE", 16)
    monkeypatch.setattr(G, "SLAB_BYTES", 2 * 256 * 128 * 4)
    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))


def _routed(tokens, k, experts, held, seed):
    """Group sizes [held] as `moe_dropless` counts them: every token chooses
    `k` distinct experts of `experts`; the first `held` are here, the
    assignments to the others lie behind the last group."""
    rng = np.random.default_rng(seed)
    picks = np.concatenate([rng.choice(experts, k, replace=False)
                            for _ in range(tokens)])
    return np.bincount(picks[picks < held], minlength=held), tokens * k


def _sizes(case):
    layout, rows = case["sizes"], case.get("rows")
    if callable(layout):
        return layout()
    return np.asarray(layout), rows


# name: sizes (or how a router draws them) and rows, contraction, width, dtype
CASES = {
    # the four cells: tokens x k of all experts, the held ones' groups
    "reason_32x1_of_16": dict(sizes=lambda: _routed(32, 1, 16, 16, 1),
                              k=256, n=256, dtype=jnp.bfloat16),
    "moe_doc_16x8_of_64": dict(sizes=lambda: _routed(16, 8, 64, 64, 2),
                               k=256, n=128, dtype=jnp.bfloat16),
    "code_32x10_held_half": dict(sizes=lambda: _routed(32, 10, 64, 32, 3),
                                 k=384, n=128, dtype=jnp.bfloat16),
    "rollout_32x8_held_a_sixteenth": dict(
        sizes=lambda: _routed(32, 8, 256, 16, 4),
        # 2304-like: nine lanes' worth, three blocks of three, so that a
        # group's first block lands in either buffer
        k=1152, n=256, dtype=jnp.bfloat16),
    "empty_groups_between_full_ones": dict(
        sizes=[0, 9, 0, 0, 16, 1, 0, 6], rows=32, k=256, n=128,
        dtype=jnp.float32),
    "all_rows_in_one_group": dict(
        sizes=[0, 0, 32, 0], rows=32, k=1152, n=128, dtype=jnp.float32),
    "rows_behind_the_last_group": dict(
        sizes=[3, 0, 2, 0], rows=24, k=128, n=128, dtype=jnp.bfloat16),
    "no_group_holds_a_row": dict(
        sizes=[0, 0, 0, 0], rows=16, k=256, n=128, dtype=jnp.bfloat16),
    "rows_that_fill_no_tile": dict(
        sizes=[2, 5, 0, 4], rows=11, k=1152, n=256, dtype=jnp.float32),
    # many rows a group: they pass the matrices a tile of 32 at a time
    "tiles_prefill_64x8_of_8": dict(
        sizes=lambda: _routed(64, 8, 8, 8, 5), k=256, n=128,
        dtype=jnp.bfloat16, path="row_tiles"),
    "tiles_empty_groups_a_group_of_one_row_rows_behind_the_last": dict(
        sizes=[40, 0, 3, 1, 100, 0, 57, 31], rows=256, k=256, n=256,
        dtype=jnp.float32, path="row_tiles"),  # gated: two column blocks
    "tiles_sizes_and_rows_no_multiple_of_the_tile": dict(
        sizes=[5, 0, 70, 2], rows=77, k=128, n=128, dtype=jnp.float32,
        path="row_tiles"),
    "tiles_a_held_share_most_rows_in_no_group": dict(
        sizes=lambda: _routed(256, 8, 256, 16, 6), k=128, n=256,
        dtype=jnp.bfloat16, path="row_tiles"),
    # the last group's window would pass the last row: it starts earlier and
    # takes everything before its rows from the tile written before it
    "tiles_the_last_window_reaches_back": dict(
        sizes=[3, 0, 250, 3], rows=256, k=128, n=128, dtype=jnp.bfloat16,
        path="row_tiles"),
    "tiles_no_group_holds_a_row": dict(
        sizes=[0, 0, 0, 0], rows=128, k=128, n=128, dtype=jnp.bfloat16,
        path="row_tiles"),
}


def _inputs(case, layers=3, seed=0):
    sizes, r = _sizes(case)
    key = jax.random.key(seed)
    dtype, e = case["dtype"], len(sizes)
    rows = jax.random.normal(key, (r, case["k"]), dtype)
    gate, up = (jax.random.normal(jax.random.fold_in(key, i),
                                  (layers, e, case["k"], case["n"]), dtype)
                * case["k"] ** -0.5 for i in (1, 2))
    return rows, gate, up, jnp.asarray(sizes, jnp.int32)


def _close(got, want, dtype):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    else:  # as for the attention kernels: one rounding to bf16, another order
        assert np.linalg.norm(got - want) <= 4e-3 * np.linalg.norm(want) + 1e-9
        np.testing.assert_allclose(got, want, rtol=2.0 ** -6, atol=2.0 ** -8)


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_kernel_gives_ragged_dots_values(
        kernel_through_the_interpreter, name):
    """One matrix a call against `lax.ragged_dot` on the layer cut out, and
    gate and up in one call against two calls and the gated unit around
    them; `layer` is traced and every other layer's weights are NaN, so a
    block read from the wrong place shows; rows behind the last group come
    back zero."""
    case = CASES[name]
    rows, gate, up, sizes = _inputs(case)
    layer, held = 1, int(sizes.sum())
    poisoned = [w.at[jnp.asarray([0, 2])].set(jnp.nan) for w in (gate, up)]
    path = case.get("path", "kernel")
    assert G.takes(rows, gate) == G.takes(rows, gate, 2) == path
    with G.paths_traced() as paths:
        plain = jax.jit(G.grouped_matmul)(rows, poisoned[0], sizes, layer)
        gated = jax.jit(lambda r, a, b, s, l: G.grouped_matmul(
            r, (a, b), s, l))(rows, *poisoned, sizes, layer)
    assert paths == {path}
    assert plain.dtype == jnp.float32 and gated.dtype == rows.dtype
    want_gate, want_up = (lax.ragged_dot(
        rows, w[layer], sizes, preferred_element_type=jnp.float32)
        for w in (gate, up))
    _close(plain[:held], want_gate[:held], jnp.float32)
    _close(gated[:held],
           (jax.nn.silu(want_gate) * want_up).astype(rows.dtype)[:held],
           case["dtype"])
    assert not np.asarray(plain[held:]).any()
    assert not np.asarray(gated[held:], np.float32).any()
    # the same from a stack that is one layer, no index given
    alone = jax.jit(lambda r, w, s: G.grouped_matmul(r, w, s))(
        rows, gate[layer], sizes)
    np.testing.assert_array_equal(np.asarray(alone), np.asarray(plain))


@pytest.mark.parametrize("name", [
    "moe_doc_16x8_of_64", "rows_that_fill_no_tile",
    "tiles_empty_groups_a_group_of_one_row_rows_behind_the_last",
    "tiles_sizes_and_rows_no_multiple_of_the_tile"])
def test_the_gradient_is_ragged_dots(kernel_through_the_interpreter, name,
                                     monkeypatch):
    """Through either kernel's forward the cotangents of the rows and of
    both stacks are what `lax.ragged_dot` alone gives."""
    case = CASES[name]
    rows, gate, up, sizes = _inputs(case, layers=2)
    rows, gate, up = (a.astype(jnp.float32) for a in (rows, gate, up))
    held = int(sizes.sum())
    weight = jax.random.normal(jax.random.key(9), (rows.shape[0], case["n"]))
    weight = weight.at[held:].set(0.0)  # rows of no group carry no gradient

    def loss(r, a, b):
        act = G.grouped_matmul(r, (a, b), sizes, 1)
        return (act * weight).sum() + (
            G.grouped_matmul(r, a, sizes, 1) * weight).sum()

    with G.paths_traced() as paths:
        got = jax.grad(loss, argnums=(0, 1, 2))(rows, gate, up)
    assert paths == {case.get("path", "kernel")}
    from ray_tpu.ops import attention as A

    monkeypatch.setattr(A, "_on_tpu", lambda: False)
    with G.paths_traced() as paths:
        want = jax.grad(loss, argnums=(0, 1, 2))(rows, gate, up)
    assert paths == {"ragged_dot"}
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5,
                                   rtol=1e-5)


def test_the_choice_is_by_the_shapes_of_the_call(monkeypatch):
    """A decode step's few rows a group take the kernel that keeps them in
    fast memory, a long prompt's many rows a group the one that passes them
    a tile at a time (a held share's capped call too, and a matrix wider
    than `SLAB_BYTES` in column blocks); widths that fill no lane, mixed
    dtypes and every call off a TPU keep `lax.ragged_dot`, and say so."""
    from ray_tpu.ops import attention as A

    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    bf16 = jnp.bfloat16
    stack = jax.ShapeDtypeStruct((26, 16, 2304, 1024), bf16)
    rows = lambda r, k=2304, dtype=bf16: jax.ShapeDtypeStruct((r, k), dtype)
    assert G.takes(rows(256), stack, 2) == "kernel"  # the rollout cell's step
    # its 2,048-token prefill: the capped call and the whole layout
    assert G.takes(rows(4096), stack, 2) == "row_tiles"
    assert G.takes(rows(16384), stack, 2) == "row_tiles"
    # the sparse document cell's 16,384 rows over 64 groups, up and down
    olmoe = lambda k, n: jax.ShapeDtypeStruct((8, 64, k, n), bf16)
    assert G.takes(rows(16384, 2048), olmoe(2048, 1024), 2) == "row_tiles"
    assert G.takes(rows(16384, 1024), olmoe(1024, 2048)) == "row_tiles"
    assert G.takes(rows(128, 2048), olmoe(2048, 1024), 2) == "kernel"
    # LongCat's gate and up, 2 x 6144 x 2048: two column blocks of 1024
    assert G.column_block(6144, 2048, 2, 2) == 1024
    assert G.column_block(2048, 6144, 2, 1) == 6144
    longcat = jax.ShapeDtypeStruct((4, 16, 6144, 2048), bf16)
    assert G.takes(rows(1024, 6144), longcat, 2) == "row_tiles"
    # its decode step when the cap gives way, 384 rows over 16 groups:
    # between one tile a group and two nothing was measured
    assert G.takes(rows(64, 6144), longcat, 2) == "kernel"
    assert G.takes(rows(384, 6144), longcat, 2) is None
    assert not G.takes(rows(256, dtype=jnp.float32), stack)  # mixed dtypes
    assert not G.takes(rows(32, 200), jax.ShapeDtypeStruct((4, 200, 128),
                                                           bf16))
    assert G.contraction_block(2304, 1024, 2) % 128 == 0
    assert 2304 % G.contraction_block(2304, 1024, 2) == 0
    assert [G.contraction_block(*kn, 2) for kn in (
        (2304, 1024), (3072, 1024), (1024, 3072), (2048, 2048))] == [
            384, 512, 128, 256]  # as its docstring says
    monkeypatch.setattr(A, "_on_tpu", lambda: False)
    assert not G.takes(rows(256), stack, 2)
    case = CASES["rows_behind_the_last_group"]
    r, gate, _, sizes = _inputs(case)
    with G.paths_traced() as paths:
        out = G.grouped_matmul(r, gate, sizes, 2)
    assert paths == {"ragged_dot"}
    _close(out[:5], lax.ragged_dot(r, gate[2], sizes,
                                   preferred_element_type=jnp.float32)[:5],
           jnp.float32)


@pytest.mark.parametrize("name", sorted(
    n for n, case in CASES.items() if case.get("path") == "row_tiles"))
def test_a_row_tile_multiplies_for_one_group(
        kernel_through_the_interpreter, name):
    """The tiles the kernel visited (its own count) against the layout: a
    group costs its rows rounded up to the tile, counted from its first row
    rounded down to whole sublanes, and a tile that holds two groups' rows is
    NOT multiplied once a group it crosses: with aligned groups exactly
    sum(ceil(size / tile)), never more than one tile a group over it."""
    case = CASES[name]
    rows, gate, up, sizes = _inputs(case)
    tile, align = G.ROWS_TILE, G.row_tile(case["dtype"])
    _, visits = G._with_tiles(rows, (gate,), sizes, 1, False)
    sizes = np.asarray(sizes)
    first = np.cumsum(sizes) - sizes
    reached = sizes > 0
    least = int(np.sum(-(-sizes // tile)))
    assert int(visits[0]) == int(np.sum(
        -(-(sizes + first % align) // tile)[reached]))
    assert least <= int(visits[0]) <= least + int(reached.sum())
    # what tiles at multiples of `tile` cost: one visit a group a tile holds
    crossed = int(np.sum(((first + sizes - 1) // tile - first // tile
                          + 1)[reached]))
    assert int(visits[0]) <= crossed
    aligned = jnp.asarray(sizes // align * align)
    _, visits = G._with_tiles(rows, (gate,), aligned, 1, False)
    assert int(visits[0]) == int(np.sum(-(-np.asarray(aligned) // tile)))


def test_the_engine_says_what_each_program_was_traced_with(
        kernel_through_the_interpreter, monkeypatch):
    """A sparse model through `ContinuousBatcher`: the decode step's few
    rows a group take the kernel, the 64-token prefill's 256 rows over 16
    groups the one of row tiles, `moe_grouped_path` says both, and the
    greedy tokens are the ones the same engine gives off a TPU, where every
    program reads "ragged_dot"."""
    from ray_tpu.models import transformer as T
    from ray_tpu.models.continuous_batching import ContinuousBatcher
    from ray_tpu.models.decoding import SamplingParams
    from ray_tpu.ops import attention as A

    cfg = T.config("olmoe_debug", mlp_hidden=128)
    params = T.init_params(cfg, jax.random.key(3))
    prompt = np.random.default_rng(5).integers(0, 512, 40).tolist()

    def served():
        batcher = ContinuousBatcher(cfg, params, max_len=64, slots=4)
        try:
            return batcher.submit(prompt, SamplingParams(max_tokens=6)
                                  ).result(timeout=300), \
                batcher.moe_grouped_path
        finally:
            batcher.shutdown()

    tokens, paths = served()
    assert paths == {"prefill_64": "row_tiles", "decode": "kernel"}
    monkeypatch.setattr(A, "_on_tpu", lambda: False)
    want, paths = served()
    assert paths == {"prefill_64": "ragged_dot", "decode": "ragged_dot"}
    assert tokens == want
