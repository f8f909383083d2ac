"""The decode step's grouped expert matmuls as a kernel
(`ops/grouped_matmul.py`), through the Pallas interpreter on the CPU, against
`lax.ragged_dot`: the four serve cells' decode shapes at toy widths and the
group layouts a router can produce."""

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
    several."""
    import jax.experimental.pallas as pl

    from ray_tpu.ops import attention as A

    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    monkeypatch.setattr(G, "BLOCK_BYTES", 384 * 256 * 4)
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
    assert G.takes(rows, gate)
    with G.paths_traced() as paths:
        plain = jax.jit(G.grouped_matmul)(rows, poisoned[0], sizes, layer)
        gated = jax.jit(lambda r, a, b, s, l: G.grouped_matmul(
            r, (a, b), s, l))(rows, *poisoned, sizes, layer)
    assert paths == {"kernel"}
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


@pytest.mark.parametrize("name", ["moe_doc_16x8_of_64",
                                  "rows_that_fill_no_tile"])
def test_the_gradient_is_ragged_dots(kernel_through_the_interpreter, name,
                                     monkeypatch):
    """Through the kernel's forward the cotangents of the rows and of both
    stacks are what `lax.ragged_dot` alone gives."""
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
    assert paths == {"kernel"}
    from ray_tpu.ops import attention as A

    monkeypatch.setattr(A, "_on_tpu", lambda: False)
    with G.paths_traced() as paths:
        want = jax.grad(loss, argnums=(0, 1, 2))(rows, gate, up)
    assert paths == {"ragged_dot"}
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5,
                                   rtol=1e-5)


def test_the_choice_is_by_the_shapes_of_the_call(
        kernel_through_the_interpreter, monkeypatch):
    """A decode step's few rows a group take the kernel; a long prompt's
    many rows a group, widths that fill no lane and every call off a TPU
    keep `lax.ragged_dot`, and say so."""
    bf16 = jnp.bfloat16
    stack = jax.ShapeDtypeStruct((26, 16, 2304, 1024), bf16)
    rows = lambda r, k=2304, dtype=bf16: jax.ShapeDtypeStruct((r, k), dtype)
    assert G.takes(rows(256), stack, 2)  # the rollout cell's decode step
    assert not G.takes(rows(16384), stack, 2)  # its 2,048-token prefill
    assert not G.takes(rows(256, dtype=jnp.float32), stack)  # mixed dtypes
    assert not G.takes(rows(32, 200), jax.ShapeDtypeStruct((4, 200, 128),
                                                           bf16))
    assert G.contraction_block(2304, 1024, 2) % 128 == 0
    assert 2304 % G.contraction_block(2304, 1024, 2) == 0
    from ray_tpu.ops import attention as A

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


def test_the_engine_says_what_each_program_was_traced_with(
        kernel_through_the_interpreter, monkeypatch):
    """A sparse model through `ContinuousBatcher`: the decode step's few
    rows a group take the kernel, the 64-token prefill's 256 rows over 16
    groups keep `lax.ragged_dot`, `moe_grouped_path` says both, and the
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
    assert paths == {"prefill_64": "ragged_dot", "decode": "kernel"}
    monkeypatch.setattr(A, "_on_tpu", lambda: False)
    want, paths = served()
    assert paths == {"prefill_64": "ragged_dot", "decode": "ragged_dot"}
    assert tokens == want
