"""A Mamba-2 state-space mixer's recurrence (models/nemotron_h.py): one
position of it for every sequence on the state stack where it lies (TPU), and
a prefill's positions a chunk at a time.

Head h of group g = h // (heads / groups) keeps S [head_dim, state] float32:
`S <- a S + (dt x) B_g^T`, `o = S C_g` with ONE decay a head and B, C shared
by a group's heads (`D x` is the caller's). A sequence's states lie as ONE
matrix `[state, heads * head_dim]`: row n, lane (h, p) holds S_h[p, n]. So a
step's a and dt x are lane-major ROWS as the projection leaves them, B and C
columns over the sublanes, and the read-out sums over sublanes: kept `[heads,
head_dim, state]` the read-out is a reduction over LANES a head (1,024
cross-lane reductions a sequence and layer), and kept `[heads, state,
head_dim]` a head's 64 values fill half a lane word.

As XLA fuses it the read-out is a second pass over a layer's states beside
the pass that rewrites them (`ops/delta_rule.py` found the same); the kernel
holds one sequence's states in fast memory, so the stack is read once and
written once, in place.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops import attention as attention_ops

HI = lax.Precision.HIGHEST
F32 = jnp.float32


def ssm_state_update_takes(mat) -> bool:
    """Whether `ssm_state_update` runs on a state stack [N, B, state, heads *
    head_dim] of this shape and dtype, here: on a TPU (as `flash_attention`),
    float32 states of whole (8, 128) tiles."""
    _, _, n, lanes = mat.shape
    return (attention_ops._on_tpu() and mat.dtype == jnp.float32
            and n % 8 == 0 and lanes % 128 == 0)


def ssm_step(state, a, dtx, b, c):
    """The plain spelling of one position: state [B, state, heads *
    head_dim] float32; a (the decay, one value a head, along its head_dim)
    and dtx (dt x) [B, heads * head_dim]; b, c [B, groups, state]. Returns
    (state, o [B, heads * head_dim]). Elementwise products and sums: every
    product into the state is exact in float32."""
    n_b, n, lanes = state.shape
    groups = b.shape[1]

    def by_group(row):  # [B, lanes] -> [B, 1, groups, lanes / groups]
        return row.reshape(n_b, 1, groups, lanes // groups)

    def column(v):  # [B, groups, state] -> [B, state, groups, 1]
        return jnp.swapaxes(v, 1, 2)[..., None]

    state = state.reshape(n_b, n, groups, lanes // groups)
    state = state * by_group(a) + column(b) * by_group(dtx)
    o = (state * column(c)).sum(1)
    return state.reshape(n_b, n, lanes), o.reshape(n_b, lanes)


def _state_update_kernel(layer_ref, mat_ref, rows_ref, cols_ref, mat_out,
                         o_ref, *, groups):
    """One sequence: mat_ref / mat_out [state, lanes] (the same buffer of the
    stack, at [layer, b]); rows_ref [2, lanes]: a and dt x; cols_ref [2,
    state, groups]: B and C, the state dimension on sublanes, a group a lane;
    o_ref [1, lanes]. A tile of 128 lanes (two heads of 64) at a time; every
    product is float32 on the vector unit."""
    del layer_ref
    lanes = mat_ref.shape[1]
    for lo in range(0, lanes, 128):
        g = lo // (lanes // groups)
        at = slice(lo, lo + 128)
        state = mat_ref[:, at] * rows_ref[0:1, at] \
            + cols_ref[0, :, g:g + 1] * rows_ref[1:2, at]
        mat_out[:, at] = state
        o_ref[0:1, at] = jnp.sum(state * cols_ref[1, :, g:g + 1], axis=0,
                                 keepdims=True)


def ssm_state_update(mat, layer, a, dtx, b, c):
    """`mat` [N, B, state, heads * head_dim] float32, the layers' state
    stack; `layer` (int32 scalar) the layer to update; a, dtx, b, c as
    `ssm_step`'s. Returns (the stack with layer `layer` updated, in the
    buffer it came in by when the caller donates it; o [B, heads *
    head_dim]). A sequence that takes no part has a 1 and dtx 0 and keeps
    its state bit for bit.

    A Pallas kernel over the sequences: each step takes one sequence's
    states (state x lanes x 4 bytes) from [layer, b] and puts them back;
    `layer` is a scalar-prefetch operand, the other layers are never
    touched."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, n_b, n, lanes = mat.shape
    groups = b.shape[1]
    rows = jnp.stack([a, dtx], axis=1).astype(F32)  # [B, 2, lanes]
    cols = jnp.swapaxes(jnp.stack([b, c], axis=1), 2, 3).astype(F32)
    here = pl.BlockSpec((None, None, n, lanes),
                        lambda i, layer: (layer[0], i, 0, 0))
    mat, o = pl.pallas_call(
        functools.partial(_state_update_kernel, groups=groups),
        name="ssm_state_update",
        out_shape=(jax.ShapeDtypeStruct(mat.shape, mat.dtype),
                   jax.ShapeDtypeStruct((n_b, 1, lanes), F32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_b,),
            in_specs=[
                here,
                pl.BlockSpec((None, 2, lanes), lambda i, layer: (i, 0, 0)),
                pl.BlockSpec((None, 2, n, groups),
                             lambda i, layer: (i, 0, 0, 0)),
            ],
            out_specs=[
                here,
                pl.BlockSpec((None, 1, lanes), lambda i, layer: (i, 0, 0)),
            ],
        ),
        input_output_aliases={1: 0},  # the stack (behind the prefetched layer)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # a sequence's states in and out, each double buffered
            vmem_limit_bytes=4 * n * lanes * 4 + (16 << 20)),
    )(jnp.asarray(layer, jnp.int32).reshape(1), mat, rows, cols)
    return mat, o[:, 0]


def ssm_chunks(state, x, dt, log_a, b, c, chunk: int):
    """The recurrence over S positions a chunk at a time: state [B, state,
    heads * head_dim] float32 entering; x [B, S, heads, head_dim], dt and
    log_a (= -exp(A_log) dt, the decay's log) [B, S, heads], b, c [B, S,
    groups, state], all float32. Returns (state after position S - 1, o [B,
    S, heads, head_dim]).

    Exact, derived from the recurrence. With g_t the running sum of log_a
    inside a chunk and S_0 the state entering it: `o_t = exp(g_t) S_0 C_t +
    sum_{s<=t} L_ts (C_t . B_s) dt_s x_s` with the masked decay matrix `L_ts =
    exp(g_t - g_s) = prod_{s<r<=t} a_r`, and `S_C = exp(g_C) S_0 + sum_s
    exp(g_C - g_s) dt_s x_s B_s^T`. Every ratio is formed as the exponential
    of a difference of float32 sums with s <= t, at most 1. The products run
    at the highest precision. A position with dt 0 (a pad) has decay 1 and
    adds nothing: the state passes it unchanged."""
    n_b, s, h, p = x.shape
    groups, n = b.shape[2:]
    j = h // groups
    chunk = min(chunk, s)
    pad = -s % chunk
    if pad:
        x, b, c = (jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for v in (x, b, c))
        dt, log_a = (jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                     for v in (dt, log_a))
    chunks = (s + pad) // chunk

    def by_chunk(v):  # [B, S, ...] -> [chunks, B, C, ...]
        return jnp.moveaxis(v.reshape(n_b, chunks, chunk, *v.shape[2:]), 1, 0)

    t = jnp.arange(chunk)
    upto = t[:, None] >= t[None, :]

    def one(state, xs):
        x, dt, log_a, b, c = xs  # [B, C, G, J, P], [B, C, G, J], [B, C, G, N]
        g = jnp.cumsum(log_a, axis=1)
        # L[t, s] = exp(g_t - g_s) for s <= t, 0 above the diagonal
        decay = jnp.exp(jnp.where(
            upto[None, :, :, None, None],
            g[:, :, None] - g[:, None, :], -jnp.inf))  # [B, C, C, G, J]
        cb = jnp.einsum("btgn,bsgn->btsg", c, b, precision=HI)
        weights = decay * cb[..., None] * dt[:, None]  # on dt_s x_s
        o = jnp.einsum("btsgj,bsgjp->btgjp", weights, x, precision=HI) \
            + jnp.exp(g)[..., None] * jnp.einsum(
                "btgn,bngjp->btgjp", c, state, precision=HI)
        to_end = jnp.exp(g[:, -1:] - g) * dt  # exp(g_C - g_s) dt_s
        state = jnp.exp(g[:, -1])[:, None, ..., None] * state + jnp.einsum(
            "bsgn,bsgjp->bngjp", b, to_end[..., None] * x, precision=HI)
        return state, o

    state, o = lax.scan(
        one, state.reshape(n_b, n, groups, j, p),
        (by_chunk(x.reshape(n_b, -1, groups, j, p)),
         by_chunk(dt.reshape(n_b, -1, groups, j)),
         by_chunk(log_a.reshape(n_b, -1, groups, j)), by_chunk(b),
         by_chunk(c)))
    o = jnp.moveaxis(o, 0, 1).reshape(n_b, chunks * chunk, h, p)
    return state.reshape(n_b, n, h * p), o[:, :s]
