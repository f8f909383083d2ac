"""A state-space mixer's recurrence (models/nemotron_h.py): one position of
it for every sequence on the state stack where it lies (TPU), and a prefill's
positions a chunk at a time. Mamba-2's first (ONE decay a head: a chunk has a
matrix form), then Mamba-1's (`selective_*`: the decay differs by channel AND
by state index, so a chunk has none and a prefill is a true scan).

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
from ray_tpu.ops import traced

HI = lax.Precision.HIGHEST
F32 = jnp.float32


def book(what: str, kernel: bool) -> None:
    """The caller's pick for a program's state update ("state") or prefill
    recurrence ("scan"): the kernel or the plain spelling
    (`traced.TOLD["ssm"]`)."""
    traced.book("ssm", f"{what}:{'kernel' if kernel else 'plain'}")


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


# -- Mamba-1: a decay by channel and by state index --------------------------------
#
# Channel c keeps h [state] float32: `h[n] <- exp(dt[c] A[c, n]) h[n] + dt[c]
# x[c] B[n]`, `o[c] = sum_n h[n] C[n]` (`D x` is the caller's), B and C shared
# by every channel. A sequence's states lie as ONE matrix `[state, channels]`
# as above (row n, lane c), and so does `a` = A transposed, `[state,
# channels]`: the decay of a position is the exponential of a whole tile, dt a
# lane-major row over its sublanes.

# Positions and channels a grid step of `selective_scan` at most: its blocks
# of dt, dt x and o, double buffered, with B's and C's stay under the 16 MiB
# of fast memory a program's custom call is given where the compiler fuses
# the call's producers into it (the engine's prefill: at 256 positions it
# asked 19.94 MiB and the chip's compiler refused the program)
SCAN_CHUNK = 128
SCAN_LANES = 2560
_SCAN_TILES = 4  # lane tiles whose states one inner loop carries in registers


def selective_step(state, a, dt, dtx, b, c):
    """The plain spelling of one position: state [B, state, channels]
    float32; a [state, channels] (A transposed: negative rates); dt and dtx
    (dt x) [B, channels]; b, c [B, state]. Returns (state, o [B, channels]).
    Elementwise: every product into the state is exact in float32. A
    sequence that takes no part has dt 0 and dtx 0: decay 1, nothing added."""
    state = jnp.exp(dt[:, None, :] * a) * state \
        + b[:, :, None] * dtx[:, None, :]
    return state, (state * c[:, :, None]).sum(1)


def _selective_update_kernel(layer_ref, mat_ref, a_ref, rows_ref, cols_ref,
                             mat_out, o_ref):
    """One sequence: mat_ref / mat_out [state, lanes] (the same buffer of the
    stack, at [layer, b]); a_ref [state, lanes], resident; rows_ref [2,
    lanes]: dt and dt x; cols_ref [2, state, 1]: B and C down the sublanes;
    o_ref [1, lanes]. A tile of 128 lanes at a time, float32 on the vector
    unit, the decay formed here."""
    del layer_ref
    for lo in range(0, mat_ref.shape[1], 128):
        at = slice(lo, lo + 128)
        state = jnp.exp(rows_ref[0:1, at] * a_ref[:, at]) * mat_ref[:, at] \
            + cols_ref[0] * rows_ref[1:2, at]
        mat_out[:, at] = state
        o_ref[0:1, at] = jnp.sum(state * cols_ref[1], axis=0, keepdims=True)


def selective_state_update(mat, layer, a, dt, dtx, b, c):
    """`ssm_state_update` for a decay by channel and state index: `mat` [N,
    B, state, channels] float32, `a` [state, channels], the rest as
    `selective_step`'s. Returns (the stack with layer `layer` updated in
    place, o [B, channels]). The same walk over the sequences; `a` is one
    block for every step (fetched once) and the decay `exp(dt a)` is computed
    inside, so no [B, state, channels] array exists outside the stack."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, n_b, n, lanes = mat.shape
    rows = jnp.stack([dt, dtx], axis=1).astype(F32)  # [B, 2, lanes]
    cols = jnp.stack([b, c], axis=1).astype(F32)[..., None]  # [B, 2, n, 1]
    here = pl.BlockSpec((None, None, n, lanes),
                        lambda i, layer: (layer[0], i, 0, 0))
    mat, o = pl.pallas_call(
        _selective_update_kernel,
        name="selective_state_update",
        out_shape=(jax.ShapeDtypeStruct(mat.shape, mat.dtype),
                   jax.ShapeDtypeStruct((n_b, 1, lanes), F32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_b,),
            in_specs=[
                here,
                pl.BlockSpec((n, lanes), lambda i, layer: (0, 0)),
                pl.BlockSpec((None, 2, lanes), lambda i, layer: (i, 0, 0)),
                pl.BlockSpec((None, 2, n, 1), lambda i, layer: (i, 0, 0, 0)),
            ],
            out_specs=[
                here,
                pl.BlockSpec((None, 1, lanes), lambda i, layer: (i, 0, 0)),
            ],
        ),
        input_output_aliases={1: 0},  # the stack (behind the prefetched layer)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=6 * n * lanes * 4 + (16 << 20)),
    )(jnp.asarray(layer, jnp.int32).reshape(1), mat, a.astype(F32), rows,
      cols)
    return mat, o[:, 0]


def selective_scan_takes(state, s: int) -> bool:
    """Whether `selective_scan` runs on states [B, state, channels] over `s`
    positions, here: on a TPU, float32 states of whole (8, 128) tiles whose
    channels are whole inner blocks, positions in whole sublane tiles."""
    _, n, lanes = state.shape
    return (attention_ops._on_tpu() and state.dtype == jnp.float32
            and n % 8 == 0 and lanes % (128 * _SCAN_TILES) == 0
            and s % 8 == 0)


def selective_scan_plain(state, a, dt, dtx, b, c, chunk: int):
    """The recurrence over S positions off the chip: state [B, state,
    channels] float32 entering; a [state, channels]; dt, dtx [B, S,
    channels]; b, c [B, S, state], all float32. Returns (state after position
    S - 1, o [B, S, channels]). `selective_step` a position, `chunk`
    positions an iteration of the outer loop (the inner one unrolled): no [S,
    state, channels] array exists. A position with dt 0 (a pad) passes the
    state on unchanged."""
    n_b, s, lanes = dt.shape
    chunk = min(chunk, s)
    pad = -s % chunk
    if pad:
        dt, dtx, b, c = (jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                         for v in (dt, dtx, b, c))

    def by_chunk(v):  # [B, S, ...] -> [chunks, C, B, ...]
        return jnp.moveaxis(v, 1, 0).reshape(-1, chunk, n_b, v.shape[-1])

    def one(state, xs):
        return lax.scan(lambda h, x: selective_step(h, a, *x), state, xs,
                        unroll=True)

    state, o = lax.scan(one, state, tuple(by_chunk(v)
                                          for v in (dt, dtx, b, c)))
    return state, jnp.moveaxis(o.reshape(-1, n_b, lanes), 0, 1)[:, :s]


def _selective_scan_kernel(h0_ref, a_ref, dt_ref, dtx_ref, b_ref, c_ref,
                           o_ref, h_ref):
    """One sequence, one block of channels, one chunk of positions: h0_ref /
    h_ref [state, lanes] (h_ref stays in fast memory over the chunks: the
    carried state); a_ref [state, lanes]; dt_ref, dtx_ref, o_ref [chunk,
    lanes]; b_ref, c_ref [chunk, state, 128]: a position's B (C) down the
    sublanes, the same in every lane. `_SCAN_TILES` lane tiles at a time
    keep their states in registers over the chunk's positions (their chains
    are independent: the unit's latency is hidden), eight positions an
    iteration: one aligned tile of dt and dt x read, one of o written."""
    import jax.experimental.pallas as pl

    chunk, lanes = dt_ref.shape
    width = 128 * _SCAN_TILES

    @pl.when(pl.program_id(2) == 0)
    def _():
        h_ref[...] = h0_ref[...]

    row = lax.broadcasted_iota(jnp.int32, (8, 128), 0)
    for lo in range(0, lanes, width):
        tiles = [slice(lo + 128 * w, lo + 128 * (w + 1))
                 for w in range(_SCAN_TILES)]
        rates = [a_ref[:, at] for at in tiles]

        def eight(t8, hs, tiles=tiles, rates=rates):
            base = pl.multiple_of(t8 * 8, 8)
            dt8 = [dt_ref[pl.ds(base, 8), at] for at in tiles]
            dtx8 = [dtx_ref[pl.ds(base, 8), at] for at in tiles]
            outs = [jnp.zeros((8, 128), F32)] * _SCAN_TILES
            for i in range(8):
                b_col, c_col = b_ref[base + i], c_ref[base + i]
                new = []
                for w in range(_SCAN_TILES):
                    h = jnp.exp(dt8[w][i:i + 1] * rates[w]) * hs[w] \
                        + dtx8[w][i:i + 1] * b_col
                    new.append(h)
                    o = jnp.sum(h * c_col, axis=0, keepdims=True)
                    outs[w] = jnp.where(row == i, o, outs[w])
                hs = tuple(new)
            for w, at in enumerate(tiles):
                o_ref[pl.ds(base, 8), at] = outs[w]
            return hs

        hs = lax.fori_loop(0, chunk // 8, eight,
                           tuple(h_ref[:, at] for at in tiles))
        for w, at in enumerate(tiles):
            h_ref[:, at] = hs[w]


def selective_scan(state, a, dt, dtx, b, c, chunk: int = SCAN_CHUNK):
    """`selective_scan_plain` as a Pallas kernel: a grid of (sequence, block
    of channels, chunk of positions), the last in order, a block's [state,
    lanes] float32 states resident in fast memory across its chunks and in
    registers across a chunk's positions; dt, dt x and o stream through a
    chunk at a time, B and C as one [state, 128] tile a position (spread over
    the lanes outside: the kernel then multiplies whole tiles and broadcasts
    nothing along lanes). No [S, state, channels] array exists anywhere.
    S is padded to whole chunks with dt 0 (the state passes unchanged)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_b, s, lanes = dt.shape
    n = state.shape[1]
    chunk = min(chunk, -(-s // 8) * 8)
    pad = -s % chunk
    if pad:
        dt, dtx, b, c = (jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                         for v in (dt, dtx, b, c))
    width = 128 * _SCAN_TILES
    block = max(w for w in range(width, min(lanes, SCAN_LANES) + 1, width)
                if lanes % w == 0)
    b, c = (jnp.broadcast_to(v.astype(F32)[..., None], (*v.shape, 128))
            for v in (b, c))
    states = pl.BlockSpec((None, n, block), lambda i, j, t: (i, 0, j))
    rows = pl.BlockSpec((None, chunk, block), lambda i, j, t: (i, t, j))
    cols = pl.BlockSpec((None, chunk, n, 128), lambda i, j, t: (i, t, 0, 0))
    o, state = pl.pallas_call(
        _selective_scan_kernel,
        name="selective_scan",
        out_shape=(jax.ShapeDtypeStruct(dt.shape, F32),
                   jax.ShapeDtypeStruct(state.shape, F32)),
        grid=(n_b, lanes // block, (s + pad) // chunk),
        in_specs=[states, pl.BlockSpec((n, block), lambda i, j, t: (0, j)),
                  rows, rows, cols, cols],
        out_specs=[rows, states],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            # dt, dt x, o and the two column blocks, each double buffered
            vmem_limit_bytes=2 * 4 * chunk * (3 * block + 2 * n * 128)
            + (16 << 20)),
    )(state, a.astype(F32), dt.astype(F32), dtx.astype(F32), b, c)
    return state, o[:, :s]
