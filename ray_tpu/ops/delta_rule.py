"""The gated delta rule of a linear-attention layer (models/kimi_linear.py)
on a TPU, two kernels: a decode step's (`state_update`: one position for
every sequence and head, on the state stack where it lies) and a prefill's
(`chunk_scan`: S positions a chunk at a time, a block of heads' states in
fast memory across the chunks).

**A step.** `S <- Diag(a) S`, `u = beta (v - S^T k)`, `S <- S + k u^T`, `o =
S^T q`: as XLA fuses it, the two reductions over keys are passes of their
own over a layer's states beside the pass that rewrites them (7.6 ms of a
26.7 ms step for 2.7 GB of required traffic on the v5e: PERF.md, PR 38); the
kernel holds one sequence's states in fast memory, so the stack is read once
and written once, in place.

**A prefill.** `kimi_linear.kda_chunks`' chunked algebra (its docstring is
the derivation) as a scan of XLA operations is a hundred small operations a
chunk with the state and the chunk's [C, C] matrices through HBM between
them (31.6 ms a layer of 8,192 positions at 64 heads, 1,536 steps of about
120 us a prefill: PERF.md, PR 67); the kernel's grid is (sequence, block of
heads, chunk) with the chunks in order, the block's states resident, q, k,
v and the decays streaming in a chunk at a time and o streaming out.

Which of the two a program compiled, or their plain spellings, is booked
under `traced.TOLD["delta_rule"]` (`book`).
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
SCAN_CHUNK = 64  # positions a chunk of `chunk_scan`, whole sub-chunks
SCAN_SUB = 16  # positions a sub-chunk: its [SUB, SUB, K] ratios elementwise
SCAN_HEADS = 4  # heads a grid step at most, side by side in every operation


def book(what: str, kernel: bool) -> None:
    """The caller's pick for a program's decode step ("state") or prefill
    recurrence ("scan"): the kernel or the plain spelling
    (`traced.TOLD["delta_rule"]`)."""
    traced.book("delta_rule", f"{what}:{'kernel' if kernel else 'plain'}")


def state_update_takes(mat) -> bool:
    """Whether `state_update` runs on a state stack [N, B, H, K, V] of this
    shape and dtype, here: on a TPU (as `flash_attention`), float32 states of
    whole (8, 128) tiles."""
    _, _, _, k, v = mat.shape
    return (attention_ops._on_tpu() and mat.dtype == jnp.float32
            and k % 8 == 0 and v % 128 == 0)


def _state_update_kernel(layer_ref, mat_ref, cols_ref, rows_ref, mat_out,
                         o_ref):
    """One sequence: mat_ref / mat_out [H, K, V] (the same buffer of the
    stack, at [layer, b]); cols_ref [3, K, H]: the decay a, k and q with the
    key dimension on sublanes, a head a lane; rows_ref [2, H, V]: v and beta
    (one value a head, along V); o_ref [H, V]. Every product is float32 on
    the vector unit."""
    del layer_ref
    for h in range(mat_ref.shape[0]):
        a, k, q = (cols_ref[i, :, h:h + 1] for i in range(3))  # [K, 1]
        state = mat_ref[h] * a
        u = rows_ref[1, h:h + 1, :] * (
            rows_ref[0, h:h + 1, :] - jnp.sum(k * state, axis=0,
                                              keepdims=True))  # [1, V]
        state = state + k * u
        mat_out[h] = state
        o_ref[h:h + 1, :] = jnp.sum(q * state, axis=0, keepdims=True)


def state_update(mat, layer, q, k, v, log_a, beta):
    """`mat` [N, B, H, K, V] float32, the layers' state stack; `layer` (int32
    scalar) the layer to update; q, k, log_a [B, H, K], v [B, H, V] float32;
    beta [B, H]. Returns (the stack with layer `layer` updated, in the
    buffer it came in by when the caller donates it; o [B, H, V]). A
    sequence that takes no part has log_a 0 and beta 0 and keeps its state
    bit for bit.

    A Pallas kernel over the sequences: each step takes one sequence's H
    states (H x K x V x 4 bytes) from [layer, b] and puts them back; `layer`
    is a scalar-prefetch operand, the other layers are never touched."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, b, h, kd, vd = mat.shape
    cols = jnp.stack([jnp.exp(log_a), k, q], axis=1)  # [B, 3, H, K]
    cols = jnp.swapaxes(cols, 2, 3).astype(jnp.float32)  # [B, 3, K, H]
    rows = jnp.stack(
        [v, jnp.broadcast_to(beta[..., None], v.shape)], axis=1
    ).astype(jnp.float32)  # [B, 2, H, V]
    here = pl.BlockSpec((None, None, h, kd, vd),
                        lambda i, layer: (layer[0], i, 0, 0, 0))
    mat, o = pl.pallas_call(
        _state_update_kernel,
        name="kda_state_update",
        out_shape=(jax.ShapeDtypeStruct(mat.shape, mat.dtype),
                   jax.ShapeDtypeStruct((b, h, vd), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b,),
            in_specs=[
                here,
                pl.BlockSpec((None, 3, kd, h), lambda i, layer: (i, 0, 0, 0)),
                pl.BlockSpec((None, 2, h, vd), lambda i, layer: (i, 0, 0, 0)),
            ],
            out_specs=[
                here,
                pl.BlockSpec((None, h, vd), lambda i, layer: (i, 0, 0)),
            ],
        ),
        input_output_aliases={1: 0},  # the stack (behind the prefetched layer)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # a sequence's states in and out, each double buffered
            vmem_limit_bytes=4 * h * kd * vd * 4 + (16 << 20)),
    )(jnp.asarray(layer, jnp.int32).reshape(1), mat, cols, rows)
    return mat, o


def chunk_scan_takes(state, q) -> bool:
    """Whether `chunk_scan` runs on states [B, H, K, V] and inputs [B, S, H,
    K] of these shapes and dtypes, here: on a TPU, float32, keys and values
    in whole lanes of 128 (a chunk's q and k lie [positions, K], the state
    [K, V]), at least one chunk of positions."""
    _, _, k, v = state.shape
    return (attention_ops._on_tpu() and state.dtype == jnp.float32
            and q.dtype == jnp.float32 and k % 128 == 0 and v % 128 == 0
            and q.shape[1] >= SCAN_CHUNK)


def _dot(a, b, contract=((1,), (0,))):
    """Float32 operands at the precision the plain spelling's products with
    the state have."""
    return lax.dot_general(a, b, (contract, ((), ())), precision=HI,
                           preferred_element_type=F32)


_NT = ((1,), (1,))  # a [m, k] against b [n, k]
_TN = ((0,), (0,))  # a [k, m] against b [k, n]


def _one_chunk(state_t, q, k, kb, vb, log_a, sub: int):
    """One head, one chunk of C positions in `C // sub` sub-chunks: state_t
    [V, K] entering, the state TRANSPOSED (a chunk's decay G_C lies along
    K, the lanes); q, k, kb (beta k), log_a [C, K]; vb (beta v) [C, V].
    Returns (state_t leaving, o [C, V]). `kda_chunks`' algebra with beta
    folded into the rows it scales: `(I + tril(A_kb k)) U = vb - (G kb) S_0`.

    Every decay ratio is exp of a difference that is at most 0. Inside a
    sub-chunk the ratios exp(g_t - g_s) are elementwise [sub, K] a column s.
    Between sub-chunk i and the positions before it, `A[t, s] = (x_t exp(g_t
    - g_ref)) . (k_s exp(g_ref - g_s))` about g_ref = g at the last position
    before the sub-chunk: g only falls, so both exponents are at most 0 and
    the block is a matmul. The unit-triangular system `I + L`: with D its
    diagonal sub-blocks, inverted by forward substitution (all of them a
    step, their chains independent), `N = D^-1 (L - D)` and `W = D^-1 rhs`
    (one matmul), then block forward substitution, `U_i = W_i - N[i, :i]
    U[:i]`, a sub-chunk a matmul."""
    c, kd = q.shape
    n = c // sub
    t = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    g = _dot((t >= lax.broadcasted_iota(jnp.int32, (c, c), 1)).astype(F32),
             log_a)  # the running sum of log_a down the positions: [C, K]
    decayed = jnp.exp(g)
    both = _dot(jnp.concatenate([decayed * kb, decayed * q], axis=0),
                state_t, _NT)
    rhs, o_state = vb - both[:c], both[c:]

    # the diagonal sub-blocks, [n, sub, C] with block b's columns at lanes
    # b * sub..: a_qk of s <= t, and the inverse of I + (a_kk of s < t)
    g3, k3, kb3, q3 = (a.reshape(n, sub, kd) for a in (g, k, kb, q))
    row = lax.broadcasted_iota(jnp.int32, (n, sub, kd), 1)
    at = lax.broadcasted_iota(jnp.int32, (n, sub, 1), 1)
    lane = lax.broadcasted_iota(jnp.int32, (n, sub, c), 2) \
        - sub * lax.broadcasted_iota(jnp.int32, (n, sub, c), 0)
    a_qk = jnp.zeros((n, sub, c), F32)
    inv = (lane == lax.broadcasted_iota(jnp.int32, (n, sub, c), 1)
           ).astype(F32)
    kk = []  # a_kk's columns, [n, sub, 1] each, 0 at and above the diagonal
    for j in range(sub):  # column j of every diagonal sub-block
        w = k3[:, j:j + 1] * jnp.where(
            row >= j, jnp.exp(g3 - g3[:, j:j + 1]), 0.0)
        kk.append(jnp.where(at > j, jnp.sum(kb3 * w, axis=-1, keepdims=True),
                            0.0))
        a_qk = jnp.where(lane == j, jnp.sum(q3 * w, axis=-1, keepdims=True),
                         a_qk)
    for j in range(sub - 1):  # row j of every block final at step j
        inv = inv - kk[j] * inv[:, j:j + 1, :]
    inv, a_qk = inv.reshape(c, c), a_qk.reshape(c, c)

    # the blocks below the diagonal, a row of sub-chunks a matmul
    before = lax.broadcasted_iota(jnp.int32, (c, kd), 0)
    off = [jnp.zeros((2 * sub, c), F32)]
    for i in range(1, n):
        here = slice(i * sub, (i + 1) * sub)
        ref = g[i * sub - 1:i * sub]  # [1, K]
        fall = jnp.exp(g[here] - ref)
        cols = jnp.where(before < i * sub, k * jnp.exp(ref - g), 0.0)
        off.append(_dot(jnp.concatenate(
            [kb[here] * fall, q[here] * fall], axis=0), cols, _NT))
    a_qk = a_qk + jnp.concatenate([x[sub:] for x in off], axis=0)
    # D^-1 times the right-hand side and, in the lanes beside it, times the
    # blocks below the diagonal: one matmul
    l_off = jnp.concatenate([x[:sub] for x in off], axis=0)
    both = _dot(inv, jnp.concatenate([rhs, l_off], axis=1))
    first, below = both[:, :rhs.shape[1]], both[:, rhs.shape[1]:]
    us = [first[:sub]]  # block forward substitution, a sub-chunk a matmul
    for i in range(1, n):
        here = slice(i * sub, (i + 1) * sub)
        us.append(first[here] - _dot(below[here, :i * sub],
                                     jnp.concatenate(us, axis=0)))
    u = jnp.concatenate(us, axis=0)
    to_end = jnp.exp(g[c - 1:c] - g)  # G_C / G_s
    return (decayed[c - 1:c] * state_t + _dot(u, to_end * k, _TN),
            o_state + _dot(a_qk, u))


def _chunk_scan_kernel(s0_ref, q_ref, k_ref, kb_ref, vb_ref, la_ref, o_ref,
                       s_ref, *, sub: int):
    """One sequence, one block of heads, one chunk: s0_ref / s_ref [heads, V,
    K], the states transposed (s_ref stays in fast memory over the chunks:
    the carried states); q_ref, k_ref, kb_ref, la_ref [heads, C, K]; vb_ref,
    o_ref [heads, C, V]."""
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = s0_ref[...]

    # the heads side by side in every operation: a head's chain of dependent
    # matmuls waits on the matrix unit, and the others' fill the wait (13.8
    # ms a layer of 8,192 positions at 64 heads a head after the other, 8.6
    # four abreast: PERF.md, PR 68)
    s_ref[...], o_ref[...] = jax.vmap(lambda *a: _one_chunk(*a, sub))(
        s_ref[...], q_ref[...], k_ref[...], kb_ref[...], vb_ref[...],
        la_ref[...])


@functools.partial(jax.jit, static_argnames=("chunk",))
def chunk_scan(state, q, k, v, log_a, beta, chunk: int = None):
    """The recurrence over S positions, `kimi_linear.kda_chunks`' contract:
    state [B, H, K, V] float32 ENTERING; q, k, log_a [B, S, H, K], v [B, S,
    H, V] float32; beta [B, S, H]. Returns (state after position S - 1, o [B,
    S, H, V]).

    A Pallas kernel over (sequence, block of heads, chunk), the chunks in
    order: a block's states are read from HBM before its first chunk, stay
    in fast memory (transposed, `_one_chunk`), and are written after its
    last; the inputs lie [B, H, S, D] for it (a head's chunk is one run of
    [C, D] tiles; in a prefill program the compiler writes them so where
    they are made) and pass a chunk at a time, beta folded into the rows it
    scales (beta k, beta v). The heads are an extent of the grid, `SCAN_HEADS`
    or the largest divisor of H under it a step: 32 and 64 are the same
    code. S is padded to whole chunks with beta 0 and decay 1 (the state
    passes unchanged). Jitted, so that a program's call sites of one shape
    (five in Kimi-Linear's prefill) share one trace and one lowering of the
    kernel's body: 2.4 -> 1.6 s of a set-up."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    chunk = chunk or SCAN_CHUNK
    sub = min(SCAN_SUB, chunk)
    if chunk % sub or sub % 8:
        raise ValueError(
            f"a chunk of {chunk} positions is not whole sub-chunks of {sub}, "
            "or those are not whole tiles of 8 rows")
    b, s, h, kd = q.shape
    vd = v.shape[-1]
    pad = -s % chunk
    heads = max(n for n in range(1, SCAN_HEADS + 1) if h % n == 0)
    by = beta.astype(F32)[..., None]

    def lay(a):  # [B, S, H, D] -> [B, H, S + pad, D]
        a = jnp.swapaxes(a.astype(F32), 1, 2)
        return jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else a

    states = pl.BlockSpec((None, heads, vd, kd), lambda i, j, t: (i, j, 0, 0))
    keys = pl.BlockSpec((None, heads, chunk, kd), lambda i, j, t: (i, j, t, 0))
    values = pl.BlockSpec((None, heads, chunk, vd),
                          lambda i, j, t: (i, j, t, 0))
    o, state = pl.pallas_call(
        functools.partial(_chunk_scan_kernel, sub=sub),
        name="kda_chunk_scan",
        out_shape=(jax.ShapeDtypeStruct((b, h, s + pad, vd), F32),
                   jax.ShapeDtypeStruct((b, h, vd, kd), F32)),
        grid=(b, h // heads, (s + pad) // chunk),
        in_specs=[states, keys, keys, keys, values, keys],
        out_specs=[values, states],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(jnp.swapaxes(state, 2, 3), lay(q), lay(k), lay(by * k), lay(by * v),
      lay(log_a))
    return jnp.swapaxes(state, 2, 3), jnp.swapaxes(o, 1, 2)[:, :s]
