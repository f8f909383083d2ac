"""The grouped matmuls of a sparse expert layer (models/transformer.py::
moe_dropless): rows sorted by expert times each expert's matrix, read from the
layers' stack where it lies.

Off a TPU, and wherever a group holds many rows (a long prefill), this is
`lax.ragged_dot`. A decode step's shape is another: a few rows a group (one
to three of 32-320 sorted rows over 16-128 experts), so the call is nothing
but the reading of each reached expert's matrix once, and `lax.ragged_dot`'s
tiling, the compiler's, reads Kimi-Linear's 2304 x 1024 matrices at 350 GB/s
of the v5e's 819 (510-630 at the other cells' shapes) and spends 12-22 us a
call on metadata over the whole stack's L x E groups (PERF.md, PR 41). The
kernel here is cut to that shape: the rows wait in fast memory, each reached
expert's matrix comes in blocks of whole rows of the contraction (contiguous
where the stack lies, about 1 MB), the next block in flight while this one
multiplies, across experts; an expert no row reached starts no copy: 720-755
GB/s at all four shapes and 1-3.5 us for a call that reaches no expert.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops import attention as attention_ops
from ray_tpu.ops import traced

# Bytes of one block of an expert's matrix at most: a block is [bk, n], whole
# rows of the matrix, so one contiguous piece of the stack. Two buffers a
# matrix. The first block of a call is the one copy nothing hides. On the v5e
# (my chip runs, PR 41, the four cells' decode shapes): 707-754 GB/s of
# reached experts at 5 MiB, 717-755 at 2.5, 722-755 at 1.25.
BLOCK_BYTES = 5 << 18  # 1.25 MiB
# Fast memory the kernel may ask for (the v5e has 128 MiB): a call that would
# need more keeps `lax.ragged_dot`.
VMEM_BYTES = 96 << 20

# The implementations ("kernel", "ragged_dot") that `grouped_matmul` chose
# while the body ran: this choice's share of `traced.booked()`.
paths_traced = functools.partial(traced.booked, "grouped_matmul")


def row_tile(dtype) -> int:
    """Rows of one tile of `dtype`: the sublanes of a vector register, 8 of
    32-bit values, 16 of 16-bit ones. The kernel multiplies a tile of rows
    at a time: a group of one row costs the MXU what 16 do."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def contraction_block(k: int, n: int, itemsize: int) -> int:
    """Rows of one block [bk, n] of a [k, n] matrix: the largest divisor of
    `k` in whole lanes (the rows' slice `[:, k0:k0 + bk]`) within
    `BLOCK_BYTES`; in bfloat16 2304 x 1024 gives 384 (six blocks of 0.79
    MB), 3072 x 1024 gives 512, 1024 x 3072 gives 128, 2048 x 2048 gives
    256 (1.05 MB)."""
    lanes = k // 128
    best = 1
    for d in range(1, lanes + 1):
        if lanes % d == 0 and d * 128 * n * itemsize <= BLOCK_BYTES:
            best = d
    return best * 128


def _vmem_bytes(r: int, k: int, n: int, itemsize: int, mats: int) -> int:
    bk = contraction_block(k, n, itemsize)
    return (r * k * itemsize + r * n * 4  # the rows, the result
            + mats * 2 * bk * n * itemsize  # two buffers a matrix
            + mats * r * n * 4)  # partial sums


def takes(rows, weights, mats: int = 1) -> bool:
    """Whether the kernel runs `rows` [R, k] against a stack [L, E, k, n],
    decided by what can be seen of the call: on a TPU (as `flash_attention`),
    2- or 4-byte values of one dtype in whole lanes, and at most one tile of
    rows a group on average, so that the call is bound by the reading of the
    matrices (a 2,048-token prefill's 16,384 rows over 64 groups is not: its
    products are, and `lax.ragged_dot`'s tiling is made for them)."""
    r, k = rows.shape
    groups, n = weights.shape[-3], weights.shape[-1]
    itemsize = rows.dtype.itemsize
    return (attention_ops._on_tpu() and rows.dtype == weights.dtype
            and itemsize in (2, 4) and k % 128 == 0 and n % 128 == 0
            and r <= row_tile(rows.dtype) * groups
            and _vmem_bytes(r, k, n, itemsize, mats) <= VMEM_BYTES)


def _ragged_dot(rows, stack, group_sizes, layer):
    """`lax.ragged_dot` over the stack [L, E, k, n] viewed as L * E groups of
    which only `layer`'s E hold rows: the layer is not cut out first (a
    custom call's operand cannot be fused into it: XLA would copy a layer's
    experts, 102-2,457 us a call at the four cells' shapes against 106-887
    for the call itself, PERF.md PR 41)."""
    n_layers, e = stack.shape[:2]
    if n_layers > 1:
        group_sizes = lax.dynamic_update_slice(
            jnp.zeros((n_layers * e,), group_sizes.dtype), group_sizes,
            (layer * e,))
    return lax.ragged_dot(rows, stack.reshape(n_layers * e, *stack.shape[2:]),
                          group_sizes, preferred_element_type=jnp.float32)


def _with_ragged_dot(rows, mats, group_sizes, layer, gated):
    outs = [_ragged_dot(rows, w, group_sizes, layer) for w in mats]
    if not gated:
        return outs[0]
    return (jax.nn.silu(outs[0]) * outs[1]).astype(rows.dtype)


def _kernel(layer_ref, sizes_ref, rows_ref, *refs, n_mats, bk, tile, gated):
    """rows_ref [R, k] and o_ref [R, n] in VMEM; the `n_mats` stacks [L, E,
    k, n] where XLA keeps them; wbuf [n_mats, 2, bk, n]; ids / starts [E] in
    SMEM: the groups that hold rows, in order, and each one's first row; acc
    [n_mats, R, n] float32, the partial sums over a matrix's blocks. ONE
    invocation walks the groups that hold rows and each one's k / bk blocks,
    the next block's copy (the same expert's, or the first of the next
    expert a row reached) in flight while this one multiplies."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    w_hbm, (o_ref, wbuf, sem, ids, starts, *acc) = refs[:n_mats], \
        refs[n_mats:]
    acc = acc[0] if acc else None  # a matrix of one block sums nothing
    n_groups = sizes_ref.shape[0]
    r, k = rows_ref.shape
    n = o_ref.shape[1]
    nk, n_tiles = k // bk, r // tile
    layer = layer_ref[0]

    def compact(g, carry):
        held, start = carry
        size = sizes_ref[g]

        @pl.when(size > 0)
        def _():
            ids[held] = g
            starts[held] = start

        return held + (size > 0).astype(jnp.int32), start + size

    held, _ = lax.fori_loop(0, n_groups, compact,
                            (jnp.int32(0), jnp.int32(0)))

    def copies(gi, kb, slot):
        return [pltpu.make_async_copy(
            w.at[layer, ids[gi], pl.ds(kb * bk, bk)], wbuf.at[i, slot],
            sem.at[i, slot]) for i, w in enumerate(w_hbm)]

    def start(gi, kb, slot):
        for copy in copies(gi, kb, slot):
            copy.start()

    @pl.when(held > 0)
    def _():
        start(0, 0, 0)

    # rows in no group multiply nothing and read zero; a tile's rows of
    # another group keep what that group wrote
    o_ref[...] = jnp.zeros_like(o_ref)

    def group(gi, carry):
        first = starts[gi]
        size = sizes_ref[ids[gi]]
        t0 = first // tile
        t1 = jnp.minimum((first + size - 1) // tile, n_tiles - 1)
        for kb in range(nk):
            slot = (gi * nk + kb) % 2
            if kb + 1 < nk:
                start(gi, kb + 1, 1 - slot)
            else:
                @pl.when(gi + 1 < held)
                def _():
                    start(gi + 1, 0, 1 - slot)

            for copy in copies(gi, kb, slot):
                copy.wait()

            def rows_tile(t, carry, kb=kb, slot=slot):
                at = pl.ds(pl.multiple_of(t * tile, tile), tile)
                x = rows_ref[at, kb * bk:(kb + 1) * bk]
                sums = []
                for i in range(n_mats):
                    d = jnp.dot(x, wbuf[i, slot],
                                preferred_element_type=jnp.float32)
                    if kb > 0:
                        d = d + acc[i, at, :]
                    if kb + 1 < nk:
                        acc[i, at, :] = d
                    sums.append(d)
                if kb + 1 == nk:
                    out = sums[0]
                    if gated:
                        out = out * jax.nn.sigmoid(out) * sums[1]
                    row = t * tile + lax.broadcasted_iota(
                        jnp.int32, (tile, n), 0)
                    mine = (row >= first) & (row < first + size)
                    o_ref[at, :] = jnp.where(
                        mine, out, o_ref[at, :].astype(jnp.float32)
                    ).astype(o_ref.dtype)
                return carry

            lax.fori_loop(t0, t1 + 1, rows_tile, 0)
        return carry

    lax.fori_loop(0, held, group, 0)


@functools.partial(jax.jit, static_argnames="gated")
def _with_kernel(rows, mats, group_sizes, layer, gated):
    """The Pallas call, jitted so that a program whose layer bodies make the
    same call (a pattern's six, twice each) traces and lowers the kernel
    once a shape and not once a call: 7 s of every replica's start
    otherwise (PERF.md, PR 41)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, k = rows.shape
    groups, n = mats[0].shape[1], mats[0].shape[-1]
    itemsize = rows.dtype.itemsize
    tile = row_tile(rows.dtype)
    padded = -(-r // tile) * tile
    if padded != r:
        rows = jnp.pad(rows, ((0, padded - r), (0, 0)))
    bk = contraction_block(k, n, itemsize)
    scratch = [
        pltpu.VMEM((len(mats), 2, bk, n), rows.dtype),
        pltpu.SemaphoreType.DMA((len(mats), 2)),
        pltpu.SMEM((groups,), jnp.int32),
        pltpu.SMEM((groups,), jnp.int32),
    ]
    if bk != k:
        scratch.append(pltpu.VMEM((len(mats), padded, n), jnp.float32))
    out = pl.pallas_call(
        functools.partial(_kernel, n_mats=len(mats), bk=bk, tile=tile,
                          gated=gated),
        # `ragged_dot...`: the benchmark's readers find the expert
        # operations by this name (benchmarks/moe_cost.py::EXPERT_OP)
        name="ragged_dot_gated" if gated else "ragged_dot_rows",
        out_shape=jax.ShapeDtypeStruct(
            (padded, n), rows.dtype if gated else jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(mats),
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=scratch,
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_bytes(padded, k, n, itemsize, len(mats))
            + (16 << 20)),
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      group_sizes.astype(jnp.int32), rows, *mats)
    return out[:r]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _kernel_call(rows, mats, group_sizes, layer, gated):
    return _with_kernel(rows, mats, group_sizes, layer, gated)


def _kernel_fwd(rows, mats, group_sizes, layer, gated):
    return (_with_kernel(rows, mats, group_sizes, layer, gated),
            (rows, mats, group_sizes, layer))


def _kernel_bwd(gated, res, g):
    """`lax.ragged_dot`'s own: nothing trains through the kernel's shape."""
    rows, mats, group_sizes, layer = res
    _, vjp = jax.vjp(
        lambda r, m: _with_ragged_dot(r, m, group_sizes, layer, gated),
        rows, mats)
    return (*vjp(g), None, None)


_kernel_call.defvjp(_kernel_fwd, _kernel_bwd)


def grouped_matmul(rows, weights, group_sizes, layer=None):
    """rows [R, k] in E adjoining groups (`group_sizes` [E] int32, in order
    from row 0) times each group's matrix.

    `weights` is one array [E, k, n], or with `layer` (a traced index) the
    layers' whole stack [L, E, k, n], read in place: -> [R, n] float32. Or a
    PAIR of such arrays, a gated unit's (gate, up): -> silu(rows x gate) *
    (rows x up) [R, n] in the rows' dtype, the rows read once. Float32
    products and sums either way. Rows behind the last group are in no group:
    zeros from the kernel, whatever `lax.ragged_dot` leaves otherwise.
    Differentiable (`lax.ragged_dot`'s gradient).

    The kernel where `takes` says so, `lax.ragged_dot` elsewhere; which one a
    program was traced with is collected by `paths_traced`."""
    gated = isinstance(weights, (tuple, list))
    mats = tuple(weights) if gated else (weights,)
    if layer is None:
        mats, layer = tuple(w[None] for w in mats), 0
    kernel = takes(rows, mats[0], len(mats))
    traced.book("grouped_matmul", "kernel" if kernel else "ragged_dot")
    if kernel:
        return _kernel_call(rows, mats, group_sizes, layer, gated)
    return _with_ragged_dot(rows, mats, group_sizes, layer, gated)
