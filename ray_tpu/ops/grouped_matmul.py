"""The grouped matmuls of a sparse expert layer (models/transformer.py::
moe_dropless): rows sorted by expert times each expert's matrix, read from the
layers' stack where it lies.

Off a TPU this is `lax.ragged_dot`. On one, `takes` sends a call to one of
two kernels by its shape, and both are ONE algorithm, stream each reached
expert's matrix once and multiply its rows, whose tile and residency differ.

A decode step's shape is a few rows a group (one to three of 32-320 sorted
rows over 16-128 experts), so the call is nothing but the reading of each
reached expert's matrix once, and `lax.ragged_dot`'s tiling, the compiler's,
reads Kimi-Linear's 2304 x 1024 matrices at 350 GB/s of the v5e's 819
(510-630 at the other cells' shapes) and spends 12-22 us a call on metadata
over the whole stack's L x E groups (PERF.md, PR 41). `_kernel` is cut to that
shape: the rows wait in fast memory, each reached expert's matrix comes in
blocks of whole rows of the contraction (contiguous where the stack lies,
about 1 MB), the next block in flight while this one multiplies, across
experts; an expert no row reached starts no copy: 720-755 GB/s at all four
shapes and 1-3.5 us for a call that reaches no expert.

A prefill's shape is many rows a group (16,384 sorted rows over 64 experts; a
held share's capped 1,024-8,192 over 16, most of them in no group), too many
to wait in fast memory, and `lax.ragged_dot` reads a third of its roofline
there (3.1 ms a layer in the sparse document cell's prefill, 4.6 alone, for
1.0 ms of weights and 1.0 of products). `_tiles_kernel` turns the residency
round: a reached group's matrices wait WHOLE in fast memory (4-32 MB; wider
ones a column block at a time), the next reached group's in flight, while the
group's rows pass by them from HBM a tile of 256 at a time and the results
leave a tile at a time. A tile belongs to one group, so the MXU multiplies
about sum(ceil(size / 256)) tiles and never a tile once a group it crosses;
gate and up come from one read of the rows and the activation leaves in the
rows' dtype. It is bound by what it moves, at about 600 GB/s of reads and
writes mixed: 1.8 ms for that layer, 1.4-3.5 times `lax.ragged_dot` at every
sparse configuration's prefill shape (the table: PERF.md, PR 63), to the last
bit of its values.
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

# Rows of one tile of a call of many rows a group (`_tiles_kernel`): what the
# MXU multiplies at a time against a group's matrix, so a group costs its
# rows rounded up to this. On the v5e (my chip runs, PR 63, 16,384 rows over
# 64 groups of 2048 x 1024, gate and up): 1.42 ms at 128 (the MXU loads a
# matrix's blocks for few rows), 1.09 at 256, 1.64 at 512 (padding).
ROWS_TILE = 256
# Rows of one piece of a tile: a tile's rows come in and its results go out
# in copies of this many rows, and only those that reach the group's rows
# (22-128 rows a group where a share is held: up to 27% off such calls,
# 1-4% off a call whose groups fill their tiles).
ROWS_PIECE = 32
# Bytes of a group's matrices that `_tiles_kernel` holds at a time (both of
# a gated pair; two buffers of this): wider ones come a column block at a
# time and their rows pass once a block.
SLAB_BYTES = 32 << 20

# The implementations ("kernel", "row_tiles", "ragged_dot") that
# `grouped_matmul` chose while the body ran: this choice's share of
# `traced.booked()`.
paths_traced = functools.partial(traced.booked, "grouped_matmul")


def row_tile(dtype) -> int:
    """Rows of one tile of `dtype`: the sublanes of a vector register, 8 of
    32-bit values, 16 of 16-bit ones. The kernel multiplies a tile of rows
    at a time: a group of one row costs the MXU what 16 do."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def _whole_lanes(extent: int, bytes_a_lane: int, budget: int) -> int:
    """The largest divisor of `extent` in whole lanes of 128 of which each
    costs `bytes_a_lane` x 128 and all fit `budget`; one lane's 128 where
    none does."""
    lanes = extent // 128
    return 128 * max(d for d in range(1, lanes + 1) if lanes % d == 0 and (
        d == 1 or d * 128 * bytes_a_lane <= budget))


def contraction_block(k: int, n: int, itemsize: int) -> int:
    """Rows of one block [bk, n] of a [k, n] matrix: the largest divisor of
    `k` in whole lanes (the rows' slice `[:, k0:k0 + bk]`) within
    `BLOCK_BYTES`; in bfloat16 2304 x 1024 gives 384 (six blocks of 0.79
    MB), 3072 x 1024 gives 512, 1024 x 3072 gives 128, 2048 x 2048 gives
    256 (1.05 MB)."""
    return _whole_lanes(k, n * itemsize, BLOCK_BYTES)


def _vmem_bytes(r: int, k: int, n: int, itemsize: int, mats: int) -> int:
    bk = contraction_block(k, n, itemsize)
    return (r * k * itemsize + r * n * 4  # the rows, the result
            + mats * 2 * bk * n * itemsize  # two buffers a matrix
            + mats * r * n * 4)  # partial sums


def takes(rows, weights, mats: int = 1):
    """Which kernel runs `rows` [R, k] against a stack [L, E, k, n], or None
    (`lax.ragged_dot`), decided by what can be seen of the call: on a TPU (as
    `flash_attention`), 2- or 4-byte values of one dtype in whole lanes.
    "kernel": at most one tile of rows a group on average, so that the call
    is bound by the reading of the matrices and its rows can wait in fast
    memory (a decode step). "row_tiles": two tiles a group or more (a
    prefill's 16,384 rows over 64 groups, a held share's 4,096 over 16 of
    which most are in no group), which pass a reached group's matrices a
    tile at a time: 1.4-3.5 times `lax.ragged_dot` at every sparse
    configuration's prefill call, 256 tokens' 2,048 rows over 64 groups
    among them (PERF.md, PR 63: the table this rule is read off)."""
    r, k = rows.shape
    groups, n = weights.shape[-3], weights.shape[-1]
    itemsize = rows.dtype.itemsize
    if not (attention_ops._on_tpu() and rows.dtype == weights.dtype
            and itemsize in (2, 4) and k % 128 == 0 and n % 128 == 0):
        return None
    if r <= row_tile(rows.dtype) * groups:
        return "kernel" if _vmem_bytes(
            r, k, n, itemsize, mats) <= VMEM_BYTES else None
    # between one tile a group and two nothing was measured (a decode step
    # whose `held_rows_cap` gave way, LongCat's 384 rows over 16 groups)
    if r < 2 * row_tile(rows.dtype) * groups:
        return None
    return "row_tiles" if _tiles_vmem_bytes(
        k, n, itemsize, mats) <= VMEM_BYTES else None


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


def _compact(sizes_ref, ids, starts):
    """The groups that hold rows, in order, into `ids`, and each one's first
    row into `starts` (SMEM, [E]): -> how many there are. Both kernels walk
    these and never a group no row reached."""
    import jax.experimental.pallas as pl

    def compact(g, carry):
        held, start = carry
        size = sizes_ref[g]

        @pl.when(size > 0)
        def _():
            ids[held] = g
            starts[held] = start

        return held + (size > 0).astype(jnp.int32), start + size

    held, _ = lax.fori_loop(0, sizes_ref.shape[0], compact,
                            (jnp.int32(0), jnp.int32(0)))
    return held


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
    r, k = rows_ref.shape
    n = o_ref.shape[1]
    nk, n_tiles = k // bk, r // tile
    layer = layer_ref[0]

    held = _compact(sizes_ref, ids, starts)

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


def _tiles_kernel(layer_ref, sizes_ref, rows_hbm, *refs, n_mats, bn, tm,
                  piece, align, gated):
    """The call of many rows a group: rows_hbm [R, k], the `n_mats` stacks
    [L, E, k, n] and o_hbm [R, n] all where XLA keeps them. ONE invocation
    walks the groups that hold rows; a group's matrices wait whole in wbuf
    [n_mats, 2, k, bn] (a column block of them where they are wider than
    `SLAB_BYTES`: the outermost loop) while its rows pass through xbuf [2,
    tm, k] a tile at a time and its results leave through obuf [2, tm, bn];
    the next tile's rows, the next reached group's matrices and the last
    tile's results are in flight while this tile multiplies. The call is
    bound by what it moves (about 600 GB/s of the v5e's 819, reads and
    writes mixed: PERF.md, PR 63), so a tile's rows come and go in pieces of
    `piece` rows and only the pieces that hold rows of the group.

    A tile belongs to ONE group: a group's tiles start at its first row
    rounded down to `align` (a copy starts at whole sublanes), so a tile
    that also holds the neighbours' rows multiplies for this group alone
    and the call makes about sum(ceil(size / tm)) visits, `visits_ref`'s
    count, and never tiles x groups crossed. What a tile's window holds of
    EARLIER rows it takes from the tile written before it (`head`), whose
    window always reaches that far back, and copies run out in the order
    of the visits: a later tile's copy lands on an earlier one's."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    w_hbm = refs[:n_mats]
    (o_hbm, visits_ref, wbuf, xbuf, obuf, zbuf, wsem, xsem, osem, zsem, ids,
     starts) = refs[n_mats:]
    r, n = o_hbm.shape
    last_window = r - tm
    layer = layer_ref[0]
    held = _compact(sizes_ref, ids, starts)

    def floor(row):
        return row // align * align

    def span(gi):
        first = starts[gi]
        return first, first + sizes_ref[ids[gi]]

    def visit_of(end, begin):
        """Where the tile from row `begin` of the group that ends at `end`
        lies (the last one starts earlier, inside the array) and how many
        pieces of it reach the group's rows."""
        window = jnp.minimum(begin, last_window)
        rows = jnp.minimum(end, begin + tm) - window
        return window, (rows + piece - 1) // piece

    def w_copies(nb, gi, slot):
        return [pltpu.make_async_copy(
            w.at[layer, ids[gi], :, nb * bn:(nb + 1) * bn], wbuf.at[i, slot],
            wsem.at[i, slot]) for i, w in enumerate(w_hbm)]

    def pieces(visit, copy, act):
        """`act` ("start" or "wait") on the copies of a visit's pieces."""
        window, count = visit

        def one(i, carry):
            at = pl.multiple_of(i * piece, piece)
            getattr(copy(pl.multiple_of(window + at, align), at), act)()
            return carry

        lax.fori_loop(0, count, one, 0)

    def x_copy(slot):
        return lambda row, at: pltpu.make_async_copy(
            rows_hbm.at[pl.ds(row, piece)], xbuf.at[slot, pl.ds(at, piece)],
            xsem.at[slot])

    def o_copy(nb, slot):
        return lambda row, at: pltpu.make_async_copy(
            obuf.at[slot, pl.ds(at, piece)],
            o_hbm.at[pl.ds(row, piece), nb * bn:(nb + 1) * bn], osem.at[0])

    def z_copy(row, rows, nb):
        return pltpu.make_async_copy(
            zbuf.at[0:rows],
            o_hbm.at[pl.ds(pl.multiple_of(row, align), rows),
                     nb * bn:(nb + 1) * bn], zsem.at[0])

    @pl.when(held > 0)
    def _():
        for copy in w_copies(0, 0, 0):
            copy.start()
        pieces(visit_of(span(0)[1], 0), x_copy(0), "start")

    zbuf[...] = jnp.zeros_like(zbuf)
    n_blocks = n // bn
    # visits so far, and the last one (its window, its pieces)
    carry = jnp.int32(0), (jnp.int32(0), jnp.int32(0))
    for nb in range(n_blocks):
        def group(gi, carry, nb=nb):
            first, end = span(gi)
            n_tiles = (end - floor(first) + tm - 1) // tm
            wslot = (nb * held + gi) % 2

            @pl.when(gi + 1 < held)
            def _():
                for copy in w_copies(nb, gi + 1, 1 - wslot):
                    copy.start()

            if nb + 1 < n_blocks:
                @pl.when(gi + 1 == held)
                def _():
                    for copy in w_copies(nb + 1, 0, 1 - wslot):
                        copy.start()

            for copy in w_copies(nb, gi, wslot):
                copy.wait()
            # the group after this one: the next that holds rows, or the
            # first again for the next column block
            more = (gi + 1 < held) | (nb + 1 < n_blocks)
            then_first, then_end = span(jnp.where(gi + 1 < held, gi + 1, 0))

            def tile(j, carry):
                visits, before = carry
                begin = floor(first) + j * tm
                window, count = visit = visit_of(end, begin)
                slot = visits % 2
                within = j + 1 < n_tiles
                follows = visit_of(jnp.where(within, end, then_end),
                                   jnp.where(within, begin + tm,
                                             floor(then_first)))

                @pl.when(within | more)
                def _():
                    pieces(follows, x_copy(1 - slot), "start")

                pieces(visit, x_copy(slot), "wait")
                x = xbuf[slot]
                out = jnp.dot(x, wbuf[0, wslot],
                              preferred_element_type=jnp.float32)
                if gated:
                    out = out * jax.nn.sigmoid(out) * jnp.dot(
                        x, wbuf[1, wslot], preferred_element_type=jnp.float32)
                lo, hi = jnp.maximum(first, begin), jnp.minimum(
                    end, begin + tm)
                row = window + lax.broadcasted_iota(jnp.int32, (tm, bn), 0)
                obuf[slot] = jnp.where((row >= lo) & (row < hi), out,
                                       0.0).astype(obuf.dtype)

                def head(c, carry):
                    at = pl.ds(pl.multiple_of(c * align, align), align)
                    was = pl.ds(pl.multiple_of(
                        window - before[0] + c * align, align), align)
                    row = window + c * align + lax.broadcasted_iota(
                        jnp.int32, (align, bn), 0)
                    obuf[slot, at, :] = jnp.where(
                        row < lo, obuf[1 - slot, was, :], obuf[slot, at, :])
                    return carry

                lax.fori_loop(0, (lo - window + align - 1) // align, head, 0)

                @pl.when(visits > 0)
                def _():
                    pieces(before, o_copy(nb, 1 - slot), "wait")

                pieces(visit, o_copy(nb, slot), "start")
                return visits + 1, visit

            return lax.fori_loop(0, n_tiles, tile, carry)

        carry = lax.fori_loop(0, held, group, carry)
        # rows behind the last group read zero: whole tiles, then the rest
        # in halves of a tile
        visits, (window, count) = carry
        behind = jnp.where(held > 0, window + count * piece, 0)
        whole = (r - behind) // tm

        rest, half, halves = (r - behind) % tm, tm // 2, []
        while half >= align:
            row = behind + whole * tm + rest // (2 * half) * (2 * half)
            halves.append(((rest // half) % 2 == 1, z_copy(row, half, nb)))
            half //= 2

        def zeros(act, nb=nb, behind=behind, whole=whole, halves=halves):
            def one(i, carry):
                getattr(z_copy(behind + i * tm, tm, nb), act)()
                return carry

            lax.fori_loop(0, whole, one, 0)
            for there, copy in halves:
                pl.when(there)(getattr(copy, act))

        zeros("start")
        zeros("wait")

    visits, before = carry

    @pl.when(visits > 0)
    def _():
        pieces(before, o_copy(0, 0), "wait")

    visits_ref[0] = visits


def column_block(k: int, n: int, itemsize: int, mats: int) -> int:
    """Columns of the block [k, bn] of a group's matrices that
    `_tiles_kernel` holds at a time: all `n` where the `mats` matrices fit
    `SLAB_BYTES`, else the largest divisor in whole lanes that does (LongCat's
    gate and up, 6144 x 2048 each: two blocks of 1024)."""
    return _whole_lanes(n, mats * k * itemsize, SLAB_BYTES)


def _tiles_vmem_bytes(k: int, n: int, itemsize: int, mats: int) -> int:
    tm, bn = ROWS_TILE, column_block(k, n, itemsize, mats)
    return (2 * mats * k * bn * itemsize + 2 * tm * k * itemsize  # wbuf, xbuf
            + 3 * tm * bn * 4  # obuf, zbuf
            + (mats + 2) * tm * bn * 4)  # a tile's products and its mask


@functools.partial(jax.jit, static_argnames="gated")
def _with_tiles(rows, mats, group_sizes, layer, gated):
    """`_tiles_kernel`'s Pallas call -> (the result, how many tiles it
    visited [1] int32); jitted for what `_with_kernel` is."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, k = rows.shape
    groups, n = mats[0].shape[1], mats[0].shape[-1]
    itemsize = rows.dtype.itemsize
    tm, align = ROWS_TILE, row_tile(rows.dtype)
    padded = max(-(-r // align) * align, tm)
    if padded != r:  # no call of a model's: a toy's
        rows = jnp.pad(rows, ((0, padded - r), (0, 0)))
    bn = column_block(k, n, itemsize, len(mats))
    out_dtype = rows.dtype if gated else jnp.float32
    out, visits = pl.pallas_call(
        functools.partial(_tiles_kernel, n_mats=len(mats), bn=bn, tm=tm,
                          piece=max(ROWS_PIECE, align), align=align,
                          gated=gated),
        # `ragged_dot...`, as the other kernel's
        name="ragged_dot_gated_tiles" if gated else "ragged_dot_rows_tiles",
        out_shape=(jax.ShapeDtypeStruct((padded, n), out_dtype),
                   jax.ShapeDtypeStruct((1,), jnp.int32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * (1 + len(mats)),
            out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec(memory_space=pltpu.SMEM)),
            scratch_shapes=[
                pltpu.VMEM((len(mats), 2, k, bn), rows.dtype),
                pltpu.VMEM((2, tm, k), rows.dtype),
                pltpu.VMEM((2, tm, bn), out_dtype),
                pltpu.VMEM((tm, bn), out_dtype),
                pltpu.SemaphoreType.DMA((len(mats), 2)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((1,)),
                pltpu.SemaphoreType.DMA((1,)),
                pltpu.SMEM((groups,), jnp.int32),
                pltpu.SMEM((groups,), jnp.int32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_tiles_vmem_bytes(k, n, itemsize, len(mats))
            + (16 << 20)),
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      group_sizes.astype(jnp.int32), rows, *mats)
    return out[:r], visits


def _with(path, rows, mats, group_sizes, layer, gated):
    if path == "row_tiles":
        return _with_tiles(rows, mats, group_sizes, layer, gated)[0]
    return _with_kernel(rows, mats, group_sizes, layer, gated)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _kernel_call(rows, mats, group_sizes, layer, gated, path):
    return _with(path, rows, mats, group_sizes, layer, gated)


def _kernel_fwd(rows, mats, group_sizes, layer, gated, path):
    return (_with(path, rows, mats, group_sizes, layer, gated),
            (rows, mats, group_sizes, layer))


def _kernel_bwd(gated, path, res, g):
    """`lax.ragged_dot`'s own: nothing trains through the kernels' shapes."""
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
    zeros from the kernels, whatever `lax.ragged_dot` leaves otherwise.
    Differentiable (`lax.ragged_dot`'s gradient).

    One of the two kernels where `takes` names it, `lax.ragged_dot`
    elsewhere; which a program was traced with ("kernel", "row_tiles",
    "ragged_dot") is collected by `paths_traced`."""
    gated = isinstance(weights, (tuple, list))
    mats = tuple(weights) if gated else (weights,)
    if layer is None:
        mats, layer = tuple(w[None] for w in mats), 0
    path = takes(rows, mats[0], len(mats))
    traced.book("grouped_matmul", path or "ragged_dot")
    if path:
        return _kernel_call(rows, mats, group_sizes, layer, gated, path)
    return _with_ragged_dot(rows, mats, group_sizes, layer, gated)
