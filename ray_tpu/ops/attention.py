"""Attention ops, TPU-first.

Three tiers, all the same math (softmax(QK^T * scale + mask) V):

- `mha_reference`   : plain jnp, O(S^2) memory — ground truth for tests.
- `blockwise_attention` : online-softmax over KV chunks via `lax.scan` —
  O(S * block) memory, differentiable by autodiff, XLA-fusable. This is
  the building block ring attention rotates (ops/ring_attention.py).
- `flash_attention` : pallas TPU kernels for both passes
  (FlashAttention-2 forward and backward under a custom_vjp); off-TPU
  the same entry point runs blockwise. GSPMD cannot partition the
  kernels: on a mesh they are called under shard_map
  (train/step.py make_attn_fn). Their block step multiplies the operands
  in the dtype they arrive in (float32 sums), transposes no score block
  and masks only a block that the diagonal crosses or that holds padding
  (`flash_block_plan` counts them); default blocks (512, 512).

The reference framework has NO native attention (SURVEY.md §5
"Long-context: absent in the reference" — it defers to vLLM/torch).
Here it is a first-class op because the flagship models run *inside*
this framework.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

NEG_INF = -1e30


def _scale(q, sm_scale):
    return q * (sm_scale if sm_scale is not None else q.shape[-1] ** -0.5)


def mha_reference(q, k, v, causal: bool = True, sm_scale: Optional[float] = None,
                  q_offset: int = 0):
    """Plain O(S^2) attention. Shapes: q [B, Sq, H, D], k/v [B, Sk, H, D].

    `q_offset`: global position of q[0] relative to k[0] (used by ring
    attention tests and decode).
    """
    q = _scale(q, sm_scale)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        qi = jnp.arange(sq)[:, None] + q_offset
        ki = jnp.arange(sk)[None, :]
        logits = jnp.where(qi >= ki, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


def softmax_with_sink(logits, sink=None):
    """Softmax over the last axis with one more term in the denominator:
    `sink`, a logit that takes its share of the probability and carries no
    value (broadcast against `logits[..., :1]`); None: the plain softmax.
    A sink of -inf gives the plain softmax's very numbers."""
    if sink is None:
        return jax.nn.softmax(logits, axis=-1)
    m = jnp.maximum(logits.max(axis=-1, keepdims=True), sink)
    e = jnp.exp(logits - m)
    return e / (e.sum(axis=-1, keepdims=True) + jnp.exp(sink - m))


def _block_step(q, kc, vc, acc, m, l, mask=None):
    """One online-softmax accumulation step.

    q [B,Sq,H,D] fp32-scaled; kc/vc [B,Bk,H,D]; acc [B,Sq,H,D] fp32;
    m,l [B,H,Sq] fp32 running max / normalizer. Returns updated (acc,m,l).
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kc, preferred_element_type=jnp.float32)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(vc.dtype), vc).astype(jnp.float32)
    acc_new = acc * alpha.transpose(0, 2, 1)[..., None] + pv
    return acc_new, m_new, l_new


def blockwise_attention(q, k, v, causal: bool = True,
                        sm_scale: Optional[float] = None,
                        block_k: int = 512, q_offset: int = 0):
    """Memory-efficient attention: scan over KV chunks with online softmax.

    Never materializes the [Sq, Sk] matrix; autodiff through the scan
    gives a memory-efficient backward for free (combine with
    `jax.checkpoint` at the layer level for long sequences).
    """
    b, sq, h, d = q.shape
    sk, dv = k.shape[1], v.shape[-1]  # the values may have a width of their own
    block_k = min(block_k, sk)
    nblocks = (sk + block_k - 1) // block_k
    pad = nblocks * block_k - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))

    qs = _scale(q, sm_scale).astype(jnp.float32)
    kb = k.reshape(b, nblocks, block_k, h, d).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nblocks, block_k, h, dv).transpose(1, 0, 2, 3, 4)

    qi = jnp.arange(sq)[:, None] + q_offset  # global q positions

    def step(carry, inp):
        acc, m, l = carry
        blk_idx, kc, vc = inp
        ki = blk_idx * block_k + jnp.arange(block_k)[None, :]
        valid = ki < sk
        msk = valid if not causal else (qi >= ki) & valid
        msk = msk[None, None]  # [1,1,Sq,Bk]
        acc, m, l = _block_step(qs, kc, vc, acc, m, l, mask=msk)
        return (acc, m, l), None

    init = (
        jnp.zeros((b, sq, h, dv), jnp.float32),
        jnp.full((b, h, sq), NEG_INF, jnp.float32),
        jnp.zeros((b, h, sq), jnp.float32),
    )
    (acc, m, l), _ = lax.scan(step, init, (jnp.arange(nblocks), kb, vb))
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas flash kernels (TPU): one (batch*head, q block) program a grid cell
# for the forward and the dQ pass, one (batch*head, k block) program for the
# dK/dV pass, whole K/V (dK/dV: whole Q/dO) of the head resident in VMEM and
# an inner loop over its blocks.
#
# The block step does what the arithmetic asks for and nothing else:
# - q, k, v, dO reach the MXU in the dtype they arrive in and the products
#   are summed in float32 (bf16 x bf16 is exact in float32: the parent's
#   arithmetic in another order of summation); `sm_scale` multiplies the
#   float32 scores, never a bf16 input; the computed `p` and `ds` enter
#   their products in the operands' dtype (float32 for a float32 caller).
# - no score block is transposed: q-major programs contract q with k over
#   `d` for scores [block_q, block_k]; the k-major dK/dV program contracts k
#   with q over `d` for the scores' transpose [block_k, block_q] directly,
#   so `p^T dO` and `ds^T q` are plain products.
# - only a block that the diagonal crosses, or that holds padding, builds a
#   mask: `_k_blocks` / `_q_blocks` split each program's loop into blocks
#   that need none, blocks that do, and blocks that are skipped.
# - what a program carries from block to block (accumulators, the running
#   maximum and sum) lives in VMEM scratch, the row statistics a whole
#   vector register wide (`_LANES`).
# ---------------------------------------------------------------------------

FLASH_BLOCK_Q = 512
FLASH_BLOCK_K = 512
# contract the last dimension of both operands: a @ b.T without the transpose
_NT = (((1,), (1,)), ((), ()))


class FlashBlockPlan(NamedTuple):
    """Blocks of the [q blocks, k blocks] grid of one head: all of them,
    those a kernel visits (any allowed pair) and, of those, the ones it
    masks (any pair disallowed or padding)."""
    blocks: int
    visited: int
    masked: int


def _flash_blocks(sq, sk, block_q, block_k):
    """The blocks clipped to the lengths."""
    return min(block_q, sq), min(block_k, sk)


def _k_blocks(j, sq, sk, block_q, block_k, causal, xp=np):
    """The k blocks of q block `j` (forward and dQ): `(bare, end)`. Blocks
    [0, bare) hold allowed pairs only and need no mask, [bare, end) are
    crossed by the diagonal or hold padding, [end, ...) are skipped. `j` is
    an int (`flash_block_plan`) or a traced `program_id` with `xp=jnp`."""
    nk = -(-sk // block_k)
    bare = sk // block_k
    end = nk
    if causal:
        last_row = xp.minimum((j + 1) * block_q, sq) - 1
        end = xp.minimum(last_row // block_k + 1, nk)
        bare = xp.minimum((j * block_q + 1) // block_k, bare)
    if sq % block_q:  # the last q block holds padded rows
        bare = xp.where((j + 1) * block_q <= sq, bare, 0)
    return bare, end


def _q_blocks(i, sq, sk, block_q, block_k, causal, xp=np):
    """The q blocks of k block `i` (dK/dV): `(start, lo, hi, nq)`. Blocks
    [lo, hi) need no mask, [start, lo) and [hi, nq) do, [0, start) are
    skipped (wholly above the diagonal)."""
    nq = -(-sq // block_q)
    start, lo = 0, 0
    if causal:
        start = (i * block_k) // block_q
        if (-(-sk // block_k) - 1) * block_k >= sq:  # k blocks below every row
            start = xp.where(i * block_k < sq, start, nq)
        # the first q block whose first row sees the k block's last key
        lo = ((i + 1) * block_k + block_q - 2) // block_q
        lo = xp.minimum(xp.maximum(lo, start), nq)
    hi = nq
    if sq % block_q:  # the last q block holds padded rows
        hi = xp.maximum(lo, sq // block_q)
    if sk % block_k:  # the last k block holds padded keys
        whole = (i + 1) * block_k <= sk
        lo = xp.where(whole, lo, nq)
        if sq % block_q:
            hi = xp.where(whole, hi, nq)
    return start, lo, hi, nq


def flash_block_plan(sq: int, sk: int, block_q: int = FLASH_BLOCK_Q,
                     block_k: int = FLASH_BLOCK_K,
                     causal: bool = True) -> FlashBlockPlan:
    """How often each body of the kernels' block step runs a head, static at
    trace time: the kernels take their loop bounds from the same
    `_k_blocks` / `_q_blocks`. Causal 2,048 x 2,048 at (512, 512): 16
    blocks, 10 visited, 4 of them masked."""
    block_q, block_k = _flash_blocks(sq, sk, block_q, block_k)
    nq, nk = -(-sq // block_q), -(-sk // block_k)
    visited = masked = 0
    for j in range(nq):
        bare, end = _k_blocks(j, sq, sk, block_q, block_k, causal)
        visited += int(end)
        masked += int(end) - int(bare)
    return FlashBlockPlan(nq * nk, visited, masked)


def _walk(first, end, step, masked):
    """`step(i, None, masked)` for the blocks [first, end); no loop at all
    where the bounds say at trace time that it is empty."""
    if not (isinstance(first, int) and isinstance(end, int) and first >= end):
        lax.fori_loop(first, end, functools.partial(step, masked=masked), None)


def _mask(x, fill, q_dim, q_base, k_base, seq_q, seq_k, causal):
    """Score block `x` with `fill` where a pair is not allowed: q positions
    run along `q_dim` from `q_base`, keys along the other from `k_base`.
    Only the comparisons the static lengths ask for: the diagonal's if
    `causal`, a length's if its last block holds padding."""
    q_at = q_base + lax.broadcasted_iota(jnp.int32, x.shape, q_dim)
    k_at = k_base + lax.broadcasted_iota(jnp.int32, x.shape, 1 - q_dim)
    ok = None
    for asked, cond in ((causal, lambda: q_at >= k_at),
                        (seq_k % x.shape[1 - q_dim], lambda: k_at < seq_k),
                        (seq_q % x.shape[q_dim], lambda: q_at < seq_q)):
        if asked:
            ok = cond() if ok is None else ok & cond()
    return x if ok is None else jnp.where(ok, x, fill)


# A q-major program keeps its row statistics (running maximum and sum, lse,
# delta) as [block_q, _LANES] float32 with every lane of a row alike: whole
# vector registers to load, store and broadcast from, where a [block_q, 1]
# column is one lane of each. HBM holds them as rows [1, S], which the
# k-major program reads as they lie.
_LANES = 128


def _lanes(stat, n):
    """A row statistic [rows, _LANES] beside a block [rows, n]."""
    if n % _LANES:  # toy widths
        return jnp.broadcast_to(stat[:, :1], (stat.shape[0], n))
    return stat if n == _LANES else jnp.tile(stat, (1, n // _LANES))


def _diagonal():
    return (lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)
            == lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1))


def _store_row(row_ref, at, stat):
    """`stat` [n, _LANES] into `row_ref[0, at : at + n]`: 128 rows at a
    time, the diagonal of the [128, 128] tile summed over its rows (a
    select and adds; no transpose and no lane-by-lane copy)."""
    import jax.experimental.pallas as pl

    n = stat.shape[0]
    if n % _LANES:  # toy widths
        row_ref[0, pl.ds(at, n)] = stat[:, 0]
        return
    diagonal = _diagonal()
    for c in range(0, n, _LANES):
        row_ref[:, pl.ds(at + c, _LANES)] = jnp.sum(
            jnp.where(diagonal, stat[c:c + _LANES], 0.0), axis=0, keepdims=True)


def _load_row(row_ref, at, n):
    """`row_ref[0, at : at + n]` as a row statistic [n, _LANES]: the way
    back, the diagonal summed over its lanes and spread over them."""
    import jax.experimental.pallas as pl

    if n % _LANES:  # toy widths
        return jnp.broadcast_to(row_ref[0, pl.ds(at, n)][:, None], (n, _LANES))
    diagonal = _diagonal()
    return jnp.concatenate([jnp.broadcast_to(jnp.sum(
        jnp.where(diagonal, row_ref[:, pl.ds(at + c, _LANES)], 0.0),
        axis=1, keepdims=True), (_LANES, _LANES))
        for c in range(0, n, _LANES)], axis=0)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                      l_ref, *, sm_scale, block_k, causal, seq_k, seq_q):
    """One q block a program, inner loop over its k blocks with the online
    softmax's state (accumulator, running maximum and sum) in VMEM scratch."""
    import jax.experimental.pallas as pl

    block_q, d = o_ref.shape  # the values' width: the keys' may be another
    j = pl.program_id(1)
    q = q_ref[:]
    bare, end = _k_blocks(j, seq_q, seq_k, block_q, block_k, causal, xp=jnp)
    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)

    def step(i, _, masked):
        at = pl.ds(pl.multiple_of(i * block_k, block_k), block_k)
        s = lax.dot_general(q, k_ref[at, :], _NT,
                            preferred_element_type=jnp.float32) * sm_scale
        if masked:
            s = _mask(s, NEG_INF, 0, j * block_q, i * block_k, seq_q, seq_k,
                      causal)
        m = m_ref[:]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, block_k))
        alpha = jnp.exp(m - m_new)
        l_ref[:] = l_ref[:] * alpha + p.sum(axis=-1, keepdims=True)
        vc = v_ref[at, :]
        acc_ref[:] = acc_ref[:] * _lanes(alpha, d) + jnp.dot(
            p.astype(vc.dtype), vc, preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    _walk(0, bare, step, False)
    _walk(bare, end, step, True)
    l = jnp.maximum(l_ref[:], 1e-30)
    o_ref[:] = (acc_ref[:] / _lanes(l, d)).astype(o_ref.dtype)
    # logsumexp rows for the FlashAttention-2 backward: p = exp(s - lse).
    # lse_ref holds the FULL row (all q blocks of this bh program write
    # disjoint slices of one VMEM-resident block).
    _store_row(lse_ref, j * block_q, m_ref[:] + jnp.log(l))


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, acc_ref, *, sm_scale, block_k, causal, seq_k,
                         seq_q):
    """dQ = scale * sum_k [P ∘ (dO V^T − Δ)] K, one q block per program,
    inner loop over k blocks (FlashAttention-2 backward, dQ pass)."""
    import jax.experimental.pallas as pl

    block_q, d = q_ref.shape
    j = pl.program_id(1)
    q, do = q_ref[:], do_ref[:]
    lse = _lanes(_load_row(lse_ref, j * block_q, block_q), block_k)
    delta = _lanes(_load_row(delta_ref, j * block_q, block_q), block_k)
    bare, end = _k_blocks(j, seq_q, seq_k, block_q, block_k, causal, xp=jnp)
    acc_ref[:] = jnp.zeros_like(acc_ref)

    def step(i, _, masked):
        at = pl.ds(pl.multiple_of(i * block_k, block_k), block_k)
        kc, vc = k_ref[at, :], v_ref[at, :]
        s = lax.dot_general(q, kc, _NT,
                            preferred_element_type=jnp.float32) * sm_scale
        p = jnp.exp(s - lse)
        if masked:
            p = _mask(p, 0.0, 0, j * block_q, i * block_k, seq_q, seq_k,
                      causal)
        dp = lax.dot_general(do, vc, _NT, preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        acc_ref[:] += jnp.dot(ds.astype(kc.dtype), kc,
                              preferred_element_type=jnp.float32)

    _walk(0, bare, step, False)
    _walk(bare, end, step, True)
    dq_ref[:] = (acc_ref[:] * sm_scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, sm_scale, block_q,
                          causal, seq_k, seq_q):
    """dK/dV for one k block per program, inner loop over q blocks
    (FlashAttention-2 backward, dK/dV pass), on the scores' transpose
    S^T = K Q^T [block_k, block_q]:
    dV = Σ_q P^T dO;  dK = scale * Σ_q [P^T ∘ (V dO^T − Δ)] Q."""
    import jax.experimental.pallas as pl

    block_k, d = k_ref.shape
    i = pl.program_id(1)
    kc, vc = k_ref[:], v_ref[:]
    start, lo, hi, nq = _q_blocks(i, seq_q, seq_k, block_q, block_k, causal,
                                  xp=jnp)
    dk_acc[:] = jnp.zeros_like(dk_acc)
    dv_acc[:] = jnp.zeros_like(dv_acc)

    def step(j, _, masked):
        at = pl.ds(pl.multiple_of(j * block_q, block_q), block_q)
        q, do = q_ref[at, :], do_ref[at, :]
        st = lax.dot_general(kc, q, _NT,
                             preferred_element_type=jnp.float32) * sm_scale
        pt = jnp.exp(st - lse_ref[:, at])  # lse, delta: rows [1, block_q]
        if masked:
            pt = _mask(pt, 0.0, 1, j * block_q, i * block_k, seq_q, seq_k,
                       causal)
        dv_acc[:] += jnp.dot(pt.astype(do.dtype), do,
                             preferred_element_type=jnp.float32)
        dpt = lax.dot_general(vc, do, _NT, preferred_element_type=jnp.float32)
        dst = pt * (dpt - delta_ref[:, at])
        dk_acc[:] += jnp.dot(dst.astype(q.dtype), q,
                             preferred_element_type=jnp.float32)

    _walk(start, lo, step, True)
    _walk(lo, hi, step, False)
    _walk(hi, nq, step, True)
    dk_ref[:] = (dk_acc[:] * sm_scale).astype(dk_ref.dtype)
    dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


def _bhsd_to_flat(x, pad_s):
    """[B,S,H,D] -> [B*H, S+pad, D]."""
    b, s, h, d = x.shape
    if pad_s:
        x = jnp.pad(x, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
    return x.transpose(0, 2, 1, 3).reshape(b * h, s + pad_s, d)


def _flash_fwd_pallas(q, k, v, causal, sm_scale, block_q, block_k):
    """q [B, S, H, D], k [B, Sk, kvH, D], v [B, Sk, kvH, Dv]: the values may
    have a width of their own, and `H / kvH` adjoining query heads may share
    a KV head, whose K and V a program then maps by index (no head is
    repeated in HBM)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, sq, h, d = q.shape
    sk, dv, rep = k.shape[1], v.shape[-1], h // k.shape[2]
    scale = sm_scale if sm_scale is not None else d ** -0.5
    block_q, block_k = _flash_blocks(sq, sk, block_q, block_k)
    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    sqp, skp = sq + pad_q, sk + pad_k

    qf = _bhsd_to_flat(q, pad_q)
    kf = _bhsd_to_flat(k, pad_k)
    vf = _bhsd_to_flat(v, pad_k)

    grid = (b * h, sqp // block_q)
    kernel = functools.partial(
        _flash_fwd_kernel, sm_scale=scale, block_k=block_k, causal=causal,
        seq_k=sk, seq_q=sq,
    )
    # program i is head i % h of sequence i // h; its KV head i // rep
    kv_at = (lambda i, j: (i, 0, 0)) if rep == 1 else \
        (lambda i, j: (i // rep, 0, 0))
    kv_vmem = 2 * skp * (d + dv) * k.dtype.itemsize  # two buffers each
    params = {}
    if kv_vmem > FLASH_KV_VMEM_BYTES:  # past the compiler's own allowance
        params = dict(compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=kv_vmem + FLASH_BLOCKS_VMEM_BYTES))
    out, lse = pl.pallas_call(
        kernel,
        name="flash_attention_fwd",
        out_shape=(
            jax.ShapeDtypeStruct((b * h, sqp, dv), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, sqp), jnp.float32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, skp, d), kv_at),
            pl.BlockSpec((None, skp, dv), kv_at),
        ],
        out_specs=(
            pl.BlockSpec((None, block_q, dv), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, 1, sqp), lambda i, j: (i, 0, 0)),
        ),
        scratch_shapes=[pltpu.VMEM((block_q, dv), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32)],
        **params,
    )(qf, kf, vf)
    out = out.reshape(b, h, sqp, dv).transpose(0, 2, 1, 3)
    return out[:, :sq], lse


def _flash_bwd_pallas(q, k, v, o, lse, g, causal, sm_scale, block_q, block_k):
    """FlashAttention-2 backward: a dQ pass and a dK/dV pass, both pallas."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = sm_scale if sm_scale is not None else d ** -0.5
    block_q, block_k = _flash_blocks(sq, sk, block_q, block_k)
    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    sqp, skp = sq + pad_q, sk + pad_k

    qf = _bhsd_to_flat(q, pad_q)
    kf = _bhsd_to_flat(k, pad_k)
    vf = _bhsd_to_flat(v, pad_k)
    dof = _bhsd_to_flat(g, pad_q)
    # Δ_i = rowsum(dO ∘ O) (the softmax-jacobian diagonal term)
    delta = jnp.sum(
        g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    ).transpose(0, 2, 1).reshape(b * h, 1, sq)
    if pad_q:
        delta = jnp.pad(delta, ((0, 0), (0, 0), (0, pad_q)))

    dq_kernel = functools.partial(
        _flash_bwd_dq_kernel, sm_scale=scale, block_k=block_k, causal=causal,
        seq_k=sk, seq_q=sq,
    )
    dq = pl.pallas_call(
        dq_kernel,
        name="flash_attention_bwd_dq",
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        grid=(b * h, sqp // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, skp, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, skp, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, 1, sqp), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, 1, sqp), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
    )(qf, kf, vf, dof, lse, delta)

    dkv_kernel = functools.partial(
        _flash_bwd_dkv_kernel, sm_scale=scale, block_q=block_q, causal=causal,
        seq_k=sk, seq_q=sq,
    )
    dk, dv = pl.pallas_call(
        dkv_kernel,
        name="flash_attention_bwd_dkv",
        out_shape=(
            jax.ShapeDtypeStruct(kf.shape, k.dtype),
            jax.ShapeDtypeStruct(vf.shape, v.dtype),
        ),
        grid=(b * h, skp // block_k),
        in_specs=[
            pl.BlockSpec((None, sqp, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, sqp, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, 1, sqp), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, 1, sqp), lambda i, j: (i, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
        ),
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
    )(qf, kf, vf, dof, lse, delta)

    def unflat(x, s_pad, s):
        return x.reshape(b, h, s_pad, d).transpose(0, 2, 1, 3)[:, :s]

    return unflat(dq, sqp, sq), unflat(dk, skp, sk), unflat(dv, skp, sk)


def _on_tpu() -> bool:
    """Whether the default backend is a TPU. A failure to query devices
    propagates: answering "no" would run a TPU job blockwise unnoticed."""
    return jax.devices()[0].platform == "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: int = FLASH_BLOCK_Q,
                    block_k: int = FLASH_BLOCK_K):
    """Fused attention. Pallas kernels on TPU for BOTH passes
    (FlashAttention-2: forward saves O + logsumexp rows; backward runs a
    dQ pass and a dK/dV pass, no O(S^2) residuals). Blockwise-scan
    fallback off-TPU.

    q [B, Sq, H, D], k / v [B, Sk, H, D] in any one float dtype; `causal`
    aligns position 0 of q with position 0 of k. The FORWARD alone also takes
    values of another width than the keys (v [B, Sk, kvH, Dv]) and `H / kvH`
    adjoining query heads on one KV head, read where it lies (a prefill
    whose keys are 192 wide in 256 lanes beside values of 128, 16 query
    heads a KV head; a latent prefill's expanded rows, `[k_n ; k_r]` of 128 +
    64 in 256 lanes a head beside values of 128). A kernel program holds one
    head's whole K and V (dK/dV: Q and dO) in VMEM and walks them in blocks
    of `block_q` x `block_k` scores, (512, 512) by default and clipped to
    the lengths: on a v5e the best or within 2% of it at every shape probed
    (2,048 to 8,192 positions, head widths 128 and 256, bf16 and float32;
    PERF.md section 6, PRs 50 and 52), where smaller blocks lose up to 2.5 x.
    In a block step q, k, v and dO go to the MXU as they arrive (bf16
    operands are not cast up; the sums are float32 and `sm_scale` multiplies
    the float32 scores), `p` and `ds` enter their products in the operands'
    dtype, no score block is transposed, and only the blocks that the
    diagonal crosses or that hold padding build a mask: blocks wholly below
    the diagonal run a body without one, blocks wholly above it are skipped
    (`flash_block_plan`)."""
    return _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k)[0]


# What a `jax.checkpoint` around the caller has to keep for the backward not
# to run the forward kernel again (`save_only_these_names`). The names are
# given HERE, to the very values `_flash_bwd` reads: named outside
# `flash_attention`, the result is another variable than the residual, and
# the kernel runs a second time for its `lse`.
FLASH_KEPT = ("flash_out", "flash_lse")


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k):
    if _on_tpu():
        out, lse = _flash_fwd_pallas(q, k, v, causal, sm_scale, block_q, block_k)
        out = checkpoint_name(out, FLASH_KEPT[0])
        lse = checkpoint_name(lse, FLASH_KEPT[1])
        return out, (q, k, v, out, lse)
    out = checkpoint_name(
        blockwise_attention(q, *gqa_expand(k, v, q.shape[2]), causal,
                            sm_scale, block_k), FLASH_KEPT[0])
    return out, (q, k, v, None, None)


def _flash_bwd(causal, sm_scale, block_q, block_k, res, g):
    q, k, v, o, lse = res
    if q.shape[2:] != k.shape[2:] or k.shape != v.shape:
        raise NotImplementedError(
            "flash_attention's backward kernels take q, k and v of one "
            "shape: a value width of its own and KV heads shared by query "
            "heads are the forward's alone (a prefill's)")
    if lse is not None:
        return _flash_bwd_pallas(
            q, k, v, o, lse, g, causal, sm_scale, block_q, block_k
        )
    _, vjp = jax.vjp(
        lambda q_, k_, v_: blockwise_attention(q_, k_, v_, causal, sm_scale, block_k),
        q, k, v,
    )
    return vjp(g)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# K and V of one head, two buffers each, as the forward kernel's BlockSpecs
# map them whole a program: 8,192 keys of 128 bf16 values, the longest that
# has run on a v5e (PERF.md section 6, PRs 52 and 53). The chip's compiler
# refuses four times that, and the backward kernels twice (ROADMAP
# `flash-f32` e).
FLASH_KV_VMEM_BYTES = 8 << 20
# Past it (8,192 keys of 256 beside values of 128 are 12 MiB) the forward
# asks the compiler for what K and V take and this much for its blocks of q
# and o, its scratch and the block step's scores.
FLASH_BLOCKS_VMEM_BYTES = 12 << 20
# The most of K and V that the forward has been given so (`flash_attention_
# takes`): 8,192 keys of 192 in 256 lanes beside values of 128 (PERF.md
# section 6, PR 58; a latent prefill's 4,096 expanded rows of the same
# widths are 6 MiB, inside the compiler's own allowance: PR 61).
FLASH_KV_VMEM_MAX_BYTES = 12 << 20
# Float32 scores [B, H, S, S] up to which the XLA spelling of a prefill's
# attention (`models/decoding.py::_attend_cached`) is as fast as the kernel
# or faster: the compiler keeps them in the v5e's 128 MiB of fast memory
# between its fusions. One layer, batch 1, my chip runs, PR 53: at 32-96 MiB
# of scores 0.038-0.112 ms against the kernel's and its copies' 0.042-0.184;
# at 128 MiB and past it (8 heads x 2,048, 32 x 1,024, 32 x 2,048, 48 x
# 2,048) 0.56-3.42 against 0.12-0.67.
DENSE_SCORES_BYTES = 112 << 20


def flash_attention_takes(q, k, v=None) -> bool:
    """Whether causal self-attention of q [B, S, H, D] over k [B, S, kvH, D]
    and v [B, S, kvH, Dv] (None: as k) goes to the forward KERNEL, for a
    caller that has another spelling to fall back on (a prefill's fresh
    rows, `models/decoding.py::attend_fresh`: `attend_held`'s, and a latent
    prefill's expanded ones), by what can be seen of the call: on a TPU (as
    `flash_attention`), one 2- or 4-byte dtype, heads of
    whole lanes (the values' width may be another than the keys'), a
    length of whole 128s (the row statistics leave the kernel 128 positions
    at a time, `_store_row`: the chip's compiler refuses 16 to 64
    positions), a head's whole K and V inside `FLASH_KV_VMEM_BYTES` (values
    of a width of their own: `FLASH_KV_VMEM_MAX_BYTES`, which the call then
    asks of the compiler), and scores past `DENSE_SCORES_BYTES`: below that
    the other spelling never sends them to HBM and the kernel has nothing to
    save."""
    b, s, h, d = q.shape
    itemsize = q.dtype.itemsize
    dv = d if v is None else v.shape[-1]
    fits = 4 * s * d * itemsize <= FLASH_KV_VMEM_BYTES if dv == d else \
        2 * s * (d + dv) * itemsize <= FLASH_KV_VMEM_MAX_BYTES
    return (_on_tpu() and q.dtype == k.dtype and itemsize in (2, 4)
            and k.shape[1] == s and d % 128 == 0 and dv % 128 == 0
            and s % 128 == 0 and fits
            and 4 * b * h * s * s > DENSE_SCORES_BYTES)


# ---------------------------------------------------------------------------
# Decode attention over a cache STACK (TPU): one new token a sequence against
# the rows the sequence holds, read where they lie.
# ---------------------------------------------------------------------------

DECODE_BLOCK_ROWS = 512  # rows a block at most
DECODE_BLOCK_BYTES = 2 << 20  # and bytes of K (and of V) a block at most
# rows of at most this many bytes (2 KV heads of 128 bfloat16, ONE head) come
# in blocks of up to this many rows: 512 of them are a copy of 256 KB or less
DECODE_THIN_ROW_BYTES = 512
DECODE_THIN_BLOCK_ROWS = 2048
DECODE_GRANULE_ROWS = 16  # rows a copy of a last block starts and ends on
DECODE_SUB_ROWS = 512  # rows a piece of a last block's products


def decode_block(t: int, row_bytes: int) -> int:
    """Rows of one block of a slot of `t` rows of `row_bytes` (all KV heads):
    at most 512 rows (2,048 of rows of 512 B or less) and 2 MiB (two buffers
    each of K and V: 8 MiB of VMEM), a divisor of `t`. A block is what ONE
    copy brings and what is attended to while the next is in flight; since
    PR 65 it is not what a slot costs: the slot's LAST block is copied as
    the rows it holds in whole granules (`decode_granule`) and multiplied as
    the 512-row pieces that hold rows, so a larger block wastes nothing and
    has fewer copies to start and to wait for.

    On the v5e (my chip runs, PR 65, `build/pr65/prof.py`: one layer's call
    alone under the profiler, the kernel's own microseconds a call; slots
    full / half full / at the cell's fill; the parent copied whole blocks of
    512 and multiplied a head at a time): 16 KV heads, 8 slots x 512 (Ouro's
    136-504): parent 48.8 / 48.8 / 48.8, now 47.1 / 25.1 / 36.5 (the copies
    alone 45.1 / 23.0 / 33.9 = 745 GB/s; a granule of 64: 38.7 at the
    cell's fill); 16 slots x 2,048 (1,100-2,016): 359.0 / 181.7 / 331.3,
    now 357.3 / 180.1 / 296.4. 8 KV heads, 16 x 2,048: 179.8 / 91.2 / 166.0,
    now 178.9 / 90.3 / 148.6 (blocks of 1,024: 179.7 / 91.0 / 147.6, no
    gain); 32 x 4,096 (1,040-3,024): 711.7 / 357.1 / 384.8, now 710.8 /
    356.2 / 351.4. 4 KV heads, keys in two pieces, 32 x 10,240
    (4,112-10,048): 1,333.3 / 668.5 / 936.5, now 1,332.8 / 668.0 / 912.5.
    2 KV heads (rows of 512 B), 32 x 2,048 (530-2,030; 272-1,520): 108.3 /
    55.7 / 81.0; 61.2, at 512 rows 100.8 / 51.3 / 73.2; 54.5, at 1,024 89.9
    / 45.6 / 62.9; 50.0, at 2,048 90.3 / 45.7 / 57.5; 44.5 (with products
    over the whole block 90.3 / 47.5 / 59.0; 48.7). ONE KV head (256 B), 16 x
    12,288 (4,112-10,560): 214.8 / 107.8 / 143.5, at 512 rows 216.6 / 108.7
    / 145.1, at 1,024 156.5 / 79.0 / 108.0, at 2,048 134.5 / 68.0 / 93.6, at
    4,096 134.9 / 72.3 / 95.1. Hence 2,048 for rows of 512 B or less (over
    10% at every fill) and 512 for the rest (rows of 1 KB: not measured)."""
    rows = DECODE_THIN_BLOCK_ROWS if row_bytes <= DECODE_THIN_ROW_BYTES \
        else DECODE_BLOCK_ROWS
    block = min(t, rows, max(8, DECODE_BLOCK_BYTES // row_bytes))
    while t % block:
        block //= 2
    return block


def decode_granule(block: int) -> int:
    """Rows that a copy of `decode_attention` starts and ends on in blocks
    of `block` rows: 16, where the block is whole 16s (every stack of ONE
    2-byte KV head, whose positions lie two to a sublane, 16 to a tile:
    `_one_head`; every slot of whole 16s), else 8 (`decode_attention_takes`
    slots of whole 8s: a position's heads are whole tiles there and a copy
    could start on any; 8 keeps a last block to 6 copies)."""
    return math.gcd(block, DECODE_GRANULE_ROWS)


def decode_rows_copied(rows, t: int, row_bytes: int):
    """Rows of K (and of V) that `decode_attention` copies for slots that
    hold `rows` (an int array) of `t` rows of `row_bytes`: a slot's rows in
    whole granules, whatever block they end in. The kernel rounds by the
    same `decode_granule`, and `engine_stats()["kv_rows_read"]` books
    this."""
    granule = decode_granule(decode_block(t, row_bytes))
    return -(-rows // granule) * granule


def decode_attention_takes(stack, v_stack=None) -> bool:
    """Whether `decode_attention` runs on a cache stack [N, B, T, kvH, D]
    (as `v_stack`, if given) of this shape and dtype, here: on a TPU (as
    `flash_attention`), rows of ONE lane tile or, beside values as wide,
    whole ones (the compiler takes a strided read of a head's rows only out
    of a buffer whose last dimension is one tile: keys wider than the values
    are cached in pieces of the values' width, `key_pieces`), slots of whole
    sublanes, and 2- or 4-byte values whose KV heads fill whole 32-bit words
    and whole tiles of 1, 2, 4 or 8 words (XLA then keeps a position's heads
    unpadded, and so does the kernel), or ONE 2-byte KV head with values as
    wide (`_one_head`: XLA keeps such a stack as [N, B, T, D], positions
    down the sublanes, and the kernel reads its rows as they lie)."""
    if v_stack is not None and _one_head(stack, v_stack):
        return _on_tpu() and stack.shape[2] % 16 == 0 \
            and stack.shape[-1] % 128 == 0
    for one in (stack,) if v_stack is None else (stack, v_stack):
        _, _, t, kvh, d = one.shape
        itemsize = one.dtype.itemsize
        if itemsize not in (2, 4) or kvh % (4 // itemsize):
            return False
        words = kvh // (4 // itemsize)
        if not ((words in (1, 2, 4) or words % 8 == 0) and t % 8 == 0
                and d % 128 == 0):
            return False
    # keys in pieces: each as wide as the values, one lane tile
    pieces = v_stack is not None and v_stack.shape != stack.shape
    return _on_tpu() and (not pieces or (
        stack.shape[-1] == v_stack.shape[-1] == 128
        and stack.shape[3] % v_stack.shape[3] == 0
        and stack.dtype == v_stack.dtype))


def _one_head(stack, v_stack) -> bool:
    """Whether the stacks [N, B, T, kvH, D] are ONE 2-byte KV head's, keys
    and values alike: half a 32-bit word a position, which no strided read
    of words takes apart. The chip keeps the unit dimension outside the
    positions (a position is a D-wide row, two to a sublane: `{4,2,3,1,0:
    T(8,128)(2,1)}` for bfloat16 [2, 16, 12288, 1, 128], the compiler's own
    choice for a described v5e), so [N, B, T, D] is the same bytes and the
    kernel copies blocks of whole rows."""
    return (stack.shape == v_stack.shape and stack.dtype == v_stack.dtype
            and stack.shape[3] == 1 and stack.dtype.itemsize == 2)


def key_pieces(k, width: int):
    """Keys [..., kvH, D] as [..., kvH * D / width, width]: a head's key in
    adjoining pieces of `width` (the values' width), as a cache keeps keys
    that are wider than its values; `D == width`: as they are."""
    d = k.shape[-1]
    return k if d == width else k.reshape(
        *k.shape[:-2], k.shape[-2] * (d // width), width)


def whole_keys(k, kvh: int):
    """`key_pieces`' way back: [..., kvH * pieces, width] as [..., kvH, D]."""
    return k if k.shape[-2] == kvh else k.reshape(
        *k.shape[:-2], kvh, k.shape[-1] * (k.shape[-2] // kvh))


def _decode_attention_kernel(layer_ref, rows_ref, q_ref, k_hbm, v_hbm, *rest,
                             block, granule, sub, sink, sm_scale):
    """q_ref [B, kvH, R, D] / o_ref [B, kvH, R, Dv] in VMEM (a KV head's
    `rep` query heads padded to R rows); k_hbm [N, B, T, kvH * chunks, D /
    chunks] / v_hbm [N, B, T, kvH, Dv] the stacks where XLA keeps them (keys
    wider than the values lie in `chunks` pieces of the values' width a
    head, pieces of one head adjoining: a strided read takes whole lane
    tiles only); kbuf / vbuf two blocks of them each. With `sink`, `rest` leads with sink_ref [kvH,
    R, _LANES] float32 (a query head's learned logit over its lanes): the
    online softmax STARTS from it, a running maximum of the sink and a sum
    of one, so that it is in every denominator and adds no value. ONE
    invocation walks the
    slots in order and each slot's `ceil(rows / block)` blocks, the next
    block's copies (the same slot's, or block 0 of the next slot that holds
    a row) in flight while this one is attended to: a slot without rows
    starts no copy at all. A block that the slot's rows fill goes in one
    copy; the LAST block goes as the rows it holds, rounded up to whole
    `granule`s, in the binary pieces of that count (`each_copy`: on the
    block's two semaphores, each piece waited for as it was started), and
    its products run over the whole `sub`-row pieces that hold rows (a block
    of at most `sub` rows: over all of it, the buffer's stale rows masked)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    sink_ref, (o_ref, kbuf, vbuf, sem) = (rest[0] if sink else None), \
        rest[-4:]
    n_slots, kvh, r, d = q_ref.shape
    dv = o_ref.shape[-1]
    # ONE 2-byte KV head (`_one_head`): the stacks are [N, B, T, D], a block
    # [block, D] is the head's rows as they lie
    flat = kbuf.ndim == 3
    chunks = 1 if flat else kbuf.shape[2] // kvh
    layer = layer_ref[0]
    # 2-byte rows lie in pairs of KV heads, one 32-bit word a pair and lane
    packing = 1 if flat else 4 // kbuf.dtype.itemsize
    # the sizes a last block's copies come in, largest first
    pieces = [granule << i for i in range(block.bit_length())
              if granule << i < block][::-1]
    # the rows a block's products may run over: whole `sub`s, then all
    extents = list(range(sub, block, sub)) + [block]

    def each_copy(b, j, buf, do):
        """`do` (start, or wait for) every copy of block j of slot b into
        buffer `buf`, the rows the slot holds there in whole granules: ONE
        of the whole block where they fill it; else one a set bit of that
        count, each at the sum of the larger ones, so that every copy's
        size is static."""
        n = jnp.minimum(
            block, (rows_ref[b] - j * block + granule - 1) // granule * granule)

        def copy(at, size):
            aligned = math.gcd(block, size)
            src = pl.ds(pl.multiple_of(j * block + at, aligned), size)
            dst = pl.ds(pl.multiple_of(at, aligned), size)
            do(pltpu.make_async_copy(k_hbm.at[layer, b, src],
                                     kbuf.at[buf, dst], sem.at[0, buf]))
            do(pltpu.make_async_copy(v_hbm.at[layer, b, src],
                                     vbuf.at[buf, dst], sem.at[1, buf]))

        @pl.when(n == block)
        def _():
            copy(0, block)

        @pl.when(n < block)
        def _():
            for size in pieces:
                @pl.when(n & size != 0)
                def _():
                    copy(n & -(2 * size), size)

    def start(b, j, buf):
        each_copy(b, j, buf, lambda copy: copy.start())

    def holds_rows_from(b):
        """The first slot >= b that holds a row; `n_slots` if none does."""
        return lax.while_loop(
            lambda i: (i < n_slots)
            & (rows_ref[jnp.minimum(i, n_slots - 1)] == 0),
            lambda i: i + 1, b)

    def heads_of(ref, g0, size, keep=None):
        """The first `size` rows [size, D] of KV heads g0 .. g0 + packing -
        1 of one buffer [block, kvH, D]: a strided read of the heads'
        sublanes, and for 2-byte rows the two halves of each word. Rows
        outside `keep` [size, D] come out as zeros whatever the buffer holds
        there."""
        if flat:
            rows = ref[:size, :]
            return [rows if keep is None else jnp.where(keep, rows, 0)]
        heads = ref.shape[1]  # a position's rows: KV heads, or their pieces
        joined = ref.reshape(block * heads, ref.shape[-1])
        if packing == 1:
            rows = joined[pl.ds(g0, size, stride=heads), :]
            return [rows if keep is None else jnp.where(keep, rows, 0)]
        words = joined.bitcast(jnp.uint32)[
            pl.ds(g0 // 2, size, stride=heads // 2), :]
        if keep is not None:
            words = jnp.where(keep, words, jnp.uint32(0))
        return [pltpu.bitcast(half, jnp.float32).astype(ref.dtype)
                for half in (words << 16, words & jnp.uint32(0xFFFF0000))]

    first = holds_rows_from(0)

    @pl.when(first < n_slots)
    def _():
        start(first, 0, 0)

    def slot(b, buf):
        rows = rows_ref[b]
        n_blocks = pl.cdiv(rows, block)
        after = holds_rows_from(b + 1)

        def attend_rows(j, buf, size, state):
            """The first `size` rows of block j, in buffer `buf`, into the
            running (maximum, sum, accumulator) [kvH * R, 1 | 1 | Dv] of
            all KV heads, the heads down the rows: every head's logits
            first, ONE maximum, exponential and sum over all of them, then
            every head's weighted sum. A head at a time (until PR 65), each
            head's chain of matrix unit, reduction and exponential waited
            for the head before: 34.9 us of products a call at 8 slots x
            512 rows x 16 heads against 17.1 (my chip runs, PR 65)."""
            m, l, acc = state
            # what a block holds past the slot's rows is someone else's or
            # stale: its logits are masked, and its V rows are zeros (a
            # probability of zero does not clear a NaN)
            held = (j * block + lax.broadcasted_iota(
                jnp.int32, (kvh * r, size), 1)) < rows
            v_held = (j * block + lax.broadcasted_iota(
                jnp.int32, (size, dv), 0)) < rows
            logits = []
            for g0 in range(0, kvh, packing):
                ks = [k for idx in range(g0 * chunks, (g0 + packing) * chunks,
                                         packing)
                      for k in heads_of(kbuf.at[buf], idx, size)]
                for i, g in enumerate(range(g0, g0 + packing)):
                    logits.append(functools.reduce(jnp.add, [lax.dot_general(
                        q_ref[b, g] if chunks == 1 else
                        q_ref[b, g, :, c * _LANES:(c + 1) * _LANES],
                        ks[i * chunks + c], (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
                        for c in range(chunks)]))
            s = jnp.concatenate(logits, axis=0) * sm_scale
            s = jnp.where(held, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + p.sum(axis=-1, keepdims=True)
            p = p.astype(vbuf.dtype)
            weighted = []
            for g0 in range(0, kvh, packing):
                vs = heads_of(vbuf.at[buf], g0, size, v_held)
                for g, v in zip(range(g0, g0 + packing), vs):
                    weighted.append(jnp.dot(
                        p[g * r:(g + 1) * r], v,
                        preferred_element_type=jnp.float32))
            return m_new, l, acc * alpha + jnp.concatenate(weighted, axis=0)

        def attend(j, carry):
            buf, state = carry
            more = j + 1 < n_blocks

            @pl.when(more)
            def _():
                start(b, j + 1, 1 - buf)

            @pl.when(jnp.logical_not(more) & (after < n_slots))
            def _():
                start(after, 0, 1 - buf)

            each_copy(b, j, buf, lambda copy: copy.wait())
            if len(extents) == 1:
                return 1 - buf, attend_rows(j, buf, block, state)
            # over the shortest of `extents` that holds the block's rows,
            # each ONE chain of products: a loop of pieces made every
            # piece's chain of matrix unit, maximum and exponential wait for
            # the piece before (71 us a call for 54, full slots, PR 65)
            return 1 - buf, lax.switch(
                pl.cdiv(jnp.minimum(block, rows - j * block), sub) - 1,
                [functools.partial(attend_rows, j, buf, size)
                 for size in extents], state)

        start_from = (jnp.full((kvh * r, 1), NEG_INF, jnp.float32),
                      jnp.zeros((kvh * r, 1), jnp.float32),
                      jnp.zeros((kvh * r, dv), jnp.float32))
        if sink:
            start_from = (jnp.concatenate(
                [sink_ref[g][:, :1] for g in range(kvh)], axis=0),
                jnp.ones_like(start_from[1]), start_from[2])
        buf, (_, l, acc) = lax.fori_loop(0, n_blocks, attend,
                                         (buf, start_from))
        out = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        for g in range(kvh):  # no row held: zeros
            o_ref[b, g] = out[g * r:(g + 1) * r]
        return buf

    lax.fori_loop(0, n_slots, slot, 0)


def decode_attention(q, k_stack, v_stack, layer, rows,
                     block: Optional[int] = None, sink=None,
                     sm_scale: Optional[float] = None):
    """One new token a sequence against its cached rows: q [B, H, D], the
    cache STACKS k_stack / v_stack [N, B, T, kvH, D], or, where the keys are
    wider than the values, k_stack [N, B, T, kvH * D / Dv, Dv] (a head's key
    in adjoining pieces of the values' width) beside v_stack [N, B, T, kvH,
    Dv]; `layer` (int32 scalar)
    the layer to read and `rows` (int32 [B]) how many of its T rows each
    slot holds, a prefix. Returns [B, H, Dv] in q's dtype: softmax(q k^T
    sm_scale) v (`sm_scale` None: 1 / sqrt(D)) over rows 0 .. rows[b] - 1 per
    KV-head group (the heads of one group adjoin), zeros where rows[b] == 0.
    `sink` [H] (float): a learned logit a query head that joins the
    softmax's denominator and carries no value.

    A Pallas kernel. `layer` and `rows` are scalar-prefetch operands and
    the stacks stay in HBM: slot b's first `decode_rows_copied(rows[b])`
    rows of K and of V (its rows in whole granules: 16 rows, 8 in a slot
    that is not whole 16s, `decode_granule`) are copied from [layer, b], a
    block (`decode_block`) at a time and the last block in a few pieces,
    and nothing else is read, no layer is cut out of its stack and no [B, T]
    logits exist; a free slot costs nothing. Operands in
    the cache's dtype, products summed in float32, the running maximum, sum
    and accumulator float32 across blocks; the probabilities enter the
    product with V in the cache's dtype, as the MXU takes them from
    `_attend_cached`'s float32 at default precision."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, d = q.shape
    n, _, t, kvh, dv = v_stack.shape
    rep = h // kvh
    itemsize = k_stack.dtype.itemsize
    block = block or decode_block(t, kvh * max(d, dv) * itemsize)
    if _one_head(k_stack, v_stack):  # its rows as they lie: the same bytes
        k_stack, v_stack = (s.reshape(n, b, t, dv) for s in (k_stack, v_stack))
    tile = 8 * (4 // itemsize)  # rows of a tile of q's dtype
    r = -(-rep // tile) * tile
    q4 = q.astype(k_stack.dtype).reshape(b, kvh, rep, d)
    if r != rep:
        q4 = jnp.pad(q4, ((0, 0), (0, 0), (0, r - rep), (0, 0)))
    sinks = ()
    if sink is not None:
        sinks = (jnp.broadcast_to(jnp.pad(
            sink.astype(jnp.float32).reshape(kvh, rep),
            ((0, 0), (0, r - rep)))[:, :, None], (kvh, r, _LANES)),)
    out = pl.pallas_call(
        functools.partial(
            _decode_attention_kernel, block=block,
            granule=decode_granule(block), sub=DECODE_SUB_ROWS,
            sink=sink is not None,
            sm_scale=d ** -0.5 if sm_scale is None else sm_scale),
        name="decode_attention",
        out_shape=jax.ShapeDtypeStruct((b, kvh, r, dv), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ] + [pl.BlockSpec(memory_space=pltpu.VMEM)] * len(sinks),
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((2, block, *k_stack.shape[3:]), k_stack.dtype),
                pltpu.VMEM((2, block, *v_stack.shape[3:]), v_stack.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      # copies are started, and waited for, by the rows counted here: none
      # past a slot's end (rows of 0 .. t), none for a slot without rows
      jnp.clip(rows.astype(jnp.int32), 0, t), q4, k_stack, v_stack, *sinks)
    return out[:, :, :rep].reshape(b, h, dv)


def latent_decode_attention_takes(stack, value_dim: int) -> bool:
    """Whether `latent_decode_attention` runs on a stack [N, B, T, width] of
    this shape and dtype, here: on a TPU, 2-byte rows whose value part is
    whole lanes, slots of whole tiles of rows."""
    _, _, t, width = stack.shape
    return (_on_tpu() and stack.dtype.itemsize == 2 and t % 16 == 0
            and value_dim % 128 == 0 and value_dim <= width)


def _latent_decode_kernel(layer_ref, rows_ref, q_ref, lat_hbm, o_ref, buf,
                          sem, *, block, value_dim, sm_scale):
    """q_ref [B, R, width] / o_ref [B, R, value_dim] in VMEM; lat_hbm the
    stack [N, B, T, width] where XLA keeps it; buf [2, block, width]. ONE
    invocation walks the slots and each slot's `ceil(rows / block)` blocks,
    as `_decode_attention_kernel` does, the next block's copy in flight
    while this one is attended to; a block is read ONCE and serves the
    logits (all `width` of a row) and the values (its first `value_dim`)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_slots, r, _ = q_ref.shape
    layer = layer_ref[0]

    def copy(b, j, at_buf):
        at = pl.ds(pl.multiple_of(j * block, block), block)
        return pltpu.make_async_copy(lat_hbm.at[layer, b, at],
                                     buf.at[at_buf], sem.at[at_buf])

    def holds_rows_from(b):
        return lax.while_loop(
            lambda i: (i < n_slots)
            & (rows_ref[jnp.minimum(i, n_slots - 1)] == 0),
            lambda i: i + 1, b)

    first = holds_rows_from(0)

    @pl.when(first < n_slots)
    def _():
        copy(first, 0, 0).start()

    def slot(b, at_buf):
        rows = rows_ref[b]
        n_blocks = pl.cdiv(rows, block)
        after = holds_rows_from(b + 1)

        def attend(j, carry):
            at_buf, (m, l, acc) = carry
            more = j + 1 < n_blocks

            @pl.when(more)
            def _():
                copy(b, j + 1, 1 - at_buf).start()

            @pl.when(jnp.logical_not(more) & (after < n_slots))
            def _():
                copy(after, 0, 1 - at_buf).start()

            copy(b, j, at_buf).wait()
            # past the slot's rows a block holds someone else's or stale
            # rows: their logits are masked and their values zeros
            held = (j * block + lax.broadcasted_iota(
                jnp.int32, (r, block), 1)) < rows
            v_held = (j * block + lax.broadcasted_iota(
                jnp.int32, (block, value_dim), 0)) < rows
            k = buf[at_buf]
            s = lax.dot_general(
                q_ref[b], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            s = jnp.where(held, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + p.sum(axis=-1, keepdims=True)
            v = jnp.where(v_held, k[:, :value_dim], 0)
            acc = acc * alpha + jnp.dot(p.astype(v.dtype), v,
                                        preferred_element_type=jnp.float32)
            return 1 - at_buf, (m_new, l, acc)

        empty = (jnp.full((r, 1), NEG_INF, jnp.float32),
                 jnp.zeros((r, 1), jnp.float32),
                 jnp.zeros((r, value_dim), jnp.float32))
        at_buf, (_, l, acc) = lax.fori_loop(0, n_blocks, attend,
                                            (at_buf, empty))
        o_ref[b] = acc / jnp.maximum(l, 1e-30)  # no row held: zeros
        return at_buf

    lax.fori_loop(0, n_slots, slot, 0)


def latent_decode_attention(q, stack, layer, rows, value_dim: int,
                            sm_scale: float, block: Optional[int] = None):
    """One new token a sequence against its cached LATENT rows (a decode
    step of latent attention with the key and value expansions absorbed
    into q and the output): q [B, H, width], `stack` [N, B, T, width], a
    row's first `value_dim` its value as well, `layer` (int32 scalar) the
    layer to read, `rows` (int32 [B]) how many of its T rows each slot
    holds, a prefix. Returns float32 [B, H, value_dim]: softmax(q row^T
    sm_scale) row[:value_dim] over rows 0 .. rows[b] - 1, every head over
    the same rows; zeros where rows[b] == 0.

    `decode_attention`'s sibling: the stack stays in HBM, slot b's
    `ceil(rows[b] / block)` blocks are copied from [layer, b, block], each
    ONCE, and nothing else is read; operands in the cache's dtype, sums and
    the running maximum, sum and accumulator float32."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, width = q.shape
    t = stack.shape[2]
    block = block or decode_block(t, width * stack.dtype.itemsize)
    tile = 8 * (4 // stack.dtype.itemsize)
    r = -(-h // tile) * tile
    q3 = q.astype(stack.dtype)
    if r != h:
        q3 = jnp.pad(q3, ((0, 0), (0, r - h), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_latent_decode_kernel, block=block,
                          value_dim=value_dim, sm_scale=sm_scale),
        name="latent_decode_attention",
        out_shape=jax.ShapeDtypeStruct((b, r, value_dim), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((2, block, width), stack.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.clip(rows.astype(jnp.int32), 0, t), q3, stack)
    return out[:, :h]


def gqa_expand(k, v, num_q_heads: int):
    """Expand grouped KV heads to match q heads (GQA → MHA view).

    [B,S,Hkv,D] → [B,S,Hq,D] by repeat; XLA turns this into a broadcast,
    no copy on TPU when fused into the attention einsum.
    """
    hkv = k.shape[2]
    if hkv == num_q_heads:
        return k, v
    rep = num_q_heads // hkv
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    return k, v
