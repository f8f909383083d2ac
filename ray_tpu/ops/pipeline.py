"""Pipeline parallelism — GPipe-style microbatching over the "stage" axis.

The reference has NO native PP (SURVEY.md §2.3 — Ray defers TP/PP to
vLLM/DeepSpeed); here it is a mesh axis like everything else. The
layer-stacked transformer params shard their leading (layers) dim over
"stage"; a shard_map manual ONLY over "stage" (other axes stay GSPMD-
automatic, so TP/FSDP einsums inside stages still partition normally)
rotates microbatch activations stage-to-stage with `ppermute`.

Autodiff through the scan+ppermute yields the reverse pipeline schedule
for the backward pass automatically (1F1B-equivalent bubble count for
GPipe: (S-1)/(M+S-1) idle fraction — pick num_microbatches >= 2*stages).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map as _shard_map


def pipeline_spmd(body: Callable, x_mb: jax.Array, pos_mb: jax.Array,
                  axis_name: str = "stage"):
    """Run `body(x, pos) -> x` (this stage's layers) over microbatched input.

    Called INSIDE a shard_map manual over `axis_name`. x_mb [M, mb, ...]
    and pos_mb [M, ...] (per-microbatch rope positions) are replicated
    across stages; returns [M, mb, ...] outputs valid on every stage
    (psum-broadcast from the last stage).
    """
    n_stage = lax.axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    M = x_mb.shape[0]
    total = M + n_stage - 1
    perm = [(i, (i + 1) % n_stage) for i in range(n_stage)]

    def step(carry, i):
        state, out_buf = carry
        # activation from the previous stage (its output at iter i-1)
        recv = lax.ppermute(state, axis_name, perm)
        inp = lax.dynamic_index_in_dim(x_mb, jnp.clip(i, 0, M - 1), 0,
                                       keepdims=False)
        cur = jnp.where(stage == 0, inp, recv)
        # stage s processes microbatch i - s at iteration i
        mb_idx = jnp.clip(i - stage, 0, M - 1)
        pos_cur = lax.dynamic_index_in_dim(pos_mb, mb_idx, 0, keepdims=False)
        out = body(cur, pos_cur)
        # last stage stores finished microbatch i-(S-1)
        idx_out = jnp.clip(i - (n_stage - 1), 0, M - 1)
        valid = (stage == n_stage - 1) & (i >= n_stage - 1)
        slot = lax.dynamic_index_in_dim(out_buf, idx_out, 0, keepdims=False)
        out_buf = lax.dynamic_update_index_in_dim(
            out_buf, jnp.where(valid, out, slot), idx_out, 0
        )
        return (out, out_buf), None

    init = (jnp.zeros_like(x_mb[0]), jnp.zeros_like(x_mb))
    (_, out_buf), _ = lax.scan(step, init, jnp.arange(total))
    # broadcast the last stage's results to every stage. psum in f32:
    # XLA's AllReducePromotion pass miscompiles bf16 all-reduce inside
    # partial-manual shard_map regions (crash in ChangeOpDataType).
    masked = jnp.where(
        stage == n_stage - 1, out_buf, jnp.zeros_like(out_buf)
    ).astype(jnp.float32)
    return lax.psum(masked, axis_name).astype(x_mb.dtype)


def pipelined_layers(
    mesh,
    apply_stage: Callable,  # (stage_local_layer_params, x, positions) -> x
    stacked_params,         # pytree, leading dim = layers (shards over stage)
    x: jax.Array,           # [B, S, H] activations
    positions: jax.Array,   # [S] or [B, S] rope positions
    num_microbatches: int,
    axis_name: str = "stage",
    seq_axis: str = None,   # sequence-parallel mesh axis, if SP is active
):
    """Apply layer stack under pipeline parallelism.

    `axis_name` (and, when SP composes with PP, `seq_axis`) go manual;
    remaining mesh axes stay automatic so the stage body's einsums keep
    their GSPMD TP/FSDP partitioning. Shardy can't nest manual regions
    that re-bind an ancestor axis, so PP×SP is ONE region manual over
    both axes — the stage body then calls ring_attention directly with
    axis_name="sequence" instead of wrapping it in its own shard_map."""
    from jax.sharding import PartitionSpec as P

    n_stage = mesh.shape[axis_name]
    b = x.shape[0]
    if b % num_microbatches:
        raise ValueError(f"batch {b} not divisible by microbatches {num_microbatches}")
    mb = b // num_microbatches
    x_mb = x.reshape((num_microbatches, mb) + x.shape[1:])

    param_specs = jax.tree.map(lambda _: P(axis_name), stacked_params)
    dtype = x.dtype
    manual_axes = {axis_name} | ({seq_axis} if seq_axis else set())
    # [M, mb, S, H]: split S over the sequence axis when SP is on.
    x_spec = P(None, None, seq_axis) if seq_axis else P()
    # Positions are microbatched alongside the activations: [S] shared →
    # [M, S]; per-example [B, S] → [M, mb, S] (pipeline_spmd picks the
    # slice for the microbatch each stage is processing at each tick).
    if positions.ndim == 1:
        pos_mb = jnp.broadcast_to(
            positions, (num_microbatches,) + positions.shape)
        pos_spec = P(None, seq_axis) if seq_axis else P()
    else:
        if positions.shape[0] != b:
            raise ValueError(
                f"positions batch dim {positions.shape[0]} != batch {b}")
        pos_mb = positions.reshape((num_microbatches, mb) + positions.shape[1:])
        pos_spec = P(None, None, seq_axis) if seq_axis else P()

    def inner(params_local, x_mb_local, pos_mb_local):
        out = pipeline_spmd(
            lambda h, p_: apply_stage(params_local, h.astype(dtype),
                                      p_).astype(jnp.float32),
            x_mb_local, pos_mb_local, axis_name,
        )
        return out

    # The boundary crosses in f32: the replicated input's cotangent gets
    # an autodiff-inserted psum over "stage", and XLA's AllReducePromotion
    # pass miscompiles bf16 all-reduces inside partial-manual regions.
    out = _shard_map(
        inner,
        mesh=mesh,
        in_specs=(param_specs, x_spec, pos_spec),
        out_specs=x_spec,
        axis_names=manual_axes,
        check_vma=False,
    )(stacked_params, x_mb.astype(jnp.float32), pos_mb)
    return out.astype(dtype).reshape((b,) + x.shape[1:])
