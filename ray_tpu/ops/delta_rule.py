"""One position of the gated delta rule for every sequence and head, on the
state stack where it lies (TPU): the decode step of a linear-attention layer
(models/kimi_linear.py).

`S <- Diag(a) S`, `u = beta (v - S^T k)`, `S <- S + k u^T`, `o = S^T q`: as
XLA fuses it, the two reductions over keys are passes of their own over a
layer's states beside the pass that rewrites them (7.6 ms of a 26.7 ms step
for 2.7 GB of required traffic on the v5e: PERF.md, PR 38); the kernel holds
one sequence's states in fast memory, so the stack is read once and written
once, in place.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.ops import attention as attention_ops


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
