"""Sharded train-step builder — the GSPMD heart of Train.

In the reference, parallelism is delegated to torch DDP/FSDP wrappers
(train/torch/train_loop_utils.py:178,187); here DP/FSDP/TP/SP are all
NamedSharding choices over ONE jitted program (SURVEY.md §2.3):

- params/optimizer state sharded by logical-axis rules (fsdp/tensor),
- batch sharded over (replica, data, fsdp) × sequence,
- only the trainable leaves are differentiated (``trainable_mask``: every
  leaf of a dense config, the adapters alone of a LoRA one): no gradient
  of a frozen weight is computed, summed across chips or kept, and the
  frozen leaves leave the step in the buffers they came in by; the
  ``grad_norm`` metric is the norm of the gradients that exist, the
  trainable leaves',
- those gradients all-reduced implicitly by GSPMD over the data axes,
- each block's checkpoint keeps what its backward reads (the named results
  of its matmuls and of the flash forward kernel), as far down
  ``REMAT_LADDER`` as the device's memory asks (``run.remat_kept``),
- sequence axis > 1 switches attention to ring_attention under
  shard_map (exact, comms overlap compute on ICI),
- tensor axis > 1 keeps the residual stream's rows cut over it between the
  sublayers, and the sums and gathers the projections then need run as
  rings under the products (``run.tensor_ring``).

Everything compiles to a single XLA program per step; donated input
state keeps HBM flat."""

from __future__ import annotations

import functools
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import shard_map as _shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models.transformer import (
    REMAT_LADDER, TransformerConfig, forward, init_params, loss_fn, param_axes,
    tensor_ring, trainable_mask,
)
from ray_tpu.observability.timeline import record_setup_phase, setup_phase
from ray_tpu.ops.attention import flash_attention, gqa_expand
from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.parallel.bootstrap import FirstCall
from ray_tpu.parallel.mesh import mesh_axis_size
from ray_tpu.parallel.sharding import (
    DEFAULT_RULES, Rules, named_sharding, spec_for, tree_shardings,
)

TrainState = Dict[str, Any]
# Share of the device's `bytes_limit` a compiled step leaves free to be
# taken: the compiler's count leaves out what the allocator loses between
# buffers and what else the process holds on the device (a prefetched batch,
# an evaluation's program).
REMAT_HEADROOM = 0.05


def default_optimizer(cfg: TransformerConfig, lr: float = 3e-4,
                      weight_decay: float = 0.1,
                      params_template: Optional[Any] = None) -> optax.GradientTransformation:
    """AdamW + global-norm clip; LoRA configs train only adapter leaves
    (reference target: Llama LoRA fine-tune, BASELINE.md)."""
    tx = optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adamw(lr, b1=0.9, b2=0.95, weight_decay=weight_decay),
    )
    if cfg.lora_rank:
        # multi_transform (not optax.masked — masked passes frozen-leaf
        # gradients through unchanged) so frozen params get zero updates
        # and the clip sees the adapters' norm alone: the norm the step
        # reports as ``grad_norm``. The step never computes a frozen
        # leaf's gradient (make_train_step): the zeros it hands over in
        # their place are masked out here, unread.
        labels = lambda params: jax.tree.map(
            lambda t: "train" if t else "freeze", trainable_mask(cfg, params)
        )
        tx = optax.multi_transform(
            {"train": tx, "freeze": optax.set_to_zero()}, labels
        )
    return tx


def _context_mesh(mesh: Mesh):
    """The mesh a nested shard_map must be handed: inside another
    (partial-manual) shard_map — e.g. the pipeline's "stage" region — it
    is the context's abstract mesh, whose axis_types already mark the
    outer manual axes."""
    ctx_mesh = jax.sharding.get_abstract_mesh()
    return mesh if ctx_mesh is None or ctx_mesh.empty else ctx_mesh


def make_attn_fn(cfg: TransformerConfig, mesh: Mesh,
                 rules: Optional[Rules] = None) -> Optional[Callable]:
    """Attention for a mesh: None on one device (→ the model's default,
    flash_attention called directly); ring attention under shard_map when
    the sequence axis is sharded; otherwise flash_attention under a
    shard_map over the axes that shard batch and heads.

    The Pallas kernels cannot be partitioned by GSPMD (Mosaic refuses
    anything but a fully manual region), so on a mesh of more than one
    device the dense path makes every axis manual: batch and heads are
    split by the rule table, the other axes see replicated operands.
    Nested inside the pipeline's "stage"-manual region it takes the
    remaining axes. That is right in numbers (naming "stage" again
    corrupts the gradients) and runs blockwise off-TPU, but on a TPU
    Mosaic still refuses it: JAX 0.9.0 checks the kernel against this
    region's axes alone. Pipeline × kernels therefore fails with the
    compiler's error on real chips; it is not made to pass by running
    blockwise there.

    The ring region is partial-manual over ONLY the "sequence" axis:
    batch/head axes stay GSPMD-automatic, which both keeps TP/DP
    partitioning on the einsums around attention and lets this region
    nest inside the pipeline's shard_map (PP × SP composition — disjoint
    manual axis sets nest cleanly). It does not use the kernels."""
    rules = rules or DEFAULT_RULES
    if mesh.size <= 1:
        return None
    if mesh_axis_size(mesh, "sequence") <= 1:
        qkv_spec = spec_for(("batch", None, "heads", None), rules, mesh)

        def dense(q, k, v):
            k, v = gqa_expand(k, v, q.shape[2])
            use_mesh = _context_mesh(mesh)
            free = set(use_mesh.axis_names) - set(use_mesh.manual_axes)
            return _shard_map(
                lambda q, k, v: flash_attention(q, k, v, causal=True),
                mesh=use_mesh,
                in_specs=(qkv_spec, qkv_spec, qkv_spec), out_specs=qkv_spec,
                axis_names=free,
                check_vma=False,
            )(q, k, v)

        return dense
    if mesh_axis_size(mesh, "stage") > 1:
        # PP×SP: the pipeline's shard_map is manual over {stage, sequence}
        # (ops/pipeline.py), so inside it "sequence" is already a bound
        # axis — call ring_attention directly, no nested shard_map.
        def attn_manual(q, k, v):
            k, v = gqa_expand(k, v, q.shape[2])
            return ring_attention(q, k, v, axis_name="sequence", causal=True)

        return attn_manual
    seq_spec = P(None, "sequence")  # [B, S, H, D] — split seq dim only

    def attn(q, k, v):
        def inner(q, k, v):
            k, v = gqa_expand(k, v, q.shape[2])
            return ring_attention(q, k, v, axis_name="sequence", causal=True)

        return _shard_map(
            inner, mesh=_context_mesh(mesh),
            in_specs=(seq_spec, seq_spec, seq_spec), out_specs=seq_spec,
            axis_names={"sequence"},
            check_vma=False,
        )(q, k, v)

    return attn


def _effective_rules(mesh: Mesh, rules: Optional[Rules]) -> Rules:
    """Base rules + PP: with a real stage axis, layer-stacked params shard
    their leading (layers) dim over "stage" so each stage holds only its
    own layers."""
    rules = dict(rules or DEFAULT_RULES)
    if mesh_axis_size(mesh, "stage") > 1:
        rules.setdefault("layers", "stage")
    return rules


def state_shardings(cfg: TransformerConfig, optimizer: optax.GradientTransformation,
                    mesh: Mesh, rules: Optional[Rules] = None) -> TrainState:
    """NamedShardings for the full train state. Optimizer-state leaves
    that mirror params (adam mu/nu) inherit the param shardings via
    optax.tree_map_params; scalars replicate."""
    rules = _effective_rules(mesh, rules)
    axes = param_axes(cfg)
    p_shard = tree_shardings(mesh, axes, rules)
    repl = NamedSharding(mesh, P())

    params_shape = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    opt_shape = jax.eval_shape(optimizer.init, params_shape)
    # multi_transform (LoRA) leaves an empty MaskedNode where a label's
    # transform does not own the leaf: keep it, it holds no array
    masked = lambda x: isinstance(x, optax.MaskedNode)
    opt_shard = optax.tree_map_params(
        optimizer,
        lambda leaf, s: leaf if masked(leaf) else s,
        opt_shape,
        p_shard,
        transform_non_params=lambda _: repl,
        is_leaf=masked,
    )
    return {"params": p_shard, "opt_state": opt_shard,
            "step": repl, "rng": repl}


def batch_sharding(mesh: Mesh, rules: Optional[Rules] = None) -> NamedSharding:
    """tokens [B, S] → sharded (batch, seq)."""
    return named_sharding(mesh, ("batch", "seq"), rules)


def fresh_state(cfg: TransformerConfig, optimizer: optax.GradientTransformation,
                key: jax.Array, seed: int = 0) -> TrainState:
    """The train state at step 0 (``jax.eval_shape`` of this gives its
    shapes without materializing anything)."""
    params = init_params(cfg, key)
    return {
        "params": params,
        "opt_state": optimizer.init(params),
        "step": jnp.zeros((), jnp.int32),
        "rng": jax.random.key_data(jax.random.key(seed)),
    }


def init_state(cfg: TransformerConfig, optimizer: optax.GradientTransformation,
               mesh: Mesh, rules: Optional[Rules] = None,
               seed: int = 0) -> TrainState:
    """Initialize the train state directly sharded (no host-side full
    materialization — params of a 7B model never exist unsharded)."""
    shardings = state_shardings(cfg, optimizer, mesh, rules)
    init = functools.partial(fresh_state, cfg, optimizer, seed=seed)
    with jax.set_mesh(mesh):
        return jax.jit(init, out_shardings=shardings)(jax.random.key(seed))


def _bytes_limit(mesh: Mesh) -> Optional[int]:
    """What one of this process's devices of the mesh can hold, or None
    where the backend does not say (the CPU)."""
    return (mesh.local_devices[0].memory_stats() or {}).get("bytes_limit")


def _step_bytes(compiled) -> int:
    """A device's bytes while the compiled step runs: arguments, results
    that are not donated arguments, temporaries, the program."""
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes
            + mem.generated_code_size_in_bytes)


def make_train_step(cfg: TransformerConfig, optimizer: optax.GradientTransformation,
                    mesh: Mesh, rules: Optional[Rules] = None,
                    donate: bool = True,
                    num_microbatches: Optional[int] = None) -> Callable[[TrainState, Dict], Tuple[TrainState, Dict]]:
    """Build the jitted sharded train step: (state, batch) → (state, metrics).

    The step differentiates the leaves ``trainable_mask`` marks and no
    other: a LoRA config's frozen base has no gradient in the program, and
    leaves it as it came in. ``metrics["grad_norm"]`` is the norm of the
    gradients that exist, the trainable leaves': the norm
    ``default_optimizer`` clips by. ``run.differentiated`` counts them
    (``leaves`` of ``of_leaves``, ``params`` of ``of_params``).

    With ``cfg.remat`` each block's checkpoint keeps the names of one rung
    of ``REMAT_LADDER``, and memory decides which: at the first call,
    where the device says what it holds, the step is compiled rung by
    rung, richest first, until one's count (``_step_bytes``) stays under
    ``bytes_limit`` less ``REMAT_HEADROOM``;
    the last rung, the bare checkpoint, is taken as it is. The rung that
    fits is compiled once (the call reuses the executable); a step that
    falls back pays one more compile a rung. Where no limit can be read the
    first rung stands. ``run.remat_kept`` is the names taken: ``()`` for
    the bare checkpoint, None without ``cfg.remat``.

    The residual stream between a block's sublayers is the rule table's
    ``("batch", "act_rows", "act_embed")``: rows over ``("sequence",
    "tensor")``, the hidden dimension whole. On every mesh whose `tensor`
    axis is larger than one (alone, beside `fsdp`/`data`, with `sequence`,
    inside the pipeline's `stage` region) the projections that meet it run
    as rings (``transformer._block``, ``parallel/ring.py``), and
    ``run.tensor_ring``, fixed when the step is traced, says what a block
    traced: ``{"rings": 4, "turns": 2, "rows": 1024}`` for the groups of
    projections (2 where the MLP is sparse), the `tensor` axis' size and a
    turn's rows of the sequence; None on any other mesh, where a block
    traces whole products. A sequence that `sequence` x `tensor` does not
    divide fails at trace time."""
    build_ts, build_mono = time.time(), time.monotonic()
    rules = _effective_rules(mesh, rules)
    attn = make_attn_fn(cfg, mesh, rules)
    n_stage = mesh_axis_size(mesh, "stage")
    pp_mesh = mesh if n_stage > 1 else None
    shardings = state_shardings(cfg, optimizer, mesh, rules)
    b_shard = batch_sharding(mesh, rules)
    repl = NamedSharding(mesh, P())
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    mask = trainable_mask(cfg, shapes)  # Python bools: static in the trace
    sizes = [(m, s.size) for m, s in zip(jax.tree.leaves(mask),
                                         jax.tree.leaves(shapes))]
    differentiated = {
        "leaves": sum(m for m, _ in sizes), "of_leaves": len(sizes),
        "params": sum(n for m, n in sizes if m),
        "of_params": sum(n for _, n in sizes),
    }

    def step(kept, state: TrainState, batch: Dict[str, jax.Array]):
        run.tensor_ring = tensor_ring(cfg, batch["tokens"].shape[1])
        params = state["params"]
        trainable = jax.tree.map(lambda m, p: p if m else None, mask, params)

        def lf(trainable):
            merged = jax.tree.map(lambda m, t, frozen: t if m else frozen,
                                  mask, trainable, params)
            return loss_fn(cfg, merged, batch, attn_fn=attn, mesh=pp_mesh,
                           num_microbatches=num_microbatches,
                           remat_kept=kept or ())

        (loss, metrics), grads = jax.value_and_grad(lf, has_aux=True)(trainable)
        gnorm = optax.global_norm(grads)
        # the optimizer keeps the whole tree (its state's, the checkpoint's);
        # a frozen leaf's place holds a constant that nothing reads
        grads = jax.tree.map(lambda m, g, p: g if m else jnp.zeros_like(p),
                             mask, grads, params)
        updates, new_opt = optimizer.update(grads, state["opt_state"], params)
        new_params = jax.tree.map(
            lambda m, p, u: optax.apply_updates(p, u) if m else p,
            mask, params, updates)
        metrics = dict(metrics, grad_norm=gnorm)
        new_state = {
            "params": new_params,
            "opt_state": new_opt,
            "step": state["step"] + 1,
            "rng": state["rng"],
        }
        return new_state, metrics

    in_batch_shardings = {"tokens": b_shard}
    jit_kwargs = dict(
        in_shardings=(shardings, None),
        out_shardings=(shardings, repl),
    )
    if donate:
        jit_kwargs["donate_argnums"] = (0,)
    ladder = REMAT_LADDER if cfg.remat else (None,)
    settled = len(ladder) == 1

    def rung(kept):
        # the first call of whichever rung the step comes to stand on is
        # booked, then `run._jitted` is the bare jitted callable
        return FirstCall(jax.jit(functools.partial(step, kept), **jit_kwargs),
                         "train_step", run.__dict__, "_jitted")

    def fits(jitted, state, batch, limit):
        """Compile the rung ahead of time (the call reuses the executable)
        and say whether it fits; booked as one `setup.step.rung`."""
        with setup_phase("ray_tpu.setup.step.rung",
                         kept=list(run.remat_kept)) as attrs:
            t0 = time.monotonic()
            lowered = jitted.lower(state, batch)
            t1 = time.monotonic()
            try:
                need = _step_bytes(lowered.compile())
            except jax.errors.JaxRuntimeError as e:
                if "RESOURCE_EXHAUSTED" not in str(e):
                    raise
                need = None  # the compiler itself refused it
            attrs.update(
                lower_s=t1 - t0, compile_s=time.monotonic() - t1, bytes=need,
                fits=need is not None and need <= (1 - REMAT_HEADROOM) * limit)
            return attrs["fits"]

    def settle(state, batch):
        """Down the ladder to the first rung that fits the device; booked
        as `setup.step.settle` with one `setup.step.rung` a rung compiled."""
        nonlocal settled
        settled = True
        with setup_phase("ray_tpu.setup.step.settle") as attrs:
            limit = _bytes_limit(mesh)
            if limit is not None:
                for kept in ladder:
                    if kept != run.remat_kept:
                        run.remat_kept, run._jitted = kept, rung(kept)
                    if kept == ladder[-1] or fits(run._jitted, state, batch,
                                                  limit):
                        break
            attrs.update(rungs_tried=ladder.index(run.remat_kept) + 1,
                         kept=list(run.remat_kept))

    def place(batch):
        return {k: jax.device_put(v, b_shard if v.ndim >= 2 else repl)
                for k, v in batch.items()}

    def run(state, batch):
        with jax.set_mesh(mesh):
            batch = place(batch)
            if not settled:
                settle(state, batch)
            return run._jitted(state, batch)

    def lower(state, batch):
        """The step lowered for these arguments, on the rung it stands on
        (the first until a call has settled it): ``.as_text()`` shows
        whether the kernels are in it, ``.compile()`` what it costs."""
        with jax.set_mesh(mesh):
            return run._jitted.lower(state, place(batch))

    run.lower = lower
    run.remat_kept = ladder[0]
    run._jitted = rung(ladder[0])
    run._shardings = shardings
    run._batch_sharding = b_shard
    run.differentiated = differentiated
    run.tensor_ring = None  # until a trace has seen the batch's rows
    record_setup_phase("ray_tpu.setup.step.build", build_ts, build_mono,
                       time.monotonic() - build_mono)
    return run


def make_eval_step(cfg: TransformerConfig, mesh: Mesh,
                   rules: Optional[Rules] = None) -> Callable:
    """(params, batch) → metrics, no grad."""
    rules = rules or DEFAULT_RULES
    attn = make_attn_fn(cfg, mesh, rules)

    @jax.jit
    def step(params, batch):
        _, metrics = loss_fn(cfg, params, batch, attn_fn=attn)
        return metrics

    def run(params, batch):
        with jax.set_mesh(mesh):
            return step(params, batch)

    return run
