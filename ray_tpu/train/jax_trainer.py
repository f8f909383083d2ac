"""JaxTrainer: controller + worker group (Train v2 architecture).

Reference call stack (SURVEY.md §3.4): `JaxTrainer.fit()`
(train/v2/jax/jax_trainer.py:20) → TrainController actor
(v2/_internal/execution/controller/controller.py:105) → WorkerGroup
(worker_group/worker_group.py:88, one actor per TPU host) →
`_setup_jax_distributed_environment` (v2/jax/config.py:60) → user loop.

TPU-native differences:
- workers bootstrap `jax.distributed` + MEGASCALE (parallel/bootstrap.py)
  instead of torch process groups;
- parallelism comes from the ScalingConfig's MeshSpec, not DDP wrappers;
- a failed worker kills the whole slice's ICI program, so the failure
  domain is the worker GROUP: on failure we restart the group from the
  latest checkpoint (reference FailurePolicy semantics,
  failure_handling/failure_policy.py:14).
"""

from __future__ import annotations

import os
import tempfile
import traceback
from typing import Any, Callable, Dict, List, Optional

import ray_tpu
from ray_tpu.parallel.bootstrap import (
    HostGroupSpec, configure_compilation_cache, initialize_host,
)
from ray_tpu.train.checkpoint import Checkpoint, CheckpointManager
from ray_tpu.train.config import FailureConfig, Result, RunConfig, ScalingConfig
from ray_tpu.train.session import TrainContext, _set_session


def _run_worker_loop(
    train_fn: Callable,
    config: Optional[Dict[str, Any]],
    world_rank: int,
    world_size: int,
    experiment_name: str,
    storage_path: Optional[str],
    latest_checkpoint_path: Optional[str],
    host_spec: Optional[Dict[str, Any]],
) -> Dict[str, Any]:
    """Body executed on each worker (actor or in-process). Returns the
    ordered report stream + error info."""
    if host_spec:
        initialize_host(HostGroupSpec(**host_spec))
    configure_compilation_cache()
    ctx = TrainContext(
        world_rank=world_rank,
        world_size=world_size,
        node_rank=world_rank,
        experiment_name=experiment_name,
        storage_path=storage_path,
        latest_checkpoint=(
            Checkpoint(latest_checkpoint_path) if latest_checkpoint_path else None
        ),
    )
    from ray_tpu.train.session import _session as _session_tls

    prev_ctx = getattr(_session_tls, "ctx", None)  # restore outer session
    _set_session(ctx)                               # (Train-in-Tune nesting)
    error = None
    try:
        if config is not None:
            train_fn(config)
        else:
            train_fn()
    except BaseException as e:  # reported to the controller, not raised here
        error = "".join(traceback.format_exception(type(e), e, e.__traceback__))
    finally:
        _set_session(prev_ctx)
    reports: List[Dict[str, Any]] = []
    while not ctx._report_queue.empty():
        reports.append(ctx._report_queue.get())
    return {"rank": world_rank, "reports": reports, "error": error}


@ray_tpu.remote
class TrainWorker:
    """One per host (reference: worker_group/worker_group.py:88)."""

    def run(self, train_fn, config, world_rank, world_size, experiment_name,
            storage_path, latest_checkpoint_path, host_spec):
        return _run_worker_loop(
            train_fn, config, world_rank, world_size, experiment_name,
            storage_path, latest_checkpoint_path, host_spec,
        )

    def ping(self):
        return "ok"


class JaxTrainer:
    """Data-parallel-style trainer for JAX/TPU workloads.

    `train_loop_per_worker(config)` runs on every worker with a live
    session (ray_tpu.train.report / get_context). Reference:
    train/v2/jax/jax_trainer.py:20 + data_parallel_trainer.py:159.
    """

    def __init__(
        self,
        train_loop_per_worker: Callable,
        *,
        train_loop_config: Optional[Dict[str, Any]] = None,
        scaling_config: Optional[ScalingConfig] = None,
        run_config: Optional[RunConfig] = None,
        resume_from_checkpoint: Optional[Checkpoint] = None,
    ):
        self._train_fn = train_loop_per_worker
        self._config = train_loop_config
        self._scaling = scaling_config or ScalingConfig()
        self._run = run_config or RunConfig()
        self._resume = resume_from_checkpoint

    # -- controller loop (reference: controller.py:105) -----------------
    def fit(self) -> Result:
        name = self._run.name or "train_run"
        storage = self._run.storage_path or os.path.join(
            tempfile.gettempdir(), "ray_tpu_train", name
        )
        ckpt_mgr = CheckpointManager(
            storage, self._run.checkpoint_config.num_to_keep
        )
        latest = self._resume or ckpt_mgr.latest()
        failure: FailureConfig = self._run.failure_config
        attempts_left = failure.max_failures
        last_error: Optional[str] = None

        while True:
            results = self._run_attempt(name, storage, latest)
            errors = [r["error"] for r in results if r["error"]]
            rank0 = next((r for r in results if r["rank"] == 0), results[0])
            # Register rank-0 checkpoints (workers write per-report dirs
            # under storage; the manager applies keep-K retention).
            last_metrics: Dict[str, Any] = {}
            for rep in rank0["reports"]:
                last_metrics = rep["metrics"]
                if rep["checkpoint"]:
                    ckpt_mgr.register(Checkpoint(rep["checkpoint"]), rep["metrics"])
            latest = ckpt_mgr.latest()
            if not errors:
                return Result(
                    metrics=last_metrics, checkpoint=latest, path=storage
                )
            last_error = errors[0]
            if attempts_left == 0:
                return Result(
                    metrics=last_metrics,
                    checkpoint=latest,
                    error=RuntimeError(last_error),
                    path=storage,
                )
            if attempts_left > 0:
                attempts_left -= 1
            # group restart from latest checkpoint (elastic recovery)

    def _run_attempt(self, name: str, storage: str,
                     latest: Optional[Checkpoint]) -> List[Dict[str, Any]]:
        from ray_tpu.train.scaling_policy import decide_num_workers

        # elastic: size this (re)start to what the cluster can host now
        # (reference: ElasticScalingPolicy elastic.py:29) — a lost node
        # shrinks the group, restarting from the latest checkpoint
        n = decide_num_workers(self._scaling)
        latest_path = latest.path if latest else None
        if n <= 1:
            # In-process fast path (reference: local mode,
            # train/v2/_internal/execution/local_mode/) — this is the
            # single-host TPU case: no actor hop on the hot path.
            return [
                _run_worker_loop(
                    self._train_fn, self._config, 0, 1, name, storage,
                    latest_path, None,
                )
            ]
        res = self._scaling.worker_resources()
        workers = [
            TrainWorker.options(
                name=f"{name}-worker-{i}",
                num_cpus=res.get("CPU", 1),
                num_tpus=res.get("TPU", 0),
            ).remote()
            for i in range(n)
        ]
        try:
            specs = self._host_specs(n)
            futs = [
                w.run.remote(
                    self._train_fn, self._config, i, n, name, storage,
                    latest_path, specs[i],
                )
                for i, w in enumerate(workers)
            ]
            return ray_tpu.get(futs)
        finally:
            for w in workers:
                try:
                    ray_tpu.kill(w)
                except Exception:
                    pass

    def _host_specs(self, n: int) -> List[Optional[Dict[str, Any]]]:
        """jax.distributed bootstrap specs — only for real multi-host TPU
        groups (CPU test workers run independent jax instances)."""
        if not self._scaling.use_tpu or n <= 1:
            return [None] * n
        from ray_tpu.parallel.bootstrap import local_process_specs

        specs = local_process_specs(n)
        import dataclasses as dc

        return [dc.asdict(s) for s in specs]
