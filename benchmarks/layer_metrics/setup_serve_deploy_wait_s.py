"""`ray_tpu.setup.serve.deploy` less the replica's `ray_tpu.setup.actor.init`: scheduling, the lease, the worker's boot, the health check."""

from benchmarks import setup_record as S


def read(ctx):
    rec = S.record()
    deploy = S.phase_s(rec, "serve.deploy")
    init = S.phase_s(rec, "actor.init", worker=S.chip_worker(rec))
    return deploy - init if deploy is not None and init is not None else None
