"""`ray_tpu.setup.engine.build` less `.backend` and `.params`: the rest of the engine's constructor (the generator, the batcher, the cache's allocation)."""

from benchmarks import setup_record as S


def read(ctx):
    rec = S.record()
    worker = S.chip_worker(rec)
    build = S.phase_s(rec, "engine.build", worker=worker)
    if build is None:
        return None
    return build - sum(S.phase_s(rec, inner, worker=worker) or 0.0
                       for inner in ("engine.backend", "engine.params"))
