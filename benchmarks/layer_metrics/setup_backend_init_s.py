"""`ray_tpu.setup.engine.backend`: the replica's first `jax.devices()`, the TPU runtime's start."""

from benchmarks import setup_record as S


def read(ctx):
    rec = S.record()
    return S.phase_s(rec, "engine.backend", worker=S.chip_worker(rec))
