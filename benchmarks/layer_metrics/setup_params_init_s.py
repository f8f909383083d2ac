"""`ray_tpu.setup.engine.params`: the replica's `init_params` (a pattern's `_draw`), waited for."""

from benchmarks import setup_record as S


def read(ctx):
    rec = S.record()
    return S.phase_s(rec, "engine.params", worker=S.chip_worker(rec))
