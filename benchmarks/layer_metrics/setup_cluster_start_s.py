"""`ray_tpu.setup.init`: ray_tpu.init() in the driver, whole (the GCS and the raylet spawned and answering, the driver connected)."""

from benchmarks import setup_record as S


def read(ctx):
    return S.phase_s(S.record(), "init")
