"""`ray_tpu.setup.step.settle`: the train step's first call on its way down the checkpoint ladder, every rung it compiled ahead of time."""

from benchmarks import setup_record as S


def read(ctx):
    return S.phase_s(S.record(), "step.settle")
