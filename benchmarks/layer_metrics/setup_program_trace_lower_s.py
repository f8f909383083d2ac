"""Python tracing and lowering of the chip process's jitted programs: `trace_s` + `lower_s` summed over its `ray_tpu.setup.program` (first calls) and `ray_tpu.setup.step.rung` (the train step's ahead-of-time compiles, whose `lower_s` holds the trace)."""

from benchmarks import setup_record as S


def read(ctx):
    rec = S.record()
    progs = S.programs(rec, worker=S.chip_worker(rec))
    if not progs:
        return None
    return sum(p.get("trace_s", 0.0) + p.get("lower_s", 0.0) for p in progs)
