"""`first_run_s` summed over the chip process's `ray_tpu.setup.program`: each first call's wall less JAX's trace, lower and compile (or cache read) events: the executable's load, the first transfers, the first execution, waited for."""

from benchmarks import setup_record as S


def read(ctx):
    rec = S.record()
    runs = [p["first_run_s"] for p in S.programs(rec, S.chip_worker(rec))
            if "first_run_s" in p]
    return sum(runs) if runs else None
