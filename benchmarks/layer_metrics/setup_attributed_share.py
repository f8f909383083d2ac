"""Share of `setup_s` that some process's `ray_tpu.setup.*` phase covers: the union of every process's intervals (overlaps once, cut at the window's opening where the runner carries `open_wall`) over `setup_s`. The rest is the benchmark's own (its reference check, its ramp, the train runner's `_init_state`) and what no phase names; the table goes to stderr."""

from benchmarks import harness
from benchmarks import setup_record as S

RUNNER_CLOCKS = ("replica_start_s", "warmup_s", "fit_to_first_step_s",
                 "init_s", "compile_s")  # what the runner timed from outside


def read(ctx):
    rec, counters = S.record(), ctx["counters"]
    setup_s = counters.get("setup_s")
    if not rec or not setup_s:
        return None
    opened = counters.get("open_wall")
    covered = S.union_s(rec, opened - setup_s if opened else None, opened)
    share = 100.0 * covered / setup_s  # over 100: this reader's fault
    chip = S.chip_worker(rec)
    # where the run's clock started on `mono`: `setup_s` before the window
    # opened where the runner says when that was, else the first phase
    first = rec[0]
    origin = first["mono"] - (first["ts"] - (opened - setup_s)) if opened \
        else first["mono"]
    harness.say("setup", setup_s=round(setup_s, 2),
                attributed_s=round(covered, 2), share=round(share, 1),
                remainder_s=round(setup_s - covered, 2), chip_worker=chip,
                at_is_since="run.py's start" if opened else "the first phase",
                runner_clocks={k: round(counters[k], 2) for k in RUNNER_CLOCKS
                               if counters.get(k) is not None})
    for name, worker, seconds, n, at in S.by_seconds(rec):
        harness.say("setup.phase", phase=name, s=round(seconds, 3), n=n,
                    at=round(at - origin, 2),
                    worker="chip" if worker == chip else worker)
    for p in sorted(S.programs(rec, chip),
                    key=lambda p: -p.get("first_run_s", 0.0)):
        harness.say("setup.program", **{
            k: round(v, 3) if isinstance(v, float) else v
            for k, v in p.items() if k != "kept"})
    return share
