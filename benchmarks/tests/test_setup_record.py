"""The set-up record's readers (PR 56): pure functions over a hand-made
record, each of the ten entries' readers on it, ``None`` where a cell's
record lacks the phase, and a ``--toy --trace 1`` run of a serve and of a
train cell that prints every new entry the cell lists."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import harness
from benchmarks import setup_record as S

RUN = os.path.join(harness.HERE, "run.py")
NEW = ("setup_cluster_start_s", "setup_serve_deploy_wait_s",
       "setup_worker_boot_s", "setup_backend_init_s", "setup_params_init_s",
       "setup_engine_build_s", "setup_program_trace_lower_s",
       "setup_program_first_run_s", "setup_step_settle_s",
       "setup_attributed_share")
T0 = 1000.0  # the run's start on time.time(); its monotonic clock reads 50


def ev(name, worker, start, dur, **attrs):
    """A phase that starts ``start`` seconds into the run."""
    return {"name": S.P + name, "worker": worker, "ts": T0 + start,
            "mono": 50.0 + start, "gts": 50.0 + start, "dur": dur,
            "attrs": attrs}


def serve_record():
    """A serve cell's bring-up: the driver, the controller's worker, the
    replica. 40 s of set-up, the window opens at T0 + 40."""
    return [
        ev("init", "detached", 1.0, 2.0),
        ev("init.gcs", "detached", 1.0, 0.9),
        ev("init.raylet", "detached", 1.9, 1.0, native_built=False),
        ev("serve.run", "driver", 4.0, 16.0),
        ev("serve.controller", "driver", 4.0, 0.1),
        ev("serve.deploy", "driver", 4.1, 15.9),
        ev("worker.boot", "ctl", 4.2, 1.0, ready_s=0.9, pooled=False),
        ev("actor.init", "ctl", 5.2, 0.1, cls="ServeController"),
        ev("worker.boot", "rep", 5.5, 1.5, ready_s=1.4, pooled=False),
        ev("actor.init", "rep", 7.0, 12.5, cls="Replica"),
        ev("engine.build", "rep", 7.5, 11.5),
        ev("engine.backend", "rep", 7.6, 4.0),
        ev("engine.params", "rep", 11.6, 5.0, bytes=1 << 30, programs=25),
        ev("engine.cache", "rep", 17.0, 1.0, bytes=1 << 29),
        ev("program", "rep", 21.0, 6.0, program="prefill_2048", trace_s=1.0,
           lower_s=0.5, compile_s=2.5, first_run_s=2.0, cache="hit"),
        ev("program", "rep", 27.0, 4.0, program="decode", trace_s=1.5,
           lower_s=0.5, compile_s=1.0, first_run_s=1.0, cache="miss"),
        # a program whose first call ran past the window's opening
        ev("program", "rep", 39.0, 3.0, program="reset_state", trace_s=0.1,
           lower_s=0.1, compile_s=0.3, first_run_s=2.5, cache="hit"),
    ]


def train_record():
    return [
        ev("init", "detached", 1.0, 2.0),
        ev("step.build", "driver", 9.0, 0.5),
        ev("step.settle", "driver", 10.0, 8.0, rungs_tried=2, kept=[]),
        ev("step.rung", "driver", 10.0, 4.0, kept=["attn_q"], lower_s=1.0,
           compile_s=3.0, bytes=9, fits=False),
        ev("step.rung", "driver", 14.0, 4.0, kept=[], lower_s=1.2,
           compile_s=2.8, bytes=5, fits=True),
        ev("program", "driver", 18.0, 3.0, program="train_step", trace_s=0.0,
           lower_s=0.3, compile_s=0.2, first_run_s=2.5, cache="hit"),
    ]


def read(entry, rec, monkeypatch, **counters):
    monkeypatch.setattr(S, "record", lambda: rec)
    return harness.load_reader(entry).read(
        {"cell": {}, "counters": counters, "trace": {}, "device": {}})


def test_phase_s_sums_a_phase_and_knows_a_worker():
    rec = serve_record()
    assert S.phase_s(rec, "init") == 2.0
    assert S.phase_s(rec, "actor.init") == pytest.approx(12.6)
    assert S.phase_s(rec, "actor.init", worker="rep") == 12.5
    assert S.phase_s(rec, "step.settle") is None
    assert S.phase_s(rec, "actor.init", worker="nobody") is None


def test_chip_worker_is_who_built_the_engine_or_the_step():
    assert S.chip_worker(serve_record()) == "rep"
    assert S.chip_worker(train_record()) == "driver"
    assert S.chip_worker(serve_record()[:4]) is None


def test_programs_are_the_chip_workers_first_calls_and_rungs():
    progs = S.programs(train_record(), worker="driver")
    assert [(p["phase"], p.get("program")) for p in progs] == [
        ("step.rung", None), ("step.rung", None), ("program", "train_step")]
    assert S.programs(train_record(), worker="rep") == []


def test_union_counts_overlaps_of_two_workers_once():
    rec = [ev("actor.init", "a", 0.0, 10.0), ev("engine.build", "a", 2.0, 3.0),
           ev("actor.init", "b", 8.0, 4.0), ev("init", "c", 20.0, 1.0)]
    assert S.union_s(rec) == pytest.approx(12.0 + 1.0)
    # cut to the run: from its start to the window's opening
    assert S.union_s(rec, T0 + 1.0, T0 + 11.0) == pytest.approx(10.0)
    assert S.union_s(rec, until_wall=T0 + 20.5) == pytest.approx(12.5)
    assert S.union_s([]) == 0.0


def test_a_pooled_workers_boot_is_no_part_of_the_run():
    rec = [ev("worker.boot", "w", -300.0, 305.0, ready_s=1.0, pooled=True),
           ev("actor.init", "w", 5.0, 2.0)]
    assert S.union_s(rec) == pytest.approx(2.0)


def test_each_serve_reader_on_the_hand_made_record(monkeypatch):
    rec = serve_record()
    want = {
        "setup_cluster_start_s": 2.0,
        "setup_serve_deploy_wait_s": 15.9 - 12.5,
        "setup_worker_boot_s": 1.5,
        "setup_backend_init_s": 4.0,
        "setup_params_init_s": 5.0,
        "setup_engine_build_s": 11.5 - 4.0 - 5.0,
        "setup_program_trace_lower_s": 1.5 + 2.0 + 0.2,
        "setup_program_first_run_s": 2.0 + 1.0 + 2.5,
        "setup_step_settle_s": None,
    }
    for entry, value in want.items():
        got = read(entry, rec, monkeypatch)
        assert got == (pytest.approx(value) if value is not None else None), \
            entry
    # init 1-3, serve.run 4-20, programs 21-31 and 39-40 (cut at the opening)
    share = read("setup_attributed_share", rec, monkeypatch,
                 setup_s=40.0, open_wall=T0 + 40.0, replica_start_s=16.1)
    assert share == pytest.approx(100.0 * (2.0 + 16.0 + 10.0 + 1.0) / 40.0)


def test_each_train_reader_on_the_hand_made_record(monkeypatch):
    rec = train_record()
    assert read("setup_step_settle_s", rec, monkeypatch) == 8.0
    assert read("setup_program_trace_lower_s", rec, monkeypatch) == \
        pytest.approx(1.0 + 1.2 + 0.3)
    assert read("setup_program_first_run_s", rec, monkeypatch) == 2.5
    for entry in ("setup_serve_deploy_wait_s", "setup_worker_boot_s",
                  "setup_backend_init_s", "setup_params_init_s",
                  "setup_engine_build_s"):
        assert read(entry, rec, monkeypatch) is None, entry
    # the train runner carries no open_wall: the union as it is
    share = read("setup_attributed_share", rec, monkeypatch, setup_s=25.0,
                 fit_to_first_step_s=16.0, init_s=3.0)
    assert share == pytest.approx(100.0 * (2.0 + 0.5 + 8.0 + 3.0) / 25.0)


def test_a_pooled_chip_worker_reads_a_boot_of_zero(monkeypatch):
    rec = [ev("worker.boot", "rep", -60.0, 67.0, ready_s=1.0, pooled=True),
           ev("engine.build", "rep", 7.5, 1.0)]
    assert read("setup_worker_boot_s", rec, monkeypatch) == 0.0


@pytest.mark.parametrize("entry", NEW)
def test_a_reader_never_invents_a_zero(entry, monkeypatch):
    """A program that keeps no record (a parent commit), or a record without
    the phase: no value, and no error."""
    assert read(entry, [], monkeypatch, setup_s=10.0) is None
    only_init = [ev("init", "detached", 1.0, 2.0)]
    if entry not in ("setup_cluster_start_s", "setup_attributed_share"):
        assert read(entry, only_init, monkeypatch, setup_s=10.0) is None


def test_a_program_without_the_record_gives_an_empty_one(monkeypatch):
    from ray_tpu import observability

    monkeypatch.delattr(observability, "setup_record")
    assert S.record() == []


def test_the_entries_are_in_the_benchmark_with_their_cells():
    bench = harness.load_benchmark()
    cells = [w["name"] for w in bench["workloads"]]
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    assert set(NEW) <= set(per_layer)  # wherever in the list they lie
    for name in NEW:
        m = per_layer[name]
        assert (m["moves"], m["source"]) == ("setup_s", "program_span")
        assert harness.load_reader(name).__file__.endswith(
            os.path.join("layer_metrics", name + ".py"))
    serve = [c for c in cells if c.startswith("serve-")]
    assert per_layer["setup_attributed_share"]["workloads"] == cells
    assert per_layer["setup_backend_init_s"]["workloads"] == serve
    assert per_layer["setup_step_settle_s"]["workloads"] == [
        c for c in cells if c.startswith("train-")]
    assert (per_layer["setup_attributed_share"]["better"],
            per_layer["setup_attributed_share"]["unit"]) == ("higher", "%")


@pytest.mark.parametrize("workload", ["serve-chat-steady", "train-lora-1chip"])
def test_a_toy_traced_run_prints_every_new_entry_of_its_cell(workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", workload, "--seed", "3000000019",
         "--seconds", "3", "--trace", "1", "--toy"], cwd=harness.ROOT,
        env=env, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line.get("problems")
    listed = [m["name"] for m in harness.load_benchmark()["per_layer"]
              if m["name"] in NEW and workload in m["workloads"]]
    assert len(listed) == (9 if workload.startswith("serve") else 5)
    for name in listed:
        assert line["metrics"][name]["value"] >= 0, name
    assert 0 < line["metrics"]["setup_attributed_share"]["value"] <= 100
    # the table of phases and the remainder are named on stderr
    assert "[setup.phase" in proc.stderr and "remainder_s=" in proc.stderr
