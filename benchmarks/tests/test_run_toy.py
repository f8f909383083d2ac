"""run.py end to end on the CPU: every cell with --toy, the refusal to run a
real cell without its TPU chips, and a dummy configuration, traffic mix,
runner and per-layer metric added as new files plus entries, with no file
edited.

Toy runs print the device they ran on (cpu) and are never a result.
"""

import json
import os
import pickle
import shutil
import subprocess
import sys
import time

import pytest

from benchmarks import harness

RUN = os.path.join(harness.HERE, "run.py")


def _run(args, cwd=harness.ROOT, timeout=420):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, RUN] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def _cells():
    return [w["name"] for w in harness.load_benchmark()["workloads"]]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", _cells())
def test_toy_cell_end_to_end(workload, trace):
    proc = _run(["--workload", workload, "--seed", "3", "--seconds", "3",
                 "--trace", str(trace), "--toy"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line.get("problems")
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"  # a toy run is no result
    bench = harness.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    assert line["device"]["count"] == cell["chips"]

    def expected(kind):
        return {m["name"]: m["unit"] for m in bench[kind]
                if workload in m.get("workloads", [workload])}

    if trace:
        names = expected("per_layer")
        assert set(line["metrics"]) <= set(names)
        # counters and host clocks are there on any device; what needs a
        # published peak (mfu, roofline) is left out on a CPU
        assert len(line["metrics"]) >= 4
        assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
        assert line["breakdown"]["device_ops"]
    else:
        names = expected("end_to_end")
        assert set(line["metrics"]) == set(names) and "setup_s" in names
    for name, m in line["metrics"].items():
        assert m["unit"] == names[name] and m["value"] == m["value"]


def test_a_real_run_without_its_chips_fails_and_prints_no_result():
    proc = _run(["--workload", "train-lora-1chip", "--seed", "0",
                 "--seconds", "1", "--trace", "0"])
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert proc.stdout.strip() == ""


def test_without_the_program_nothing_runs(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "train-lora-1chip",
         "--seed", "0", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


DUMMY_RUNNER = '''
def run(cell, args):
    return {"correct": True, "attempted": cell["traffic"]["n"], "failed": 0,
            "device": {"platform": "none", "kind": "none", "count": 1,
                       "memory_peak_bytes": 0},
            "counters": {"setup_s": 0.5, "dummy_rate": 2.0 * args["seed"],
                         "knob": cell["config"]["knob"],
                         "dummy_capture": {"ms_per_req": 3.0}},
            "trace": {}}
'''


def _checkout_with_a_dummy_cell(tmp_path, runner_source=DUMMY_RUNNER):
    """A copy of the benchmark beside the program, plus ``dummy-cell``: a
    configuration, a traffic mix, a runner, its answers to the questions
    several configurations share and two per-layer metrics as new files and
    entries, and its name on a shared entry's list. Returns the copied files
    as they were before."""
    shutil.copytree(harness.HERE, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(harness.ROOT, "ray_tpu"), tmp_path / "ray_tpu")
    before = {p: open(os.path.join(root, p), "rb").read()
              for root, _d, files in os.walk(tmp_path / "benchmarks")
              for p in files}
    b = tmp_path / "benchmarks"
    (b / "configs" / "dummy-config.json").write_text(json.dumps(
        {"name": "dummy-config", "runner": "dummy", "knob": 7}))
    (b / "traffic" / "dummy-mix.json").write_text(json.dumps({"n": 11}))
    (b / "runners" / "dummy.py").write_text(runner_source)
    (b / "answers" / "dummy.py").write_text(
        "ANSWERS = {'whole_prefill': 'dummy_capture'}\n")
    (b / "layer_metrics" / "dummy_knob.py").write_text(
        "def read(ctx):\n    return ctx['counters']['knob'] * 1.5\n")
    (b / "layer_metrics" / "dummy_absent.py").write_text(
        "def read(ctx):\n    return None\n")
    bench = harness.load_benchmark()
    bench["configs"].append({"name": "dummy-config", "source": "none",
                             "file": "benchmarks/configs/dummy-config.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "dummy-cell", "config": "dummy-config",
                               "traffic": "dummy-mix", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "dummy_rate", "unit": "x/s",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["dummy-cell"]})
    for name in ("dummy_knob", "dummy_absent"):
        bench["per_layer"].append({
            "name": name, "unit": "x", "better": "higher",
            "source": "program_counter", "layer": "dummy",
            "moves": "dummy_rate", "workloads": ["dummy-cell"]})
    next(m for m in bench["per_layer"] if m["name"] ==
         "whole_prefill_ms_per_req")["workloads"].append("dummy-cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return before


def test_a_cell_is_added_with_new_files_and_entries_alone(tmp_path):
    """A later PR adds a configuration, a traffic mix, a runner, its answers
    and a per-layer metric, and joins a shared entry's list: new files,
    entries in BENCHMARK.json, and not one edit to a file that is there."""
    before = _checkout_with_a_dummy_cell(tmp_path)

    def run(trace):
        proc = subprocess.run(
            [sys.executable, "benchmarks/run.py", "--workload", "dummy-cell",
             "--seed", "4", "--seconds", "1", "--trace", str(trace),
             "--keep-record", str(tmp_path / "record.pkl")],
            cwd=tmp_path, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr[-2000:]
        return json.loads(proc.stdout.strip().splitlines()[-1])

    line = run(0)
    assert line["attempted"] == 11
    assert line["metrics"] == {"dummy_rate": {"value": 8.0, "unit": "x/s"},
                               "setup_s": {"value": 0.5, "unit": "s"}}
    # a reader that finds nothing returns nothing: the metric is left out
    assert run(1)["metrics"] == {
        "dummy_knob": {"value": 10.5, "unit": "x"},
        "whole_prefill_ms_per_req": {"value": 3.0, "unit": "ms"}}
    # what the readers were given, kept for `same_readings.py`
    with open(tmp_path / "record.pkl", "rb") as f:
        assert pickle.load(f)["counters"]["knob"] == 7
    after = {p: open(os.path.join(root, p), "rb").read()
             for root, _d, files in os.walk(tmp_path / "benchmarks")
             for p in files if "__pycache__" not in root}
    assert all(after[p] == data for p, data in before.items())


# The limit of a run, at a scale a test can wait for: run.py's hidden
# --limit-s stands for CHILD_LIMIT_S (1150 s), and the dump of every
# thread's frames is armed DUMP_BEFORE_S (10 s) under it. The watchdog this
# replaced fired every 300 s, at 300 / 1150 of the limit.
LIMIT_S = 20.0
OLD_WATCHDOG_AT_S = LIMIT_S * 300 / 1150
HEALTHY_BUT_LONG = DUMMY_RUNNER + f"""
import time
_quick = run

def run(cell, args):
    time.sleep({OLD_WATCHDOG_AT_S + 0.5})
    return _quick(cell, args)
"""
HANGS = """
import time

def hangs_in_this_frame():
    time.sleep(3600)

def run(cell, args):
    hangs_in_this_frame()
"""
KILLED = """
import os, signal

def run(cell, args):
    os.kill(os.getpid(), signal.SIGSEGV)
"""


@pytest.mark.parametrize("runner,rc,stderr_has,dump_has", [
    (HEALTHY_BUT_LONG, 0, None, None),
    (HANGS, 1, "timed out at the limit of 20 s", "hangs_in_this_frame"),
    (KILLED, 1, "killed by signal 11 (Segmentation fault)", None),
], ids=["healthy-past-the-old-watchdog", "hangs", "killed-by-a-signal"])
def test_a_run_is_lost_only_to_the_program_or_the_limit(
        tmp_path, runner, rc, stderr_has, dump_has):
    """No dump of threads fires in a run that still ends; a hang is ended at
    the limit and leaves where it hung; a signal is named."""
    from benchmarks import run as run_py

    _checkout_with_a_dummy_cell(tmp_path, runner)
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "dummy-cell",
         "--seed", "4", "--seconds", "1", "--trace", "0",
         "--limit-s", str(LIMIT_S)],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    took = time.monotonic() - t0
    assert proc.returncode == rc, proc.stderr[-2000:]
    dump = tmp_path / ".bench_out" / "dummy-cell" / run_py.DUMP_FILE
    if rc == 0:
        assert took > OLD_WATCHDOG_AT_S
        line = json.loads(proc.stdout.strip().splitlines()[-1])
        assert line["correct"] is True and line["attempted"] == 11
        assert "Timeout (" not in proc.stderr
        assert "most recent call first" not in proc.stderr
    else:
        assert proc.stdout.strip() == ""
        assert stderr_has in proc.stderr
        assert proc.stderr.rstrip().splitlines()[-1].startswith("run failed")
    if dump_has is None:
        assert not dump.exists()
    else:
        assert LIMIT_S <= took < LIMIT_S + 15
        assert dump_has in dump.read_text()
        assert str(dump) in proc.stderr and dump_has in proc.stderr
