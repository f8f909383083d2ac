#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

runs one cell of ``BENCHMARK.json`` once and prints, as the last line of its
standard output, one JSON object with ``correct``, ``attempted``, ``failed``,
``metrics`` and ``device`` (with ``--trace 1`` also ``breakdown``). With
``--trace 0`` the metrics are the cell's end-to-end metrics, taken with the
profiler off; with ``--trace 1`` they are its per-layer metrics.

Driven by data: the cell names its configuration and its traffic mix, the
configuration names its runner, and every per-layer metric has a reader
under ``layer_metrics/``; all are found by name, so a later PR adds
a file and an entry and edits nothing here.

One process for each chip: this parent never imports JAX. It runs the cell
in a child process tree of its own session, waits until that tree has ended
(killing what is left), and prints the child's result. Without the cell's
TPU chips the child fails, and this command exits non-zero and prints no
result. ``--toy`` runs the same code at debug widths on whatever device JAX
finds; it prints that device (``cpu``) and is never a result.

The benchmark never touches a run that can still end well: a run is lost
only to the program or to ``CHILD_LIMIT_S``. A hang shows where it hangs
once, ``DUMP_BEFORE_S`` under that limit, in a file of the cell's
``.bench_out`` directory, and the parent copies its end to stderr once the
run is lost.
"""

from __future__ import annotations

import argparse
import json
import os
import pickle
import shutil
import signal
import subprocess
import sys
import time

T0_WALL = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402

CHILD_LIMIT_S = 1150  # the contract allows a compiling run 1200 s
DUMP_BEFORE_S = 10  # a run this near its limit is beyond saving
DUMP_FILE = "hang_dump.txt"  # in the cell's out_dir


def child(args) -> int:
    """Run the cell in this process (tree); write the record to a file."""
    import faulthandler

    # a hang shows where it hangs: once, when the parent's limit is about to
    # end the tree anyway, and in a file. A dump stops every thread of this
    # process (the load generator and the proxy) while it is written, so
    # none may fire in a run that still ends (PR 51)
    with open(os.path.join(args.out_dir, DUMP_FILE), "w") as dump:
        faulthandler.dump_traceback_later(args.limit_s - DUMP_BEFORE_S,
                                          file=dump)
        try:
            return _run_cell(args)
        finally:
            faulthandler.cancel_dump_traceback_later()


def _run_cell(args) -> int:
    cell = harness.load_cell(args.workload, toy=args.toy)
    runner = harness.load_module("runners", cell["config"]["runner"])
    run_args = {"seed": args.seed, "seconds": args.seconds,
                "trace": bool(args.trace), "t0_wall": args.t0_wall,
                "out_dir": args.out_dir, "sample_to": args.sample_to}
    rec = runner.run(cell, run_args)
    if args.keep_record:  # what the readers are given, less the cell
        with open(args.keep_record, "wb") as f:
            pickle.dump({k: rec[k] for k in ("counters", "trace", "device")}, f)
    result = assemble(cell, rec, bool(args.trace))
    with open(args.child, "w") as f:
        json.dump(result, f)
    return 0


def assemble(cell: dict, rec: dict, traced: bool) -> dict:
    """The result line from a runner's record: end-to-end metrics from its
    counters, per-layer metrics through their readers."""
    ctx = {"cell": cell, "counters": rec["counters"], "trace": rec["trace"],
           "device": rec["device"]}
    metrics = {}
    if traced:
        for m in cell["per_layer"]:
            value = harness.load_reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = harness.metric(value, m["unit"])
    else:
        for m in cell["end_to_end"]:
            value = rec["counters"].get(m["name"])
            if value is not None:
                metrics[m["name"]] = harness.metric(value, m["unit"])
    device = dict(rec["device"])
    result = {"correct": bool(rec["correct"]), "attempted": rec["attempted"],
              "failed": rec["failed"], "metrics": metrics, "device": device}
    if traced and rec["trace"]:
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]
        result["breakdown"] = {"device_ops": rec["trace"]["device_ops"][:10],
                               "idle_gaps": rec["trace"]["idle_gaps"][:10]}
        # the same run's end-to-end numbers, under the profiler: against an
        # untraced run they are what the instrumentation costs
        result["end_to_end_under_trace"] = {
            m["name"]: rec["counters"].get(m["name"])
            for m in cell["end_to_end"] if m["name"] != "setup_s"}
    if rec.get("problems"):
        result["problems"] = rec["problems"][:8]
    return result


def _session_members(sid: int) -> list:
    out = []
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        try:
            with open(f"/proc/{name}/stat") as f:
                fields = f.read().rsplit(")", 1)[1].split()
        except (OSError, IndexError):
            continue
        if int(fields[3]) == sid and fields[0] != "Z":
            out.append(int(name))
    return out


def _end_tree(sid: int, grace_s: float) -> list:
    """Wait for session ``sid`` to empty; kill what is left."""
    deadline = time.monotonic() + grace_s
    while _session_members(sid) and time.monotonic() < deadline:
        time.sleep(0.1)
    left = _session_members(sid)
    if left:
        try:
            os.killpg(sid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        time.sleep(0.5)
    return left


def _how_it_failed(returncode: int, timed_out: bool, limit_s: float,
                   dump_path: str) -> str:
    """The last words of a lost run: a child that ended on a signal is told
    from one that exited, and a run ended at the limit says where it hung."""
    if returncode < 0:
        ended = (f"the child was killed by signal {-returncode} "
                 f"({signal.strsignal(-returncode)})")
    else:
        ended = f"the child exited with code {returncode}"
    if not timed_out:
        return f"run failed: {ended}; no result"
    if os.path.exists(dump_path):
        with open(dump_path, errors="replace") as f:
            where = (f"every thread's frames {DUMP_BEFORE_S} s before are in "
                     f"{dump_path}, which ends:\n{f.read()[-1500:]}")
    else:
        where = f"it left no {dump_path}"
    return (f"{where}\nrun failed: timed out at the limit of {limit_s:g} s "
            f"and was ended ({ended}); no result")


def parent(args) -> int:
    if not os.path.isdir(os.path.join(ROOT, "ray_tpu")):
        print("the program (ray_tpu/) is not beside benchmarks/: nothing to "
              "measure", file=sys.stderr)
        return 2
    cell = harness.load_cell(args.workload, toy=args.toy)  # fails early
    out_dir = os.path.join(ROOT, ".bench_out", args.workload)
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    result_file = os.path.join(out_dir, "result.json")
    cmd = [sys.executable, os.path.abspath(__file__), "--child", result_file,
           "--workload", args.workload, "--seed", str(args.seed),
           "--seconds", str(args.seconds), "--trace", str(args.trace),
           "--t0-wall", repr(T0_WALL), "--out-dir", out_dir,
           "--limit-s", repr(args.limit_s),
           "--sample-to", args.sample_to, "--keep-record", args.keep_record
           ] + (["--toy"] if args.toy else [])
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        p for p in (ROOT, os.environ.get("PYTHONPATH")) if p))
    if args.toy and cell["chips"] > 1 and \
            "xla_force_host_platform_device_count" not in env.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                            f"platform_device_count={cell['chips']}").strip()
    proc = subprocess.Popen(cmd, env=env, cwd=ROOT, start_new_session=True,
                            stdout=sys.stderr)  # stdout is the result's

    def on_signal(signum, _frame):
        # the tree is in a session of its own: a signal to this parent
        # would otherwise leave it running
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        sys.exit(128 + signum)

    signal.signal(signal.SIGTERM, on_signal)
    signal.signal(signal.SIGINT, on_signal)
    timed_out = False
    try:
        proc.wait(timeout=args.limit_s)
    except subprocess.TimeoutExpired:
        timed_out = True
        os.killpg(proc.pid, signal.SIGTERM)
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            pass
    leftover = _end_tree(proc.pid, grace_s=0 if timed_out else 30)
    proc.wait()
    dump_path = os.path.join(out_dir, DUMP_FILE)
    if os.path.exists(dump_path) and not os.path.getsize(dump_path):
        os.remove(dump_path)  # armed and never fired
    if timed_out or proc.returncode != 0 or not os.path.exists(result_file):
        print(_how_it_failed(proc.returncode, timed_out, args.limit_s,
                             dump_path), file=sys.stderr)
        return 1
    result = harness.load_json(result_file)
    if leftover:
        result["correct"] = False
        result.setdefault("problems", []).append(
            f"{len(leftover)} process(es) outlived shutdown and were killed")
    print(json.dumps(result), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--toy", action="store_true",
                    help="debug widths on whatever device JAX finds; never "
                         "a result")
    ap.add_argument("--sample-to", default="",
                    help="with --trace 1: keep a small cut of the trace here")
    ap.add_argument("--keep-record", default="",
                    help="keep what the per-layer readers are given here "
                         "(pickled): `same_readings.py` reads it")
    ap.add_argument("--child", help=argparse.SUPPRESS)
    ap.add_argument("--t0-wall", type=float, help=argparse.SUPPRESS)
    ap.add_argument("--out-dir", help=argparse.SUPPRESS)
    # for the tests of the limit alone: the driver never passes it
    ap.add_argument("--limit-s", type=float, default=float(CHILD_LIMIT_S),
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.seconds is None:
        args.seconds = float(harness.load_benchmark()["run_seconds"])
    return child(args) if args.child else parent(args)


if __name__ == "__main__":
    sys.exit(main())
