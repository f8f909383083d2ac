#!/usr/bin/env python3
"""The stream path's counters over one window of a serve cell with the
profiler OFF, on the chip: what the pump's own Python costs a step, how long
it is ready and not running, how long it has no work, and how busy the
replica's process and the process that holds the front door are.

    python3 benchmarks/stream_counters.py --workload serve-cca-reason-long-out \
        --seed 11 [--seconds 51]

The cell's own runner deploys, warms and plays the cell's traffic as
``run.py`` would (``Deployed.measure``); the runner's ``account`` reduces the
window's two snapshots of ``engine_stats`` and ``http_proxy_stats`` through
``stream_spans.window_counters`` (``counters["stream_path"]``, PR 42). A traced
run reads the pump's two from its ``engine.step`` spans instead
(``layer_metrics/pump_*_ms_per_step.py``) and the front door's own work an
item from the same key (``layer_metrics/proxy_forward_ms_per_item.py``). The
two processes' CPU shares are read HERE ALONE and have no entry: a traced
window holds the profiler's start, collection and stop in the replica's
process between the two snapshots, and read 227.5 % of a core where this tool
read 127.1 (PERF.md, section 6, PR 42). NOTE what the front door's process is in
this benchmark: the load generator's clients run in it too. Like run.py's
Serve driver this process never initialises a JAX backend; one JSON line
goes to standard output.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
os.environ["PYTHONPATH"] = os.pathsep.join(
    p for p in (ROOT, os.environ.get("PYTHONPATH")) if p)  # for the workers

from benchmarks import harness, stream_spans  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--toy", action="store_true")
    args = ap.parse_args()
    if args.seconds is None:
        args.seconds = float(harness.load_benchmark()["run_seconds"])
    cell = harness.load_cell(args.workload, toy=args.toy)
    runner = harness.load_module("runners", cell["config"]["runner"])
    # the copy of runners/serve.py that this cell's runner runs (its own,
    # rebound, or serve.py itself)
    serve = getattr(runner, "serve", runner)
    run_args = {"seed": args.seed, "seconds": args.seconds, "trace": False,
                "t0_wall": time.time(),
                "out_dir": os.path.join(ROOT, ".bench_out", "stream_counters")}
    with serve.Deployed(cell, run_args) as dep:
        win = dep.measure(dep.traffic, args.seed, args.seconds)
    c = win["stream_path"]
    gaps = win["gaps_s"] or [0.0]
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "window_s": win["window_s"], "failed": win["failed"],
        "problems": (dep.problems + win["problems"])[:2],
        "out_tokens_per_s": win["out_tokens_per_s"],
        "itl_p50_ms": harness.percentile(gaps, 50) * 1e3,
        "itl_p95_ms": harness.percentile(gaps, 95) * 1e3,
        "steps": win["engine"]["steps"], "counters": c,
        "pump_cpu_ms_per_step": stream_spans.pump_cpu_ms_per_step(c),
        "pump_wait_ms_per_step": stream_spans.pump_wait_ms_per_step(c),
        "pump_sync_ms_per_step":
            1e3 * c["pump_sync_s"] / c["steps"] if c and c["steps"] else None,
        # the pump in no pass: nothing active and nothing waiting. In a
        # closed loop with as many clients as slots, the clients have not
        # asked again yet: they are still reading what their streams queued
        "pump_idle_share":
            100.0 * (1.0 - c["pump_step_s"] / c["window_s"]) if c else None,
        "replica_cpu_share": stream_spans.cpu_share(
            c["replica_cpu_s"], c["window_s"]) if c else None,
        "frontdoor_cpu_share": stream_spans.cpu_share(
            c["frontdoor_cpu_s"], c["window_s"]) if c else None,
        "proxy_forward_ms_per_item":
            stream_spans.proxy_forward_ms_per_item(c)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
