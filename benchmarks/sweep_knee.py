#!/usr/bin/env python3
"""Find the knee of a serve cell once, on the chip: one deployment, one
window per offered rate, lowest rate first.

    python3 benchmarks/sweep_knee.py --workload serve-chat-steady \
        --rates 1.5,2,2.5,3,3.5,4 --seconds 30 --seed 11

The knee is the highest rate the system sustains without a growing backlog:
completed tokens per second keep up with the offered rate, and the first
token does not come later in the second half of the window than in the
first. The cell then runs at a fixed rate of about four fifths of it, written
into its traffic file with the sweep that found it. The benchmark itself
never searches for a rate. Like run.py's Serve driver, this process never
initialises a JAX backend; one JSON line per rate goes to standard output.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
os.environ["PYTHONPATH"] = os.pathsep.join(
    p for p in (ROOT, os.environ.get("PYTHONPATH")) if p)  # for the workers

from benchmarks import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--repeats", type=int, default=1,
                    help="windows per rate, each with another seed: the "
                         "spread a bound has to cover")
    ap.add_argument("--toy", action="store_true")
    args = ap.parse_args()
    cell = harness.load_cell(args.workload, toy=args.toy)
    serve_runner = harness.load_module("runners", "serve")
    run_args = {"seed": args.seed, "seconds": args.seconds, "trace": False,
                "t0_wall": time.time(),
                "out_dir": os.path.join(ROOT, ".bench_out", "sweep")}
    with serve_runner.Deployed(cell, run_args) as dep:
        windows = [(float(r), k) for r in args.rates.split(",")
                   for k in range(args.repeats)]
        for i, (rate, _k) in enumerate(windows):
            traffic = dict(cell["traffic"], arrival=dict(
                cell["traffic"]["arrival"], rate_per_s=rate))
            win = dep.measure(traffic, args.seed + i, args.seconds)
            first, second = win["ttft_mean_halves_s"]
            ttft, gaps = win["ttft_all_s"] or [0.0], win["gaps_s"] or [0.0]
            print(json.dumps({
                "rate_per_s": rate, "seed": args.seed + i,
                "requests": win["attempted"], "failed": win["failed"],
                "offered_tokens_per_s": rate * dep.n_new,
                "out_tokens_per_s": win["out_tokens_per_s"],
                "ttft_ms": {f"p{q}": harness.percentile(ttft, q) * 1e3
                            for q in (50, 75, 80, 90)},
                "ttft_mean_ms": statistics.fmean(ttft) * 1e3,
                "ttft_mean_first_half_ms": first * 1e3,
                "ttft_mean_second_half_ms": second * 1e3,
                "itl_ms": {f"p{q}": harness.percentile(gaps, q) * 1e3
                           for q in (50, 90, 95, 99)},
                "decode_steps_per_s": win["engine"]["steps"] / win["window_s"],
                "problems": win["problems"][:2]}), flush=True)
            time.sleep(3.0)  # let the last window's streams drain
    return 0


if __name__ == "__main__":
    sys.exit(main())
