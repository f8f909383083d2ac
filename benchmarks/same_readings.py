#!/usr/bin/env python3
"""Do two trees' per-layer readers read the same from ONE run's record?

    python3 benchmarks/same_readings.py --workload <cell> --parent <a checkout
        of the parent> [--seed <n>] [--seconds <s>] [--record <kept>] [--out <dir>]

A `benchmark` PR that renames or merges per-layer entries (PR 42, PR 69) owes
the proof that no reading changed, and two runs cannot give it: they differ by
their spread. This makes ONE traced run of the cell with the tree it lies in,
through ``run.py`` as the driver runs it, whose ``--keep-record`` keeps what
the readers are given (the runner's counters, the reduced trace, the device),
and evaluates BOTH trees' readers on that one record, each tree's in a
process of its own that imports that tree's ``benchmarks`` alone: the
parent's reader files, cost modules and ``BENCHMARK.json`` from ``--parent``
(``git archive <commit> | tar -x -C <dir>``), the change's from here. With
``--record`` no run is made: the readers are evaluated on a record kept
before (arithmetic alone: it needs no chip). Every entry of the parent's in
this cell must read, to the last digit (``None`` for ``None``), what the
entry that took its place reads (``renamed.json``: old -> new; a name it
lacks kept its name). Entries the change alone has in the cell are listed as
joined. Exit code 1 where a pair differs.

Neither this process nor the two evaluations initialise a JAX backend.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import pickle
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def evaluate(root: str, workload: str, record: str) -> int:
    """In a process of its own: ``root``'s readers on the kept record."""
    sys.path.insert(0, root)
    from benchmarks import harness

    assert os.path.samefile(harness.ROOT, root), (harness.ROOT, root)
    with (gzip.open if record.endswith(".gz") else open)(record, "rb") as f:
        kept = pickle.load(f)
    cell = harness.load_cell(workload)
    ctx = dict(kept, cell=cell)
    print(json.dumps({m["name"]: harness.load_reader(m["name"]).read(ctx)
                      for m in cell["per_layer"]}))
    return 0


def readings(root: str, workload: str, record: str) -> dict:
    done = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--evaluate", record,
         "--workload", workload, "--root", root], cwd=root,
        capture_output=True, text=True,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    if done.returncode:
        raise RuntimeError(f"{root}'s readers failed:\n{done.stderr[-3000:]}")
    return json.loads(done.stdout.strip().splitlines()[-1])


def traced_run(args, record: str) -> dict:
    """One traced run of the cell, as the driver makes it; its result line."""
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload",
           args.workload, "--seed", str(args.seed), "--trace", "1",
           "--keep-record", record]
    if args.seconds is not None:
        cmd += ["--seconds", str(args.seconds)]
    done = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE, text=True)
    if done.returncode:
        raise RuntimeError(f"the traced run failed ({done.returncode})")
    print(done.stdout.strip().splitlines()[-1], flush=True)
    return json.loads(done.stdout.strip().splitlines()[-1])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--parent", help="a checkout of the parent commit")
    ap.add_argument("--record", help="a record kept before: make no run")
    ap.add_argument("--out", default=os.path.join(ROOT, ".bench_out"))
    ap.add_argument("--evaluate", help=argparse.SUPPRESS)
    ap.add_argument("--root", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.evaluate:
        return evaluate(os.path.abspath(args.root), args.workload,
                        args.evaluate)

    os.makedirs(args.out, exist_ok=True)
    record, line = args.record, None
    if not record:
        record = os.path.join(os.path.abspath(args.out),
                              f"record_{args.workload}.pkl")
        line = traced_run(args, record)
    record = os.path.abspath(record)
    parent = readings(os.path.abspath(args.parent), args.workload, record)
    change = readings(ROOT, args.workload, record)
    with open(os.path.join(HERE, "renamed.json")) as f:
        new_name = {row["old"]: row["new"] for row in json.load(f)["rows"]}
    rows = [{"old": old, "new": new_name.get(old, old), "parent": value,
             "change": change.get(new_name.get(old, old), "NO ENTRY")}
            for old, value in parent.items()]
    taken = {row["new"] for row in rows}
    joined = {name: value for name, value in change.items()
              if name not in taken}
    differ = [row for row in rows if row["parent"] != row["change"]]
    if line:
        # the line's own metrics are the change's readers on the same record;
        # what a reader takes from the running process and not from the
        # record (the ``setup_*`` entries: the program's set-up record) is
        # None on both sides here, and no merged entry is of that kind
        printed = {k: v["value"] for k, v in line["metrics"].items()}
        assert all(printed[k] == v for k, v in change.items() if v is not None)
    report = {"workload": args.workload, "record": os.path.basename(record),
              "rows": rows, "joined": joined, "differ": differ,
              "correct": line and line["correct"]}
    with open(os.path.join(args.out, f"same_{args.workload}.json"), "w") as f:
        json.dump(report, f, indent=1)
    for row in rows:
        if row["old"] != row["new"] or row in differ:
            print(f"{'DIFFERS' if row in differ else 'same   '} "
                  f"{row['old']} -> {row['new']}: {row['parent']!r} | "
                  f"{row['change']!r}", file=sys.stderr)
    print(f"[same_readings] {args.workload}: {len(rows)} entries of the "
          f"parent's, {sum(r['old'] != r['new'] for r in rows)} of them "
          f"renamed, {len(differ)} differ; joined {joined}", file=sys.stderr)
    return 1 if differ else 0


if __name__ == "__main__":
    sys.exit(main())
