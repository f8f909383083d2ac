"""Where ``setup_s`` goes: pure functions over the program's set-up record.

The program books the phases of every process's bring-up as ``setup_phase``
events on its bus, always on (``ray_tpu/observability/schema.py``
``SETUP_PHASES``: the cluster's start, a worker's boot, an actor's
``__init__``, the backend, parameters, engine, each jitted program's first
call, the train step's ladder), and a driver keeps them past
``ray_tpu.shutdown()``: ``run.py::assemble`` calls the readers after the
cluster is gone, and ``record()`` still finds them. A record is
``[{name, worker, ts, mono, gts, dur, attrs}]`` sorted by start: ``ts`` and
``mono`` are an interval's START on ``time.time()`` and ``time.monotonic()``
(one host: every process shares both clocks).

A program without the record (a parent commit) gives ``[]`` and every reader
``None``: a reader never invents a zero. Imports the program's
``observability`` and no JAX.
"""

from __future__ import annotations

from typing import List, Optional

P = "ray_tpu.setup."
# the phases that hold a jitted program's first call or ahead-of-time compile
PROGRAM_PHASES = (P + "program", P + "step.rung")


def record() -> List[dict]:
    """The program's set-up record in this process, ``[]`` where the
    program keeps none."""
    from ray_tpu import observability

    keep = getattr(observability, "setup_record", None)
    return keep() if keep is not None else []


def phase_s(rec: List[dict], name: str,
            worker: Optional[str] = None) -> Optional[float]:
    """Seconds of the phase ``P + name`` (of ``worker`` alone, if given),
    summed over its intervals; None where the record has none."""
    durs = [e["dur"] for e in rec if e["name"] == P + name
            and (worker is None or e["worker"] == worker)]
    return sum(durs) if durs else None


def chip_worker(rec: List[dict]) -> Optional[str]:
    """The process that holds the chip: the ``worker`` that booked
    ``setup.engine.build`` (a replica) or ``setup.step.build`` (a train
    loop)."""
    for e in rec:
        if e["name"] in (P + "engine.build", P + "step.build"):
            return e["worker"]
    return None


def programs(rec: List[dict], worker: Optional[str] = None) -> List[dict]:
    """The first calls and ahead-of-time compiles of ``worker``'s jitted
    programs, as their attrs with ``phase`` and ``dur`` added, in order."""
    return [dict(e["attrs"], phase=e["name"][len(P):], dur=e["dur"])
            for e in rec if e["name"] in PROGRAM_PHASES
            and (worker is None or e["worker"] == worker)]


def union_s(rec: List[dict], from_wall: Optional[float] = None,
            until_wall: Optional[float] = None) -> float:
    """Seconds that at least one phase of any process covers: intervals on
    ``mono``, overlaps counted once, each cut to ``[from_wall, until_wall]``
    (given on ``time.time()``; an interval's own ``ts`` carries the bound
    over to its ``mono``). A pooled worker's boot predates the lease that
    took it and is left out."""
    spans = []
    for e in rec:
        if e["name"] == P + "worker.boot" and e["attrs"].get("pooled"):
            continue
        start, end = e["mono"], e["mono"] + e["dur"]
        if from_wall is not None:
            start = max(start, e["mono"] + (from_wall - e["ts"]))
        if until_wall is not None:
            end = min(end, e["mono"] + (until_wall - e["ts"]))
        if end > start:
            spans.append((start, end))
    total, covered = 0.0, float("-inf")
    for start, end in sorted(spans):
        if end > covered:
            total += end - max(start, covered)
            covered = end
    return total


def by_seconds(rec: List[dict]) -> List[tuple]:
    """``(phase, worker, seconds, n, first start on mono)`` of every phase a
    process booked, the longest first: the table a slow start is read
    from."""
    rows = {}
    for e in rec:
        key = (e["name"][len(P):], e["worker"])
        s, n, at = rows.get(key, (0.0, 0, e["mono"]))
        rows[key] = (s + e["dur"], n + 1, min(at, e["mono"]))
    return sorted(((name, worker, s, n, at) for (name, worker), (s, n, at)
                   in rows.items()), key=lambda r: -r[2])
