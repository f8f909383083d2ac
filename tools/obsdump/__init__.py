"""obsdump — merge flight-recorder shards into one Chrome/Perfetto trace.

Every process of a failing run writes a JSON *shard* (its event ring,
active spans, metrics snapshot, loop-lag samples, counter series) into
one debug directory (``ray_tpu/observability/dump.py``). This tool
merges those shards into a single ``chrome://tracing`` /
https://ui.perfetto.dev file:

- **span** events → complete slices ("ph": "X"), grouped by process;
- **actor/task lifecycle** marks → per-entity phase slices on a
  ``lifecycle`` track (submit→registered→…→first_ping laid end to end);
- **setup_phase** intervals (always on: the cluster's start, a worker's
  boot, an actor's ``__init__``, the engine, each jitted program's first
  call) → slices on a ``setup`` track beside ``lifecycle``, one tid a
  process;
- **collective_op** events → stacked op + per-phase slices;
- **counter series** (GCS queue depth, serve shed rate) and **event-loop
  lag** samples → counter tracks ("ph": "C");
- **failure attribution** — every ``collective_failure`` event and every
  failure-reason shard extra is collected into a top-level ``failures``
  list, so "which rank died, in which op phase" is one ``jq`` away.

Merging happens on wall-clock ``ts``: shards are written by processes of
one host (or NTP-bounded hosts), and a single consistent timebase
beats per-process monotonic clocks that don't share an epoch. The
GCS-reconciled ``gts`` is for live timeline analysis; dumps are the
postmortem path and may exist when the GCS never saw the events.

CLI::

    python -m tools.obsdump /tmp/ray_tpu_debug/gcs-<addr> -o trace.json
    make obs-dump DIR=/tmp/ray_tpu_debug/gcs-<addr>
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Tuple

_US = 1e6  # chrome trace timestamps are microseconds


def load_shards(directory: str) -> List[dict]:
    """All parseable ``*.json`` shards in a debug directory, oldest
    first. Unparseable files (a process died mid-write before the
    atomic rename — shouldn't happen — or stray files) are skipped."""
    shards: List[dict] = []
    for name in sorted(os.listdir(directory)):
        if not name.endswith(".json"):
            continue
        path = os.path.join(directory, name)
        try:
            with open(path) as f:
                shard = json.load(f)
        except (OSError, ValueError):
            continue
        if isinstance(shard, dict) and "events" in shard:
            shard["_file"] = name
            shards.append(shard)
    return shards


def _span_slice(ev: dict, pid: str) -> dict:
    return {
        "name": ev.get("name", "?"),
        "cat": ev.get("kind", "span"),
        "ph": "X",
        "ts": float(ev.get("ts", 0.0)) * _US,
        "dur": max(0.0, float(ev.get("dur", 0.0)) * _US),
        "pid": pid,
        "tid": ev.get("kind", "span"),
        "args": {
            "span_id": ev.get("span_id"),
            "parent_span_id": ev.get("parent_span_id", ""),
            "trace_id": ev.get("trace_id"),
            "status": ev.get("status", "ok"),
            **(ev.get("attrs") or {}),
        },
    }


def _lifecycle_slices(marks: List[dict], entity: str) -> List[dict]:
    """Consecutive phase marks of one entity → end-to-end slices on a
    shared ``lifecycle`` pid (one tid per entity), so the per-phase
    breakdown reads directly off the track."""
    marks = sorted(marks, key=lambda m: float(m.get("ts", 0.0)))
    out: List[dict] = []
    for a, b in zip(marks, marks[1:]):
        t0, t1 = float(a.get("ts", 0.0)), float(b.get("ts", 0.0))
        out.append({
            "name": "%s->%s" % (a.get("phase", "?"), b.get("phase", "?")),
            "cat": a.get("type", "lifecycle"),
            "ph": "X",
            "ts": t0 * _US,
            "dur": max(0.0, (t1 - t0)) * _US,
            "pid": "lifecycle",
            "tid": entity[:16],
            "args": {"from": a.get("phase"), "to": b.get("phase"),
                     "job_id": a.get("job_id", "")},
        })
    return out


def _setup_slice(ev: dict) -> dict:
    """A ``setup_phase`` interval (``ts`` is its start) → one slice on the
    shared ``setup`` pid, one tid a process: children nest inside their
    parent's slice, processes lie one under another."""
    return {
        "name": ev.get("name", "?"),
        "cat": "setup_phase",
        "ph": "X",
        "ts": float(ev.get("ts", 0.0)) * _US,
        "dur": max(0.0, float(ev.get("dur", 0.0))) * _US,
        "pid": "setup",
        "tid": str(ev.get("worker", "?"))[:16],
        "args": dict(ev.get("attrs") or {}),
    }


def _collective_slices(ev: dict, pid: str) -> List[dict]:
    """A collective_op ring event carries (dur_s, phases{name: s}); lay
    the op slice back from its record time and stack the phases inside
    it (order of the phases dict = execution order on CPython)."""
    dur = float(ev.get("dur_s", 0.0))
    end = float(ev.get("ts", 0.0))
    start = end - dur
    tid = "collective:r%s" % ev.get("rank", "?")
    out = [{
        "name": ev.get("op", "?"),
        "cat": "collective",
        "ph": "X",
        "ts": start * _US,
        "dur": dur * _US,
        "pid": pid,
        "tid": tid,
        "args": {k: ev.get(k) for k in
                 ("op", "nbytes", "world_size", "rank", "algo", "codec",
                  "mb_per_s")},
    }]
    t = start
    for phase, pdur in (ev.get("phases") or {}).items():
        pdur = float(pdur)
        out.append({
            "name": "%s.%s" % (ev.get("op", "?"), phase),
            "cat": "collective.phase",
            "ph": "X",
            "ts": t * _US,
            "dur": pdur * _US,
            "pid": pid,
            "tid": tid,
            "args": {"phase": phase},
        })
        t += pdur
    return out


def _counter_events(series: Dict[str, List[List[float]]],
                    pid: str) -> List[dict]:
    out: List[dict] = []
    for name, samples in (series or {}).items():
        for sample in samples:
            try:
                ts, val = float(sample[0]), float(sample[1])
            except (TypeError, ValueError, IndexError):
                continue
            out.append({"name": name, "ph": "C", "ts": ts * _US,
                        "pid": pid, "tid": name,
                        "args": {"value": val}})
    return out


def _loop_lag_events(samples: List[dict], pid: str) -> List[dict]:
    out: List[dict] = []
    for s in samples or []:
        try:
            ts = float(s["ts"])
            held = float(s.get("held_ms", 0.0))
        except (KeyError, TypeError, ValueError):
            continue
        out.append({"name": "event_loop_held_ms", "ph": "C",
                    "ts": ts * _US, "pid": pid,
                    "tid": "event_loop_held_ms",
                    "args": {"value": held,
                             "server": s.get("server", ""),
                             "method": s.get("method", "")}})
    return out


def _failure_records(shard: dict) -> List[dict]:
    """Failure attributions from one shard: its own dump reason (when it
    names a failure) and every collective_failure event on its ring."""
    out: List[dict] = []
    reason = shard.get("reason", "")
    extra = shard.get("extra") or {}
    if reason and reason not in ("signal", "requested") \
            and not reason.startswith("atexit"):
        out.append(dict(extra, reason=reason,
                        source=shard.get("process", "?"),
                        ts=shard.get("ts", 0.0)))
    for ev in shard.get("events", ()):
        if ev.get("type") == "collective_failure":
            rec = {
                "reason": "collective_rank_failure"
                if ev.get("dead_ranks") else "collective_op_timeout",
                "source": ev.get("worker", "?"),
                "ts": ev.get("ts", 0.0),
                "group": ev.get("group"),
                "epoch": ev.get("epoch"),
                "rank": ev.get("rank"),
                "op": ev.get("op"),
                "phase": ev.get("phase"),
            }
            for k in ("dead_ranks", "suspect_ranks", "confirmed"):
                if k in ev:
                    rec[k] = ev[k]
            out.append(rec)
    return out


def _dedup_key(obj: Any) -> str:
    return json.dumps(obj, sort_keys=True, default=repr)


def merge(shards: List[dict]) -> Dict[str, Any]:
    """Merge shards into one Chrome-trace document (plus ``failures``
    and ``processes`` sidecars). Multiple shards from one process (the
    ring survives across dumps) dedup by event content."""
    trace_events: List[dict] = []
    failures: List[dict] = []
    processes: Dict[str, dict] = {}
    lifecycle: Dict[Tuple[str, str], List[dict]] = {}
    seen: set = set()

    for shard in shards:
        pid = str(shard.get("process") or shard.get("pid") or "?")
        proc = processes.setdefault(pid, {
            "process": pid, "pid": shard.get("pid"),
            "reasons": [], "shards": 0})
        proc["shards"] += 1
        if shard.get("reason") not in proc["reasons"]:
            proc["reasons"].append(shard.get("reason"))

        for ev in shard.get("events", ()):
            key = _dedup_key(ev)
            if key in seen:
                continue
            seen.add(key)
            etype = ev.get("type")
            if etype == "span":
                trace_events.append(_span_slice(ev, pid))
            elif etype in ("actor_lifecycle", "task_lifecycle"):
                eid = ev.get("actor_id") or ev.get("task_id") or "?"
                lifecycle.setdefault((etype, eid), []).append(ev)
            elif etype == "setup_phase":
                trace_events.append(_setup_slice(ev))
            elif etype == "collective_op":
                trace_events.extend(_collective_slices(ev, pid))
            else:
                # instants keep the long tail visible without a schema
                # per type (actor_restart, debug_dump, drain, ...)
                trace_events.append({
                    "name": etype or "?", "cat": "event", "ph": "i",
                    "ts": float(ev.get("ts", 0.0)) * _US,
                    "pid": pid, "tid": "events", "s": "p",
                    "args": {k: v for k, v in ev.items()
                             if k not in ("type", "ts")},
                })
        # open spans at dump time: zero-duration instants flagged so a
        # postmortem sees what the process was INSIDE when it dumped
        for sp in shard.get("active_spans", ()):
            key = _dedup_key(("active", sp.get("span_id")))
            if key in seen:
                continue
            seen.add(key)
            trace_events.append({
                "name": sp.get("name", "?"), "cat": "span.open",
                "ph": "i", "ts": float(sp.get("ts", 0.0)) * _US,
                "pid": pid, "tid": "open_at_dump", "s": "t",
                "args": {"span_id": sp.get("span_id"),
                         "trace_id": sp.get("trace_id")},
            })
        counter_evs = _counter_events(shard.get("counters"), pid) \
            + _loop_lag_events(shard.get("loop_lag"), pid)
        for cev in counter_evs:
            key = _dedup_key(cev)
            if key in seen:
                continue
            seen.add(key)
            trace_events.append(cev)
        for rec in _failure_records(shard):
            key = _dedup_key(rec)
            if key in seen:
                continue
            seen.add(key)
            failures.append(rec)

    for (_etype, eid), marks in lifecycle.items():
        trace_events.extend(_lifecycle_slices(marks, eid))

    for pid in processes:
        trace_events.append({"name": "process_name", "ph": "M",
                             "pid": pid, "tid": "", "ts": 0,
                             "args": {"name": pid}})
    trace_events.sort(key=lambda e: (e.get("ph") == "M" and -1 or 0,
                                     float(e.get("ts", 0))))
    failures.sort(key=lambda f: float(f.get("ts", 0.0)))
    return {
        "traceEvents": trace_events,
        "displayTimeUnit": "ms",
        "failures": failures,
        "processes": sorted(processes.values(),
                            key=lambda p: p["process"]),
    }


def merge_dir(directory: str,
              out_path: Optional[str] = None) -> Dict[str, Any]:
    """load_shards + merge; optionally write the merged doc."""
    doc = merge(load_shards(directory))
    if out_path:
        with open(out_path, "w") as f:
            json.dump(doc, f)
    return doc
