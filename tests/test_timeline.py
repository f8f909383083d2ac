"""Flight recorder + lifecycle timelines (PR 20).

The contract under test, per ISSUE 20:

- lifecycle analysis is honest arithmetic: per-phase wall attribution
  sums to the measured wall BY CONSTRUCTION (effective-concurrency
  normalization), and task sampling is a deterministic pure function of
  the task id so every process agrees;
- the disabled hot path costs one dict read — instrumenting every
  actor/task phase must be free when nobody asked for it — and the
  per-process ring stays bounded under an event flood;
- a failure dump round-trips: ``dump_now`` shards merge into a single
  valid Chrome-trace JSON with monotonic timestamps, counter tracks and
  a ``failures`` sidecar (both via the library and the CLI);
- chaos acceptance: a seeded mid-op rank kill leaves a merged dump that
  NAMES the dead rank and the op phase — the black box answers "who
  died, where" without a live control plane.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.observability import dump as obs_dump
from ray_tpu.observability import events as obs_events
from ray_tpu.observability import timeline
from tools import obsdump

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def _timeline_config():
    """Snapshot/restore the module config dict around a test."""
    saved = dict(timeline._config)
    yield timeline._config
    timeline._config.clear()
    timeline._config.update(saved)


def _mark(events, actor_id, phase, t):
    events.append({"type": "actor_lifecycle", "actor_id": actor_id,
                   "phase": phase, "mono": t, "ts": 1000.0 + t})


# =====================================================================
# analysis — pure-function invariants
# =====================================================================

class TestTimelineAnalysis:
    def test_build_and_transitions(self):
        evs = []
        _mark(evs, "a1", "submit", 1.0)
        _mark(evs, "a1", "registered", 1.5)
        _mark(evs, "a1", "alive", 4.0)
        _mark(evs, "a2", "submit", 2.0)
        evs.append({"type": "task_state", "actor_id": "a1", "mono": 9.0})
        tls = timeline.build_timelines(evs)
        assert set(tls) == {"a1", "a2"}
        trs = timeline.transitions(tls["a1"])
        assert [t["name"] for t in trs] == \
            ["submit->registered", "registered->alive"]
        assert trs[0]["dur"] == pytest.approx(0.5)
        assert trs[1]["dur"] == pytest.approx(2.5)

    def test_ev_time_prefers_reconciled_then_mono(self):
        assert timeline._ev_time({"gts": 5.0, "mono": 9.0, "ts": 1.0}) == 5.0
        assert timeline._ev_time({"mono": 9.0, "ts": 1.0}) == 9.0
        assert timeline._ev_time({"ts": 1.0}) == 1.0

    def test_critical_path_sums_to_wall_by_construction(self):
        # 8 entities moving through a 3-phase pipeline concurrently:
        # summed per-entity durations far exceed the wall, but the
        # attributed per-phase walls must add back up to it exactly
        evs = []
        for i in range(8):
            t0 = 0.1 * i
            _mark(evs, f"a{i}", "submit", t0)
            _mark(evs, f"a{i}", "lease_granted", t0 + 1.0)
            _mark(evs, f"a{i}", "alive", t0 + 1.3)
        wall = 4.2
        doc = timeline.critical_path(timeline.build_timelines(evs),
                                     wall_s=wall)
        assert doc["entities"] == 8
        assert doc["wall_s"] == pytest.approx(wall)
        assert doc["phase_sum_s"] == pytest.approx(wall, rel=1e-4)
        assert sum(p["share"] for p in doc["phases"].values()) == \
            pytest.approx(1.0, abs=0.01)
        # raw latencies stay per-entity: lease wait dominates
        assert doc["phases"]["submit->lease_granted"]["p50"] == \
            pytest.approx(1.0, abs=1e-6)
        assert doc["phases"]["submit->lease_granted"]["wall_s"] > \
            doc["phases"]["lease_granted->alive"]["wall_s"]

    def test_task_sampling_deterministic_and_proportional(
            self, _timeline_config):
        timeline.configure(task_sample=0.5)
        ids = [f"{i:032x}" for i in range(2000)]
        picked = [timeline.task_sampled(t) for t in ids]
        assert picked == [timeline.task_sampled(t) for t in ids]
        rate = sum(picked) / len(picked)
        assert 0.4 < rate < 0.6, rate
        timeline.configure(task_sample=1.0)
        assert all(timeline.task_sampled(t) for t in ids[:50])
        timeline.configure(task_sample=0.0)
        assert not any(timeline.task_sampled(t) for t in ids[:50])


# =====================================================================
# overhead guard — disabled path + bounded rings
# =====================================================================

class TestOverheadGuard:
    def test_disabled_marks_are_cheap(self, _timeline_config):
        """300k disabled marks in well under the (very generous) budget:
        the hot path must be one dict read, not an event build."""
        timeline.configure(enabled=False)
        n = 300_000
        t0 = time.perf_counter()
        for _ in range(n):
            timeline.mark_actor("aid", "submit")
            timeline.mark_task("tid", "run_start")
        elapsed = time.perf_counter() - t0
        assert elapsed < 3.0, f"{n} disabled marks took {elapsed:.2f}s"

    def test_ring_bounded_under_flood(self):
        buf = obs_events.EventBuffer()
        buf._flusher_started = True  # no flusher: pure bound check
        for i in range(30_000):
            buf.record({"type": "span", "i": i, "ts": float(i)})
        assert len(buf.recent()) == obs_events._RECENT_MAX
        assert len(buf._pending) <= obs_events._PENDING_MAX
        assert buf._dropped > 0
        # the ring keeps the MOST RECENT events, oldest dropped
        assert buf.recent()[-1]["i"] == 29_999

    def test_requeue_keeps_backlog_bounded(self):
        buf = obs_events.EventBuffer()
        buf._flusher_started = True
        for i in range(100):
            buf.record({"type": "span", "i": i})
        batch = buf.drain()
        buf._requeue(batch)
        assert [e["i"] for e in buf._pending[:3]] == [0, 1, 2]
        buf._requeue([{"type": "span", "i": -1}] * obs_events._PENDING_MAX)
        assert len(buf._pending) <= obs_events._PENDING_MAX

    def test_the_flusher_waits_for_work_not_for_a_period(self, monkeypatch):
        """Every process records set-up phases, so every process has a
        flusher: with an empty backlog it must run no Python. After a
        `flush()` that shipped everything the thread makes no further call
        in 2 s; the next `record()` wakes it within a period or two."""
        monkeypatch.setattr(obs_events, "_FLUSH_PERIOD_S", 0.05)
        shipped, calls = [], []
        monkeypatch.setattr(obs_events, "_local_sink",
                            lambda batch, clock: shipped.extend(batch))
        buf = obs_events.EventBuffer()
        flush_once = buf.flush_once
        monkeypatch.setattr(
            buf, "flush_once",
            lambda: calls.append(threading.current_thread().name)
            or flush_once())

        def mine():  # the sink is the module's: other buffers ship there too
            return [e["i"] for e in shipped if "i" in e]

        buf.record({"type": "span", "i": 0})
        assert buf.flush_once() and mine() == [0]
        time.sleep(0.3)  # the wake-up the record armed finds nothing
        calls.clear()
        time.sleep(2.0)
        assert calls == [], "the flusher polls an empty backlog"
        buf.record({"type": "span", "i": 1})
        deadline = time.monotonic() + 5
        while len(mine()) < 2 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert mine() == [0, 1]
        assert calls == ["obs-events-flush"]


# =====================================================================
# dump -> obsdump round trip (library + CLI)
# =====================================================================

class TestDumpRoundTrip:
    def test_dump_merges_into_valid_chrome_trace(
            self, tmp_path, monkeypatch, _timeline_config):
        monkeypatch.setenv("RAY_TPU_DEBUG_DIR", str(tmp_path))
        timeline.configure(enabled=True, task_sample=1.0)
        for i in range(3):
            aid = f"aa{i:02d}" * 8
            for phase in ("submit", "lease_granted", "init_done", "alive"):
                timeline.mark_actor(aid, phase, job_id="j1")
                time.sleep(0.002)
        obs_events.record_event(
            "collective_failure", group="g0", epoch=2, rank=1,
            dead_ranks=[3], op="allreduce", phase="encode")
        obs_dump.counter_sample("gcs_pending_actors", 5.0)
        obs_dump.counter_sample("gcs_pending_actors", 2.0)
        path = obs_dump.dump_now(
            "unit_test_failure", extra={"who": "rank3"}, force=True)
        assert path is not None and os.path.dirname(path) == str(tmp_path)

        out = tmp_path / "merged.json"
        doc = obsdump.merge_dir(str(tmp_path), out_path=str(out))
        with open(out) as f:
            assert json.load(f)["displayTimeUnit"] == "ms"

        evs = doc["traceEvents"]
        assert evs, "empty trace"
        for ev in evs:
            assert ev["ph"] in ("X", "C", "i", "M"), ev
            assert "pid" in ev and "ts" in ev and "name" in ev
        # metadata first, then non-decreasing timestamps
        body = [e for e in evs if e["ph"] != "M"]
        assert evs[:len(evs) - len(body)] == \
            [e for e in evs if e["ph"] == "M"]
        ts = [float(e["ts"]) for e in body]
        assert ts == sorted(ts), "trace timestamps not monotonic"
        # counter track + per-entity lifecycle slices made it across
        counters = [e for e in evs if e["ph"] == "C"]
        assert any(e["name"] == "gcs_pending_actors" for e in counters)
        lanes = [e for e in evs
                 if e.get("pid") == "lifecycle" and "->" in e["name"]]
        assert any(e["name"] == "submit->lease_granted" for e in lanes)
        # both failure channels: the shard's own reason + the ring event
        reasons = {f["reason"] for f in doc["failures"]}
        assert "unit_test_failure" in reasons
        col = [f for f in doc["failures"]
               if f["reason"] == "collective_rank_failure"]
        assert col and col[0]["dead_ranks"] == [3]
        assert col[0]["op"] == "allreduce" and col[0]["phase"] == "encode"
        assert doc["processes"], "no process sidecar"

    def test_cli_smoke(self, tmp_path):
        """`make obs-dump DIR=...` body: the module CLI merges a shard
        directory into <dir>/merged_trace.json and reports failures."""
        shard = {
            "version": 1, "reason": "collective_rank_failure",
            "ts": 100.0, "mono": 5.0, "process": "w1", "pid": 41,
            "events": [
                {"type": "span", "name": "collective.allreduce",
                 "kind": "collective", "ts": 99.0, "dur": 0.5,
                 "span_id": "s1", "trace_id": "t1"},
                {"type": "collective_failure", "ts": 100.0, "group": "g",
                 "epoch": 1, "rank": 1, "dead_ranks": [3],
                 "op": "allreduce", "phase": "encode", "worker": "w1"},
            ],
            "active_spans": [], "metrics": [],
            "loop_lag": [{"ts": 99.5, "server": "gcs", "method": "Poll",
                          "held_ms": 12.0, "wall_ms": 15.0}],
            "counters": {"serve_shed_total": [[99.0, 0.0], [100.0, 4.0]]},
            "extra": {"dead_ranks": [3], "op": "allreduce"},
        }
        with open(tmp_path / "w1-41-1.json", "w") as f:
            json.dump(shard, f)
        proc = subprocess.run(
            [sys.executable, "-m", "tools.obsdump", str(tmp_path)],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0, proc.stderr
        with open(tmp_path / "merged_trace.json") as f:
            doc = json.load(f)
        assert any(e["ph"] == "C" and e["name"] == "event_loop_held_ms"
                   for e in doc["traceEvents"])
        assert any(f.get("dead_ranks") == [3] for f in doc["failures"])

    def test_empty_dir_exits_nonzero(self, tmp_path):
        proc = subprocess.run(
            [sys.executable, "-m", "tools.obsdump", str(tmp_path)],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=60)
        assert proc.returncode == 1


# =====================================================================
# chaos acceptance — seeded rank kill leaves an attributed black box
# =====================================================================

@ray_tpu.remote(num_cpus=0, max_restarts=0)
class _Member:
    def __init__(self, rank, world, gname, env=None):
        for k, v in (env or {}).items():
            os.environ[k] = v
        from ray_tpu.util import collective as col
        self._col = col
        self.gname = gname
        col.init_collective_group(world, rank, backend="objstore",
                                  group_name=gname)

    def allreduce(self, arr):
        return self._col.allreduce(arr, group_name=self.gname)

    def destroy(self):
        self._col.destroy_collective_group(self.gname)
        return True


class TestChaosDumpAttribution:
    def test_seeded_rank_kill_writes_attributed_dump(
            self, tmp_path, monkeypatch):
        """Kill rank 3 mid-allreduce (seeded, at the encode phase): the
        survivors' typed failure must leave dump shards behind whose
        merged ``failures`` list names the missing rank and the op
        phase — postmortem attribution with zero live processes needed.
        Confirmed death (CollectiveRankFailure / dead_ranks) and
        deadline exhaustion (CollectiveTimeoutError / suspect_ranks)
        are BOTH acceptable attributions: which one a survivor gets
        depends on whether the liveness probe wins its race with the op
        deadline, and the flight recorder must name rank 3 either
        way."""
        from ray_tpu.util.collective import CollectiveError

        monkeypatch.setenv("RAY_TPU_DEBUG_DIR", str(tmp_path))
        ray_tpu.shutdown()
        ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
        ws = []
        try:
            gname = "chaos_dump"
            hosts = ["hostA", "hostA", "hostB", "hostB"]
            for r in range(4):
                env = {"RAY_TPU_COLLECTIVE_TOPOLOGY_KEY": hosts[r],
                       "RAY_TPU_COLLECTIVE_OP_TIMEOUT_S": "8"}
                if r == 3:
                    env["RAY_TPU_COLLECTIVE_CHAOS_DIE"] = "allreduce:encode"
                ws.append(_Member.remote(r, 4, gname, env))
            parts = [np.full((320, 320), float(r + 1), np.float32)
                     for r in range(4)]
            futs = [w.allreduce.remote(p) for w, p in zip(ws, parts)]
            outcomes = []
            for f in futs:
                try:
                    outcomes.append(("ok", ray_tpu.get(f, timeout=30)))
                except Exception as e:  # noqa: BLE001
                    outcomes.append(("err", e))
            assert outcomes[3][0] == "err", "chaos rank did not die"
            errs = [e for kind, e in outcomes[:3] if kind == "err"]
            for e in errs:
                assert isinstance(e, CollectiveError), repr(e)
            assert errs, f"no survivor failed typed: {outcomes!r}"

            # survivors dumped synchronously before raising; the GCS
            # fan-out may still be landing — poll the merged doc until
            # the attribution shows up
            rec = None
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline and rec is None:
                doc = obsdump.merge_dir(str(tmp_path))
                for f in doc["failures"]:
                    missing = list(f.get("dead_ranks") or []) + \
                        list(f.get("suspect_ranks") or [])
                    if 3 in missing:
                        rec = f
                        break
                if rec is None:
                    time.sleep(0.5)
            assert rec is not None, \
                f"merged dump never named rank 3: {doc['failures']!r}"
            assert rec.get("op"), rec
            assert rec.get("phase"), rec
            assert doc["processes"], "no shard-writing process recorded"
        finally:
            for w in ws[:3]:
                try:
                    ray_tpu.kill(w)
                except Exception:  # noqa: BLE001
                    pass
            ray_tpu.shutdown()
