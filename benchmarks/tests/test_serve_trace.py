"""Where a serve cell's trace is stopped and where it is reduced: the replica
stops the profiler and reads nothing, ``measure`` waits for the stop however
late it returns, and a traced run without a trace fails (PR 31 was refused
for a line without ``busy_s``: the reduction ran inside the replica and was
dropped after 60 s)."""

import ast
import sys
import threading
import time
import types

import pytest

from benchmarks import program_spans, replica, trace_reduce
from benchmarks.runners import serve

MS = 1_000_000
PUMP = 7


def test_the_replica_stops_the_profiler_and_reads_nothing(monkeypatch, tmp_path):
    with open(replica.__file__) as f:
        tree = ast.parse(f.read())
    imported = {a.name for n in ast.walk(tree)
                if isinstance(n, (ast.Import, ast.ImportFrom)) for a in n.names}
    assert "trace_reduce" not in imported
    calls = []
    profiler = types.SimpleNamespace(
        ProfileOptions=lambda: types.SimpleNamespace(),
        start_trace=lambda d, profiler_options: calls.append(("start", d)),
        stop_trace=lambda: calls.append(("stop",)))
    monkeypatch.setitem(sys.modules, "jax",
                        types.SimpleNamespace(profiler=profiler))
    trace_dir = str(tmp_path / "trace")
    replica.profile_start(trace_dir)
    stopped = replica.profile_stop(trace_dir)
    assert calls == [("start", trace_dir), ("stop",)]
    assert stopped["trace_dir"] == trace_dir and stopped["stop_s"] >= 0


class _Call:
    """``handle.<method>.remote(*args).result()`` of a fake replica."""

    def __init__(self, fn):
        self.fn = fn

    def remote(self, *args):
        return types.SimpleNamespace(result=lambda: self.fn(*args))


def _deployed(monkeypatch, start, stop):
    """A ``Deployed`` that was never started, over a fake replica, a fake
    proxy and a load generator whose window closes at once."""
    import ray_tpu.serve

    stats = {k: 0 for k in ("admitted", "finished", "failed", "steps",
                            "tokens_out")}
    dep = object.__new__(serve.Deployed)
    dep.handle = types.SimpleNamespace(
        engine_stats=_Call(lambda: stats),
        bench_profile_start=_Call(start), bench_profile_stop=_Call(stop))
    dep.args = {"out_dir": "/nowhere"}
    dep.cfg = types.SimpleNamespace(vocab_size=100)
    dep.port = 0

    def play(port, traffic, schedule, seconds, on_open=None):
        threading.Thread(target=on_open, daemon=True).start()
        return {"records": []}

    monkeypatch.setattr(ray_tpu.serve, "http_proxy_stats", dict)
    monkeypatch.setattr(serve.loadgen, "make_schedule", lambda *a: {})
    monkeypatch.setattr(serve.loadgen, "play", play)
    monkeypatch.setattr(serve, "account", lambda *a: a[-1])  # the marks
    # the old wait (600 x 0.1 s after the window) is over in no time: a
    # stop that is slower than it was dropped
    monkeypatch.setattr(time, "sleep", lambda s: None)
    return dep


TRAFFIC = {"trace_after_s": 0.0, "trace_s": 0.0}


def test_a_stop_that_returns_long_after_the_window_still_delivers(monkeypatch):
    def stop(trace_dir):
        threading.Event().wait(0.5)  # the window closed 0.5 s ago
        return {"trace_dir": trace_dir, "stop_s": 0.5}

    dep = _deployed(monkeypatch, start=lambda d: True, stop=stop)
    marks = dep.measure(TRAFFIC, seed=1, seconds=0.0, trace=True)
    assert marks["trace"] == {"trace_dir": "/nowhere/trace", "stop_s": 0.5}
    assert "engine_open" in marks and "engine_close" in marks


def test_an_untraced_window_never_touches_the_profiler(monkeypatch):
    def never(*_a):
        raise AssertionError("the profiler was called")

    dep = _deployed(monkeypatch, start=never, stop=never)
    marks = dep.measure(TRAFFIC, seed=1, seconds=0.0)
    assert "trace" not in marks and "engine_open" in marks


def test_a_profiler_that_does_not_start_fails_the_run(monkeypatch):
    def start(_trace_dir):
        raise OSError("profiler start refused")

    dep = _deployed(monkeypatch, start=start, stop=lambda d: {})
    with pytest.raises(RuntimeError, match="profiler start refused") as err:
        dep.measure(TRAFFIC, seed=1, seconds=0.0, trace=True)
    assert isinstance(err.value.__cause__, OSError)


def _loaded(ops):
    """A trace as ``trace_reduce.load_xplane`` gives it: the pump's steps
    of 10 ms with the device busy in the first 6 ms of each."""
    spans = []
    for i in range(3):
        t = i * 10 * MS
        spans += [[program_spans.STEP, t, 10 * MS, PUMP, {"step": i}],
                  [program_spans.DECODE_DISPATCH, t, 1 * MS, PUMP, {}],
                  [program_spans.SAMPLE_SYNC, t + 1 * MS, 7 * MS, PUMP, {}]]
    dev = {"ops": ops, "programs": [], "async": [], "other_lines": {}}
    return {"devices": {"/device:TPU:0": dev}, "spans": [],
            "program_spans": spans}


def _run_over(monkeypatch, loaded, traced=True):
    """``serve.run`` over a fake deployment whose traced window left
    ``loaded`` as its file; what happened, in order, is in ``events``."""
    events = []

    class FakeDeployed:
        stats_warm = dict.fromkeys(
            ("compile_s", "cache_hits", "cache_misses", "admitted",
             "finished", "failed", "steps", "tokens_out"), 0)
        problems, replica_start_s, warmup_s = [], 0.0, 0.0
        sv, n_new, check, traffic = {"cache_slots": 1}, 1, {}, {}

        def __init__(self, cell, args):
            stats = dict(self.stats_warm, max_active=1)
            self.handle = types.SimpleNamespace(
                engine_stats=_Call(lambda: stats),
                bench_device=_Call(lambda: {
                    "platform": "cpu", "kind": "cpu", "count": 1,
                    "memory_peak_bytes": 0}))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            events.append("cluster down")

        def measure(self, traffic, seed, seconds, trace=False):
            return {"trace": {"trace_dir": "/nowhere/trace", "stop_s": 0.25}
                    if trace else {}, "problems": [], "gaps_s": [],
                    "failed": 0, "attempted": 1, "open_wall": 1.0}

    def load_xplane(path):
        events.append("file read")
        return loaded

    monkeypatch.setattr(serve, "Deployed", FakeDeployed)
    monkeypatch.setattr(serve.trace_reduce, "find_xplane", lambda d: d)
    monkeypatch.setattr(serve.trace_reduce, "load_xplane", load_xplane)
    rec = serve.run({"end_to_end": []}, {
        "seed": 0, "seconds": 0.0, "trace": traced, "t0_wall": 0.0})
    return rec, events


def test_the_file_is_read_once_after_the_cluster_is_down(monkeypatch):
    ops = [[f"fusion.{i}", i * 10 * MS, 6 * MS, ""] for i in range(3)]
    rec, events = _run_over(monkeypatch, _loaded(ops))
    assert events == ["cluster down", "file read"]
    trace = rec["trace"]
    assert trace["busy_s"] == pytest.approx(0.018)
    assert trace["window_s"] == pytest.approx(0.026)
    # the readers find the program's spans in the reduced trace
    parsed = program_spans.load({"trace": trace})
    assert program_spans.step_period_ms(parsed) == pytest.approx(10.0)
    # idle time by the engine span the pump was in, largest first: each gap
    # is 4 ms, 2 of them under sample_sync, 2 under the bare step
    assert [g[0] for g in trace["idle_gaps"]] == ["sample_sync", "step"]
    assert trace["idle_gaps"][0][1] == pytest.approx(0.004)
    assert sum(g[1] for g in trace["idle_gaps"]) == pytest.approx(0.008)


def test_a_trace_without_a_device_operation_fails_the_run(monkeypatch):
    with pytest.raises(RuntimeError, match="no device operation"):
        _run_over(monkeypatch, _loaded(ops=[]))


def test_an_untraced_run_reads_no_file(monkeypatch):
    rec, events = _run_over(monkeypatch, None, traced=False)
    assert events == ["cluster down"] and rec["trace"] == {}


def test_one_load_serves_both_reductions():
    """``program_spans.from_trace`` takes its busy intervals from the same
    operations ``trace_reduce.summarize`` adds up."""
    ops = [["while.1", 0, 26 * MS, ""]] + \
        [[f"fusion.{i}", i * 10 * MS, 6 * MS, ""] for i in range(3)]
    loaded = _loaded(ops)
    summary = trace_reduce.reduce_trace(loaded)
    parsed = program_spans.from_trace(loaded)
    busy = parsed["busy"]["/device:TPU:0"]
    assert trace_reduce.length(busy) / 1e9 == pytest.approx(summary["busy_s"])
    assert len(busy) == 3  # the while holds the others: it is not work
