"""Set-up has a record of its own (ISSUE 56): every process books the phases
of its bring-up as ``setup_phase`` events on the bus, always on, and a driver
keeps them past ``shutdown``.

What is pinned here: the event's schema and its closed vocabulary; nesting
and attrs; the first-call wrapper (ONE ``setup.program``, then the bare
jitted callable); the engine's and the train step's sites at toy widths; a
toy cluster in a process of its own (the worker's phases reach the state
API with a ``gts`` and outlive ``shutdown``; a dead GCS costs ``shutdown``
its limit and no more); ``tools/obsdump``'s ``setup`` track.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import numpy as np
import pytest

from ray_tpu import observability as obs
from ray_tpu.observability import events as obs_events
from ray_tpu.observability import schema, timeline
from ray_tpu.parallel import bootstrap
from tools import obsdump

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLUSTER_LIMIT_S = 60  # a cluster case's own limit
# a variable, not a literal at the call: raycheck RC009 finds an undeclared
# LITERAL before the site ever runs (tests/test_raycheck.py)
UNDECLARED = "ray_tpu.setup.nonesuch"


def _phases(since: int, name: str = "") -> list:
    """This process's set-up events recorded after the first ``since``."""
    evs = obs_events.local_events("setup_phase")[since:]
    return [e for e in evs if not name or e["name"] == name]


@pytest.fixture
def since():
    return len(obs_events.local_events("setup_phase"))


# =====================================================================
# the event, its one producer, the closed vocabulary
# =====================================================================

class TestTheEvent:
    def test_schema_declares_the_event_and_every_phase_by_its_name(self):
        assert schema.EVENT_TYPES["setup_phase"] == "name, mono, dur, attrs"
        assert obs.SETUP_PHASES is schema.SETUP_PHASES
        assert all(n.startswith("ray_tpu.setup.") and what
                   for n, what in schema.SETUP_PHASES.items())
        # every site ISSUE 56 names
        assert {n[len("ray_tpu.setup."):] for n in schema.SETUP_PHASES} == {
            "init", "init.gcs", "init.raylet", "init.connect", "serve.run",
            "serve.controller", "serve.deploy", "serve.proxy", "worker.boot",
            "actor.init", "engine.build", "engine.backend", "engine.params",
            "engine.cache", "program", "step.build", "step.settle",
            "step.rung"}

    @pytest.mark.parametrize("producer", ["manager", "record"])
    def test_an_undeclared_name_raises(self, producer, since):
        with pytest.raises(ValueError, match="SETUP_PHASES"):
            if producer == "manager":
                with obs.setup_phase(UNDECLARED):
                    pass
            else:
                obs.record_setup_phase(UNDECLARED, 0.0, 0.0, 1.0)
        assert _phases(since) == []

    def test_an_interval_carries_both_clocks_of_its_start(self, since):
        wall, mono = time.time(), time.monotonic()
        with obs.setup_phase("ray_tpu.setup.init", where="here"):
            time.sleep(0.05)
        (ev,) = _phases(since)
        assert ev["type"] == "setup_phase" and "worker" in ev
        assert ev["name"] == "ray_tpu.setup.init"
        assert 0 <= ev["ts"] - wall < 0.05 and 0 <= ev["mono"] - mono < 0.05
        assert 0.05 <= ev["dur"] < 1.0
        assert ev["attrs"] == {"where": "here"}

    def test_children_nest_and_attrs_are_filled_inside(self, since):
        with obs.setup_phase("ray_tpu.setup.engine.build") as outer:
            with obs.setup_phase("ray_tpu.setup.engine.params") as attrs:
                attrs["bytes"] = 12
            outer["children"] = 1
        child, parent = _phases(since)  # booked at their ends
        assert (child["name"], child["attrs"]) == (
            "ray_tpu.setup.engine.params", {"bytes": 12})
        assert parent["attrs"] == {"children": 1}
        assert parent["mono"] <= child["mono"]
        assert child["mono"] + child["dur"] <= parent["mono"] + parent["dur"]
        # the record orders by start: the parent first
        names = [e["name"] for e in obs.setup_record()
                 if e["mono"] >= parent["mono"]]
        assert names == ["ray_tpu.setup.engine.build",
                         "ray_tpu.setup.engine.params"]

    def test_a_phase_that_raises_is_booked_with_the_error(self, since):
        with pytest.raises(KeyError):
            with obs.setup_phase("ray_tpu.setup.actor.init", cls="X"):
                raise KeyError("no such thing")
        (ev,) = _phases(since)
        assert ev["attrs"] == {"cls": "X", "error": "KeyError"}

    def test_merge_counts_an_interval_once_and_keeps_the_gts(self):
        local = {"type": "setup_phase", "name": "ray_tpu.setup.init",
                 "worker": "w", "ts": 10.0, "mono": 5.0, "dur": 1.0,
                 "attrs": {}}
        other = dict(local, name="ray_tpu.setup.init.gcs", mono=5.1)
        merged = timeline.merge_setup_phases(
            [dict(local, gts=5.25), {"type": "span", "name": "x"}],
            [local, other])
        assert [(e["name"], e["gts"]) for e in merged] == [
            ("ray_tpu.setup.init.gcs", None), ("ray_tpu.setup.init", 5.25)]
        assert set(merged[0]) == {"name", "worker", "ts", "mono", "gts",
                                  "dur", "attrs"}


# =====================================================================
# a jitted program's first call
# =====================================================================

class _Holder:
    pass


class TestFirstCall:
    def test_one_program_phase_then_the_bare_callable(self, since):
        holder = _Holder()
        bare = jax.jit(lambda x: (x * 2.0).sum())
        holder.step = bootstrap.FirstCall(bare, "toy", vars(holder), "step")
        assert float(holder.step(np.ones(8, np.float32))) == 16.0
        assert holder.step is bare  # not one added line of Python a step
        assert float(holder.step(np.ones(8, np.float32))) == 16.0
        (ev,) = _phases(since, "ray_tpu.setup.program")
        a = ev["attrs"]
        assert a["program"] == "toy" and a["cache"] in ("hit", "miss", "none")
        assert a["trace_s"] > 0 and a["lower_s"] > 0 and a["compile_s"] > 0
        assert a["first_run_s"] >= 0
        parts = a["trace_s"] + a["lower_s"] + a["compile_s"] + a["first_run_s"]
        assert parts == pytest.approx(ev["dur"], abs=0.05)

    def test_a_dict_holds_the_bare_callable_after_its_first_call(self, since):
        jits = {}
        bare = jax.jit(lambda x: x + 1)
        jits[16] = bootstrap.FirstCall(bare, "prefill_16", jits, 16)
        # what a caller asks of a jitted callable before any call is its own
        assert "stablehlo" in jits[16].lower(np.zeros(4, np.float32)
                                             ).as_text()
        jits[16](np.zeros(4, np.float32))
        assert jits[16] is bare
        assert len(_phases(since, "ray_tpu.setup.program")) == 1

    def test_a_holder_that_moved_on_is_left_alone(self, since):
        """A test (or a caller) that took the wrapper and put its own
        callable in its place keeps its own."""
        holder = _Holder()
        wrapper = holder.step = bootstrap.FirstCall(
            jax.jit(lambda x: x - 1), "toy", vars(holder), "step")

        def watched(x):
            return wrapper(x)

        holder.step = watched
        holder.step(np.zeros(4, np.float32))
        holder.step(np.zeros(4, np.float32))
        assert holder.step is watched
        assert len(_phases(since, "ray_tpu.setup.program")) == 1

    def test_nested_traces_are_counted_once(self):
        """JAX reports a jit traced inside another's trace alone AND inside
        its parent's duration: `trace_s` is their union."""
        seen = bootstrap.watch_compiles()

        @jax.jit
        def inner(x):
            time.sleep(0.2)  # Python that runs while `inner` is traced
            return x * 3.0

        @jax.jit
        def outer(x):
            time.sleep(0.2)
            return inner(x) + 1.0

        t0 = time.monotonic()
        outer.trace(np.ones(4, np.float32))
        wall = time.monotonic() - t0
        # reported: inner 0.2 and outer 0.4; spent: 0.4
        assert 0.4 <= seen["trace_s"] <= wall + 0.01

    def test_watch_compiles_counts_programs_and_their_parts(self):
        seen = bootstrap.watch_compiles()
        assert set(seen) == {"trace_s", "lower_s", "compile_s", "programs",
                             "cache_hits", "cache_misses"}
        jax.block_until_ready(jax.jit(lambda x: x * 5.0 - 2.0)(
            np.ones(3, np.float32)))
        assert seen["programs"] >= 1
        assert min(seen["trace_s"], seen["lower_s"], seen["compile_s"]) > 0


# =====================================================================
# the engine's and the train step's sites, at toy widths
# =====================================================================

class TestEngineSites:
    def test_a_toy_batcher_books_its_cache_and_each_programs_first_call(
            self, since):
        from ray_tpu.models import continuous_batching as CB
        from ray_tpu.models import transformer as T
        from ray_tpu.models.decoding import SamplingParams

        cfg = T.config("debug")
        params = T.init_params(cfg, jax.random.key(0))
        cb = CB.ContinuousBatcher(cfg, params, max_len=64, slots=2)
        try:
            # the decode step was compiled at the build (its phase is booked
            # by then); the others wait for their first call
            assert not isinstance(cb._decode_jit, bootstrap.FirstCall)
            assert isinstance(cb._install_jit, bootstrap.FirstCall)
            out = cb.submit([5, 17, 3], SamplingParams(max_tokens=4)
                            ).result(timeout=120)
            again = cb.submit(list(range(20)), SamplingParams(max_tokens=3)
                              ).result(timeout=120)
        finally:
            cb.shutdown()
        assert len(out) == 4 and len(again) == 3
        (cache,) = _phases(since, "ray_tpu.setup.engine.cache")
        assert cache["attrs"]["bytes"] == sum(
            leaf.nbytes for leaf in jax.tree.leaves(cb.cache))
        programs = [e["attrs"]["program"]
                    for e in _phases(since, "ray_tpu.setup.program")]
        # once each, however many requests and steps followed
        assert sorted(p for p in programs if p != "sample_first") == [
            "decode", "install", "prefill_16", "prefill_32"]
        # the decode program's is the build's: compiled before the cache is
        # there, with what it re-laid of the weights (nothing, on the CPU)
        (decode,) = (e for e in _phases(since, "ray_tpu.setup.program")
                     if e["attrs"]["program"] == "decode")
        assert decode["mono"] + decode["dur"] <= cache["mono"] + 1e-6
        assert decode["attrs"]["compile_s"] > 0
        assert decode["attrs"]["weights_relaid"] == []
        assert decode["attrs"]["weights_relaid_bytes"] == 0
        # and nothing of the wrapper is left on a window's path
        for fn in (cb._decode_jit, cb._install_jit, CB._sample_first,
                   *cb._prefill_jits.values()):
            assert not isinstance(fn, bootstrap.FirstCall)
            assert hasattr(fn, "lower")  # the jitted callable itself

    def test_a_toy_engine_books_backend_params_and_build(self, since):
        from ray_tpu.llm import LLMConfig
        from ray_tpu.llm.engine import ContinuousLLMEngine

        engine = ContinuousLLMEngine(LLMConfig(
            model="debug", max_len=64, cache_slots=2))
        engine.shutdown()
        names = [e["name"] for e in _phases(since)]
        assert names == ["ray_tpu.setup.engine.backend",
                         "ray_tpu.setup.engine.params",
                         "ray_tpu.setup.program",  # the decode step's
                         "ray_tpu.setup.engine.cache"]
        (params,) = _phases(since, "ray_tpu.setup.engine.params")
        assert params["attrs"]["bytes"] == sum(
            p.nbytes for p in jax.tree.leaves(engine.generator.params))
        assert params["attrs"]["programs"] >= 0


class TestTrainStepSites:
    def test_a_toy_step_with_remat_books_its_build_its_ladder_and_its_call(
            self, since, monkeypatch):
        from ray_tpu.models import transformer as T
        from ray_tpu.parallel.mesh import MeshSpec, build_mesh
        from ray_tpu.train import step as S

        cfg = T.config("debug", remat=True)
        mesh = build_mesh(MeshSpec(), jax.devices()[:1])
        opt = S.default_optimizer(cfg)
        state = S.init_state(cfg, opt, mesh, seed=0)
        # a device that says what it holds: the ladder is walked (the CPU
        # reports no limit and the first rung stands, uncompiled)
        monkeypatch.setattr(S, "_bytes_limit", lambda mesh: 1 << 40)
        step = S.make_train_step(cfg, opt, mesh)
        assert isinstance(step._jitted, bootstrap.FirstCall)
        batch = {"tokens": np.zeros((2, 32), np.int32)}
        state, _ = step(state, batch)
        bare = step._jitted
        assert not isinstance(bare, bootstrap.FirstCall)
        state, metrics = step(state, batch)
        assert step._jitted is bare and np.isfinite(float(metrics["loss"]))
        (build,) = _phases(since, "ray_tpu.setup.step.build")
        (settle,) = _phases(since, "ray_tpu.setup.step.settle")
        (rung,) = _phases(since, "ray_tpu.setup.step.rung")
        (call,) = _phases(since, "ray_tpu.setup.program")
        assert build["dur"] > 0
        assert settle["attrs"]["rungs_tried"] >= 1
        assert tuple(settle["attrs"]["kept"]) == step.remat_kept
        assert rung["attrs"]["fits"] and rung["attrs"]["bytes"] > 0
        assert rung["attrs"]["lower_s"] > 0 and rung["attrs"]["compile_s"] > 0
        assert tuple(rung["attrs"]["kept"]) == step.remat_kept
        assert settle["mono"] <= rung["mono"] and \
            rung["mono"] + rung["dur"] <= settle["mono"] + settle["dur"] + 1e-6
        assert call["attrs"]["program"] == "train_step"

    def test_a_rung_that_does_not_fit_is_booked_and_the_next_is_tried(
            self, since, monkeypatch):
        from ray_tpu.models import transformer as T
        from ray_tpu.parallel.mesh import MeshSpec, build_mesh
        from ray_tpu.train import step as S

        cfg = T.config("debug", remat=True)
        mesh = build_mesh(MeshSpec(), jax.devices()[:1])
        opt = S.default_optimizer(cfg)
        state = S.init_state(cfg, opt, mesh, seed=0)
        monkeypatch.setattr(S, "_bytes_limit", lambda mesh: 1)  # nothing fits
        step = S.make_train_step(cfg, opt, mesh)
        step(state, {"tokens": np.zeros((2, 32), np.int32)})
        (settle,) = _phases(since, "ray_tpu.setup.step.settle")
        rungs = _phases(since, "ray_tpu.setup.step.rung")
        assert step.remat_kept == T.REMAT_LADDER[-1]
        assert settle["attrs"] == {"rungs_tried": len(T.REMAT_LADDER),
                                   "kept": list(step.remat_kept)}
        # the last rung is taken as it is: every rung before it was compiled
        assert [r["attrs"]["fits"] for r in rungs] == \
            [False] * (len(T.REMAT_LADDER) - 1)


# =====================================================================
# a toy cluster, in a process of its own
# =====================================================================

def _in_a_process_of_its_own(script: str) -> dict:
    """Run ``script`` (it prints one JSON object as its last line) under the
    cluster cases' own limit; whatever it started dies with its session."""
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        p for p in (ROOT, os.environ.get("PYTHONPATH")) if p))
    proc = subprocess.Popen(
        [sys.executable, "-c", textwrap.dedent(script)], env=env, cwd=ROOT,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        start_new_session=True)
    try:
        out, err = proc.communicate(timeout=CLUSTER_LIMIT_S)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, 9)
        out, err = proc.communicate()
        pytest.fail(f"the cluster case passed its {CLUSTER_LIMIT_S} s:\n"
                    f"{err[-2000:]}")
    finally:
        try:
            os.killpg(proc.pid, 9)  # daemons a failed script left behind
        except ProcessLookupError:
            pass
    assert proc.returncode == 0, err[-3000:]
    return json.loads(out.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def toy_cluster():
    return _in_a_process_of_its_own("""
        import json, time
        import ray_tpu
        from ray_tpu import observability as obs
        from ray_tpu.util import state

        ray_tpu.init(num_cpus=2, log_to_driver=False)

        @ray_tpu.remote
        class Slow:
            def __init__(self):
                time.sleep(0.2)
            def ping(self):
                return 1

        a = Slow.remote()
        assert ray_tpu.get(a.ping.remote(), timeout=50) == 1
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            live = state.setup_timeline()
            if any(e["name"] == "ray_tpu.setup.actor.init" for e in live):
                break
            time.sleep(0.1)
        t0 = time.monotonic()
        ray_tpu.shutdown()
        shutdown_s = time.monotonic() - t0
        print(json.dumps({"live": live, "kept": obs.setup_record(),
                          "shutdown_s": shutdown_s}))
    """)


class TestToyCluster:
    def test_the_workers_phases_reach_the_state_api_with_a_gts(
            self, toy_cluster):
        live = {e["name"]: e for e in toy_cluster["live"]}
        boot, init = live["ray_tpu.setup.worker.boot"], \
            live["ray_tpu.setup.actor.init"]
        assert boot["gts"] is not None and init["gts"] is not None
        assert boot["worker"] == init["worker"] != ""
        assert init["attrs"]["cls"] == "Slow" and init["attrs"]["actor_id"]
        assert 0.2 <= init["dur"] < 10
        assert 0 < boot["attrs"]["ready_s"] <= boot["dur"] + 1e-6
        assert isinstance(boot["attrs"]["pooled"], bool)
        # one boot a process, and it ends where the actor arrives
        assert boot["mono"] + boot["dur"] <= init["mono"] + 0.05

    def test_the_drivers_own_phases_are_in_the_same_record(self, toy_cluster):
        live = {e["name"]: e for e in toy_cluster["live"]}
        whole = live["ray_tpu.setup.init"]
        for child in ("init.gcs", "init.raylet", "init.connect"):
            c = live["ray_tpu.setup." + child]
            assert whole["mono"] <= c["mono"] and \
                c["mono"] + c["dur"] <= whole["mono"] + whole["dur"] + 1e-6
        assert isinstance(
            live["ray_tpu.setup.init.raylet"]["attrs"]["native_built"], bool)
        starts = [e["gts"] if e["gts"] is not None else e["mono"]
                  for e in toy_cluster["live"]]
        assert starts == sorted(starts)

    def test_the_record_outlives_shutdown(self, toy_cluster):
        kept = {(e["worker"], e["name"], e["mono"]) for e in toy_cluster["kept"]}
        live = {(e["worker"], e["name"], e["mono"]) for e in toy_cluster["live"]}
        assert live <= kept
        assert any(name == "ray_tpu.setup.actor.init" for _, name, _ in kept)
        assert toy_cluster["shutdown_s"] < 20


def test_shutdown_with_the_gcs_dead_keeps_the_local_ring_within_its_limit():
    got = _in_a_process_of_its_own("""
        import json, os, signal, time
        import ray_tpu
        from ray_tpu import observability as obs
        from ray_tpu._private import worker as worker_mod

        ray_tpu.init(num_cpus=1, log_to_driver=False)
        node = worker_mod.global_worker.core._node
        os.kill(node.gcs_proc.pid, signal.SIGKILL)
        node.gcs_proc.wait(timeout=10)
        t0 = time.monotonic()
        ray_tpu.shutdown()
        print(json.dumps({"shutdown_s": time.monotonic() - t0,
                          "kept": obs.setup_record()}))
    """)
    names = {e["name"] for e in got["kept"]}
    assert {"ray_tpu.setup.init", "ray_tpu.setup.init.gcs",
            "ray_tpu.setup.init.raylet", "ray_tpu.setup.init.connect"} <= names
    # a dead GCS refuses the record's one call at once (1.2 s here, as at the
    # parent commit); one that hangs costs the call's 2 s limit and no more
    # (11.3 s against 9.2 s, PERF.md section 6, PR 56)
    assert got["shutdown_s"] < 1.2 + timeline._KEEP_LIMIT_S + 3.0


# =====================================================================
# tools/obsdump: the setup track
# =====================================================================

def test_obsdump_lays_the_phases_on_a_setup_track(since):
    with obs.setup_phase("ray_tpu.setup.engine.build"):
        with obs.setup_phase("ray_tpu.setup.engine.cache", bytes=7):
            time.sleep(0.01)
    shard = {"process": "worker-1", "pid": 1, "reason": "requested",
             "events": _phases(since)}
    doc = obsdump.merge([shard])
    track = [e for e in doc["traceEvents"] if e.get("pid") == "setup"]
    assert [(e["name"], e["ph"]) for e in track] == [
        ("ray_tpu.setup.engine.build", "X"),
        ("ray_tpu.setup.engine.cache", "X")]
    build, cache = track
    assert build["tid"] == cache["tid"] and cache["args"] == {"bytes": 7}
    assert build["ts"] <= cache["ts"] and \
        cache["ts"] + cache["dur"] <= build["ts"] + build["dur"] + 1
    assert cache["dur"] >= 0.01e6
