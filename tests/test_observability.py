"""Observability: metrics API + Prometheus endpoint, task events in the
state API, worker-log forwarding (reference: util/metrics.py,
stats/metric.h:104, GcsTaskManager, _private/log_monitor.py)."""

import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu.util import metrics as rmetrics
from ray_tpu.util import state as rstate


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


def test_task_events_in_state_api(cluster):
    @ray_tpu.remote
    def work(x):
        return x + 1

    assert ray_tpu.get([work.remote(i) for i in range(5)], timeout=60) == list(range(1, 6))
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        events = rstate.list_tasks()
        finished = [e for e in events if e["state"] == "FINISHED" and e["name"].endswith("work")]
        if len(finished) >= 5:
            break
        time.sleep(0.5)
    assert len(finished) >= 5
    summary = rstate.task_summary()
    assert summary.get("SUBMITTED", 0) >= 5 and summary.get("FINISHED", 0) >= 5


def test_metrics_prometheus_scrape(cluster):
    c = rmetrics.Counter("bench_requests_total", description="reqs", tag_keys=("kind",))
    g = rmetrics.Gauge("bench_inflight")
    h = rmetrics.Histogram("bench_latency_s", boundaries=[0.01, 0.1, 1.0])
    for _ in range(7):
        c.inc(1, tags={"kind": "a"})
    g.set(3.5)
    h.observe(0.05)
    h.observe(0.5)

    # metrics also flow from worker processes
    @ray_tpu.remote
    def worker_metric():
        from ray_tpu.util import metrics as m

        cc = m.Counter("bench_worker_total")
        cc.inc(2)
        time.sleep(3)  # let the pusher fire
        return 1

    ref = worker_metric.remote()
    endpoint = rstate.metrics_endpoint()
    deadline = time.monotonic() + 30
    text = ""
    while time.monotonic() < deadline:
        text = urllib.request.urlopen(f"http://{endpoint}/metrics", timeout=10).read().decode()
        if "bench_requests_total" in text and "bench_worker_total" in text:
            break
        time.sleep(1.0)
    ray_tpu.get(ref, timeout=60)
    assert 'bench_requests_total{kind="a"} 7' in text
    assert "bench_inflight 3.5" in text
    assert "bench_latency_s_count 2" in text
    assert "bench_worker_total 2" in text
    assert "ray_tpu_nodes_alive 1" in text


def test_worker_logs_forwarded(cluster):
    @ray_tpu.remote
    def noisy():
        print("hello-from-worker-stdout")
        return 1

    assert ray_tpu.get(noisy.remote(), timeout=60) == 1
    deadline = time.monotonic() + 20
    found = False
    while time.monotonic() < deadline and not found:
        lines = rstate.get_logs(limit=5000)["lines"]
        found = any("hello-from-worker-stdout" in l[3] for l in lines)
        if not found:
            time.sleep(0.5)
    assert found, "worker stdout line never reached the GCS log buffer"


def test_timeline_chrome_trace(cluster, tmp_path):
    """ray_tpu.timeline exports Chrome-trace spans with queued and
    execution phases (reference: ray.timeline, _private/profiling.py)."""
    import json

    @ray_tpu.remote
    def traced(x):
        time.sleep(0.05)
        return x

    ray_tpu.get([traced.remote(i) for i in range(4)])
    # events flush on a 1s cadence from both driver and workers — poll
    exec_spans = []
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline and len(exec_spans) < 4:
        time.sleep(0.5)
        events = ray_tpu.timeline()
        # nested test functions get qualified repr names — substring match
        exec_spans = [e for e in events if e["cat"] == "task"
                      and "traced" in e["name"]]
    assert len(exec_spans) >= 4
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in exec_spans)
    # at least some have the queued phase (needs the RUNNING event)
    assert any(e["cat"] == "queue" for e in events)
    # file export round-trips
    p = str(tmp_path / "trace.json")
    assert ray_tpu.timeline(p) is None
    with open(p) as f:
        assert json.load(f)


def test_tpu_profile_context(tmp_path):
    """tpu_profile wraps jax.profiler traces (CPU backend in CI). In a
    process of its own: a profiler session is process-wide state, and this
    process is the driver of the module's cluster — a profile that fails
    or hangs must fail this test alone (tests/test_device_spans.py holds
    the hook's failure paths, in process)."""
    import glob
    import os
    import subprocess
    import sys

    logdir = str(tmp_path / "xprof")
    code = (
        "import sys\n"
        "import jax.numpy as jnp\n"
        "import ray_tpu\n"
        "with ray_tpu.tpu_profile(sys.argv[1]):\n"
        "    (jnp.ones((64, 64)) @ jnp.ones((64, 64))).block_until_ready()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        p for p in (os.path.dirname(os.path.dirname(os.path.abspath(
            __file__))), os.environ.get("PYTHONPATH")) if p))
    proc = subprocess.run([sys.executable, "-c", code, logdir], env=env,
                          capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert glob.glob(logdir + "/plugins/profile/*/*.xplane.pb")


def test_microbenchmark_suite_runs():
    """The ray_perf microbenchmark suite (reference: _private/ray_perf.py)
    produces a positive rate for every benchmark."""
    from ray_tpu._private.ray_perf import main as perf_main

    results = perf_main(small=True)
    assert len(results) >= 10
    assert all(r["ops_per_s"] > 0 for r in results)


# ======================================================================
# Event bus + distributed tracing subsystem (ray_tpu/observability/)
# ======================================================================

def _tracing_on():
    from ray_tpu import observability as obs

    obs.configure(enabled=True, sample_rate=1.0)


def _tracing_off():
    from ray_tpu import observability as obs

    obs.configure(enabled=False)


@pytest.fixture
def tracing(cluster):
    _tracing_on()
    yield
    _tracing_off()


def _driver_job_id() -> str:
    from ray_tpu._private import worker as wm

    return wm.global_worker.job_id.hex()


def _wait_trace_spans(job_id, pred, timeout=30):
    """Poll the head aggregator until ``pred(spans)`` holds (events ride
    a 0.5s flusher from every process)."""
    from ray_tpu.observability import events as obs_events

    deadline = time.monotonic() + timeout
    spans = []
    while time.monotonic() < deadline:
        obs_events.flush()
        spans = rstate.get_trace(job_id)["spans"]
        if pred(spans):
            return spans
        time.sleep(0.25)
    raise AssertionError(
        f"trace never satisfied predicate; got {len(spans)} spans: "
        + ", ".join(sorted({s['name'] for s in spans})))


class TestDistributedTracing:
    def test_trace_propagation_3task_2actor_pipeline(self, tracing):
        """ISSUE acceptance: a traced 3-task/2-actor pipeline yields ONE
        connected span tree whose child spans reference parent span ids
        across process boundaries."""
        from ray_tpu import observability as obs

        @ray_tpu.remote
        def leaf(x):
            return x + 1

        @ray_tpu.remote
        def mid(x):
            return ray_tpu.get(leaf.remote(x)) * 2

        @ray_tpu.remote
        class Stage:
            def work(self, x):
                return ray_tpu.get(leaf.remote(x)) + 100

        with obs.span("pipeline3x2") as root:
            assert root is not None and root.sampled
            trace_id = root.trace_id
            r1 = ray_tpu.get(mid.remote(1), timeout=60)
            a, b = Stage.remote(), Stage.remote()
            r2 = ray_tpu.get(a.work.remote(5), timeout=60)
            r3 = ray_tpu.get(b.work.remote(6), timeout=60)
        assert (r1, r2, r3) == (4, 106, 107)

        job_id = _driver_job_id()
        # pipeline3x2 root + mid + 3×leaf + 2×actor work = 7 spans
        spans = _wait_trace_spans(
            job_id,
            lambda ss: sum(s["trace_id"] == trace_id for s in ss) >= 7)
        mine = [s for s in spans if s["trace_id"] == trace_id]
        by_id = {s["span_id"]: s for s in mine}

        # one connected tree: every non-root span's parent is present,
        # and walking children from the root reaches every span
        roots = [s for s in mine if not s.get("parent_span_id")]
        assert len(roots) == 1 and roots[0]["name"] == "pipeline3x2"
        for s in mine:
            if s.get("parent_span_id"):
                assert s["parent_span_id"] in by_id, s
        kids = {}
        for s in mine:
            kids.setdefault(s.get("parent_span_id") or "", []).append(
                s["span_id"])
        seen, stack = set(), [roots[0]["span_id"]]
        while stack:
            sid = stack.pop()
            seen.add(sid)
            stack.extend(kids.get(sid, []))
        assert seen == set(by_id)

        # cross-process: the tree spans ≥ 3 distinct processes (driver +
        # ≥ 2 workers), and a task child's recorder differs from its
        # parent's (the context crossed a process boundary)
        assert len({s["worker"] for s in mine}) >= 3
        mid_span = next(s for s in mine if s["name"].endswith("mid"))
        assert mid_span["worker"] != roots[0]["worker"]
        leafs = [s for s in mine if s["name"].endswith("leaf")]
        assert len(leafs) == 3
        # one leaf is mid's child, two are the actor methods' children
        actor_spans = [s for s in mine if s["kind"] == "actor_task"]
        assert len(actor_spans) == 2
        assert {s["parent_span_id"] for s in actor_spans} == {
            roots[0]["span_id"]}
        assert sorted(l["parent_span_id"] for l in leafs) == sorted(
            [mid_span["span_id"]] + [s["span_id"] for s in actor_spans])

    def test_chrome_trace_export_and_head_endpoint(self, tracing,
                                                   tmp_path):
        """ISSUE acceptance: Chrome-trace JSON export is valid and
        carries the parent linkage; the dashboard head endpoint returns
        the same span tree as rstate.get_trace()."""
        import json

        from ray_tpu import observability as obs
        from ray_tpu._private import worker as wm
        from ray_tpu.dashboard import DashboardHead

        @ray_tpu.remote
        def traced_export(x):
            return x

        with obs.span("export_root") as root:
            trace_id = root.trace_id
            ray_tpu.get([traced_export.remote(i) for i in range(3)],
                        timeout=60)
        job_id = _driver_job_id()
        spans = _wait_trace_spans(
            job_id,
            lambda ss: sum(s["trace_id"] == trace_id for s in ss) >= 4)
        mine = [s for s in spans if s["trace_id"] == trace_id]

        # file export round-trips as valid Chrome-trace JSON
        p = str(tmp_path / "trace.json")
        assert obs.export_trace(job_id, p) is None
        with open(p) as f:
            doc = json.load(f)
        assert doc["traceEvents"]
        by_args = {e["args"]["span_id"]: e for e in doc["traceEvents"]}
        root_ev = by_args[
            next(s["span_id"] for s in mine if s["name"] == "export_root")]
        assert root_ev["ph"] == "X" and root_ev["dur"] >= 0
        for s in mine:
            ev = by_args[s["span_id"]]
            assert ev["args"]["parent_span_id"] == (
                s.get("parent_span_id") or "")
            assert ev["args"]["trace_id"] == trace_id
        # a child row lives in a different pid (process) than its parent
        child = next(s for s in mine if s.get("parent_span_id"))
        assert by_args[child["span_id"]]["pid"] != root_ev["pid"]

        # the head HTTP endpoint serves the same tree
        head = DashboardHead(wm.global_worker.core.gcs_addr, port=0)
        try:
            with urllib.request.urlopen(
                    head.address + f"/api/v0/traces/{job_id}",
                    timeout=10) as r:
                via_http = json.load(r)
        finally:
            head.shutdown()
        http_ids = {s["span_id"] for s in via_http["spans"]
                    if s["trace_id"] == trace_id}
        assert http_ids == {s["span_id"] for s in mine}
        assert via_http["job_id"] == job_id

    def test_serve_request_span_parents_replica_span(self, tracing):
        """ISSUE acceptance: a serve request produces a replica-side
        execution span parented to the handle's proxy-side
        ``serve.request`` span."""
        from ray_tpu import observability as obs
        from ray_tpu import serve

        @serve.deployment
        class Echo:
            def __call__(self, x):
                return x * 3

        try:
            h = serve.run(Echo.bind())
            with obs.span("serve_root") as root:
                trace_id = root.trace_id
                assert h.remote(14).result() == 42
            # keep the replica alive until its 0.5s flusher has shipped
            # the execution span to the aggregator
            spans = _wait_trace_spans(
                _driver_job_id(),
                lambda ss: any(s["trace_id"] == trace_id
                               and s["name"] == "serve.request"
                               for s in ss)
                and any(s["trace_id"] == trace_id
                        and s["kind"] == "actor_task" for s in ss))
        finally:
            serve.shutdown()
        mine = [s for s in spans if s["trace_id"] == trace_id]
        req = next(s for s in mine if s["name"] == "serve.request")
        assert req["kind"] == "serve"
        assert req["attrs"]["deployment"] == "Echo"
        replica = next(s for s in mine if s["kind"] == "actor_task")
        assert replica["parent_span_id"] == req["span_id"]
        assert replica["worker"] != req["worker"]  # crossed into the replica

    def test_worker_side_bus_events_record_during_trace(self, tracing):
        """Worker processes are never configure()d — their task_state /
        object event recording must turn on via the INHERITED sampled
        span context (pre-fix it gated on the per-process enabled flag,
        so executor-side bus data was silently missing)."""
        from ray_tpu import observability as obs

        @ray_tpu.remote
        def traced_events_probe():
            import numpy as np
            # past object_store_inline_max_bytes (100 KiB): the return
            # takes the executor's plasma path, which must bus-record
            return np.zeros(256 * 1024, np.uint8)

        with obs.span("events_probe_root"):
            ray_tpu.get(traced_events_probe.remote(), timeout=60)

        deadline = time.monotonic() + 20
        running = []
        while time.monotonic() < deadline and not running:
            evs = rstate.list_events(etype="task_state", limit=5000)
            running = [e for e in evs
                       if "traced_events_probe" in e.get("name", "")
                       and e.get("state") == "RUNNING"]
            time.sleep(0.25)
        # RUNNING is recorded by the EXECUTING worker, not the driver
        assert running, "worker-side task_state never reached the bus"
        puts = rstate.list_events(etype="object_put", limit=5000)
        assert any(e.get("size", 0) >= 256 * 1024 for e in puts)

    def test_tracing_off_by_default_no_spans(self, cluster):
        """Tracing must be opt-in: with the default config no context is
        attached to submits and no span events reach the aggregator."""
        from ray_tpu.observability import events as obs_events
        from ray_tpu.observability import tracing as obs_tracing

        assert not obs_tracing.enabled()
        assert obs_tracing.for_outbound() is None

        @ray_tpu.remote
        def untraced_marker_task(x):
            return x

        assert ray_tpu.get(untraced_marker_task.remote(1), timeout=60) == 1
        obs_events.flush()
        time.sleep(1.5)  # outlive the workers' 0.5s flush cadence
        spans = rstate.get_trace(_driver_job_id())["spans"]
        assert not any("untraced_marker_task" in s["name"] for s in spans)


class TestEventBus:
    @pytest.mark.stress
    def test_flight_recorder_and_flush_to_aggregator(self, cluster):
        """record_event lands in the local flight-recorder ring AND (after
        a flush) in the GCS aggregator, queryable by type and job."""
        import uuid as _uuid

        from ray_tpu.observability import events as obs_events

        etype = "busprobe_" + _uuid.uuid4().hex[:8]
        for i in range(3):
            obs_events.record_event(etype, job_id="jobx", n=i)
        local = obs_events.local_events(etype)
        assert [e["n"] for e in local] == [0, 1, 2]
        assert all(e["ts"] > 0 and "worker" in e for e in local)

        deadline = time.monotonic() + 20
        got = []
        while time.monotonic() < deadline and len(got) < 3:
            obs_events.flush()
            got = rstate.list_events(etype=etype)
            time.sleep(0.1)
        assert [e["n"] for e in got] == [0, 1, 2]
        # job filter composes with the type filter
        assert rstate.list_events(etype=etype, job_id="nope") == []
        assert len(rstate.list_events(etype=etype, job_id="jobx")) == 3

    def test_node_reporter_feeds_head(self, cluster):
        """The per-node agent's reporter loop ships cpu/mem/object-store
        samples that surface through rstate.list_node_stats()."""
        deadline = time.monotonic() + 30
        stats = []
        while time.monotonic() < deadline and not stats:
            stats = rstate.list_node_stats()
            time.sleep(0.5)
        assert stats, "no node ever reported"
        s = stats[0]
        for key in ("node_id", "cpu_percent", "mem_total", "num_workers",
                    "store_capacity", "reported_at"):
            assert key in s, (key, s)

    def test_task_latency_histograms_on_scrape(self, cluster):
        """ISSUE acceptance: the Prometheus scrape exposes task-latency
        and queue-wait histograms once tasks have run."""

        @ray_tpu.remote
        def quick(x):
            return x

        assert ray_tpu.get([quick.remote(i) for i in range(4)],
                           timeout=60) == list(range(4))
        endpoint = rstate.metrics_endpoint()
        deadline = time.monotonic() + 30
        text = ""
        while time.monotonic() < deadline:
            text = urllib.request.urlopen(
                f"http://{endpoint}/metrics", timeout=10).read().decode()
            if ("ray_tpu_task_latency_s_count" in text
                    and "ray_tpu_task_queue_wait_s_count" in text):
                break
            time.sleep(1.0)
        assert 'ray_tpu_task_latency_s_bucket' in text
        assert 'ray_tpu_task_queue_wait_s_bucket' in text
        assert 'kind="task"' in text


# ======================================================================
# Satellite regression tests (each fails on the pre-fix code)
# ======================================================================

class TestPagedKvAdmitExhaustion:
    """paged_kv.py: pool exhaustion mid-admit must release every page a
    partial admit acquired (reused-prefix increfs AND fresh allocs) and
    requeue the request instead of failing it."""

    @pytest.fixture(scope="class")
    def tiny_model(self):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import transformer as T

        cfg = T.config("debug", dtype=jnp.float32,
                       param_dtype=jnp.float32)
        return cfg, T.init_params(cfg, jax.random.key(0))

    def test_exhaustion_mid_admit_no_leak_and_requeue(self, tiny_model):
        from concurrent.futures import Future

        from ray_tpu.models.decoding import SamplingParams
        from ray_tpu.models.paged_kv import (
            PagedBatcher,
            _Request,
            prefix_keys,
        )

        cfg, params = tiny_model
        pb = PagedBatcher(cfg, params, max_len=64, slots=2, page_size=16,
                          num_pages=6)  # usable pages: 1..5 (0 = trash)
        # drive _admit synchronously: kill the pump so nothing races
        pb._shutdown = True
        pb._wake.set()
        pb._thread.join(timeout=10)

        kv = pb.kv
        shared = list(range(1, 33))  # 2 full pages of prefix
        keys = prefix_keys(shared, 16)[:2]
        pA, pB = kv.alloc(), kv.alloc()
        kv.register_prefix(keys, [pA, pB])
        kv.decref(pA)
        kv.decref(pB)  # cached-free: rc=0, content + prefix entries kept
        held = [kv.alloc() for _ in range(3)]  # an "active" slot's pages
        assert all(p not in (pA, pB) for p in held)

        # 52 tokens → needs 4 pages now; reuses 2 cached, then the first
        # fresh alloc finds the free list empty → exhaustion MID-admit,
        # after the reused-prefix increfs already happened
        req = _Request(shared + list(range(100, 120)), SamplingParams(),
                       Future(), None)
        small = _Request(list(range(200, 210)), SamplingParams(),
                         Future(), None)
        pb._waiting.put(req)
        pb._waiting.put(small)  # queued BEHIND the big request
        pb._admit()

        # pre-fix: req.pages was only assigned after all allocs, so the
        # cleanup decref'd nothing — the two increfs leaked (rc stuck at
        # 1, pages gone from the free list) and the request failed with
        # RuntimeError instead of requeueing
        assert kv.rc[pA] == 0 and kv.rc[pB] == 0
        assert pA in kv.free and pB in kv.free
        assert req.kv.pages == []
        assert not req.future.done(), req.future.exception()
        assert pb._waiting.qsize() == 2
        # FIFO kept: the requeue goes to the FRONT — a tail requeue
        # would let every later small request leapfrog forever and the
        # big request's future would never resolve
        assert pb._waiting.queue[0] is req
        assert len(pb._free) == 2  # the slot went back too

        # pool pressure relieved → the requeued request admits cleanly,
        # and the small one after it
        for p in held:
            kv.decref(p)
        pb._admit()
        assert pb._waiting.qsize() == 0
        assert len(req.kv.pages) == 4 and req.slot >= 0
        assert not req.future.done()
        assert small.slot >= 0 and not small.future.done()

    def test_oversized_request_still_fails_fast(self, tiny_model):
        """A request that can NEVER fit (bigger than the whole pool)
        must not be requeued — that would spin forever."""
        from concurrent.futures import Future

        from ray_tpu.models.decoding import SamplingParams
        from ray_tpu.models.paged_kv import PagedBatcher, _Request

        cfg, params = tiny_model
        pb = PagedBatcher(cfg, params, max_len=64, slots=2, page_size=16,
                          num_pages=3)  # 2 usable pages
        pb._shutdown = True
        pb._wake.set()
        pb._thread.join(timeout=10)
        req = _Request(list(range(60)), SamplingParams(), Future(), None)
        pb._waiting.put(req)
        pb._admit()
        assert pb._waiting.qsize() == 0
        assert req.future.done() and req.future.exception() is not None


class TestActorCreationGate:
    def test_gate_queue_wait_not_charged_to_schedule_deadline(self):
        """gcs/server.py: an actor queued behind slow creations at the
        creation gate must not burn its schedule deadline while waiting —
        pre-fix it was marked DEAD on its first transient retry."""
        import asyncio

        from ray_tpu._private.config import config
        from ray_tpu._private.gcs.server import ActorInfo, GcsServer

        server = GcsServer.__new__(GcsServer)
        server._actor_create_gates = {}
        server._last_prestart = 0.0
        server.actors = {}
        server.placement_groups = {}
        server.nodes = {}
        server._pick_node_for = (
            lambda resources, pg, bundle_index, actor=None: "node1")
        server._notify_actor = lambda aid: None

        def mkactor(aid):
            return ActorInfo(actor_id=aid, job_id="j", name=None,
                             namespace="", state="PENDING",
                             serialized_spec=b"", owner_addr=None)

        attempts = {}

        async def fake_create(actor, node_id):
            if actor.actor_id == "a1":
                await asyncio.sleep(0.7)  # holds the gate past a2's window
                actor.state = "ALIVE"
                return None
            attempts[actor.actor_id] = attempts.get(actor.actor_id, 0) + 1
            if attempts[actor.actor_id] == 1:
                return 0.01  # transient lease rejection → retry loop
            actor.state = "ALIVE"
            return None

        server._try_create_once = fake_create

        old_timeout = config.actor_schedule_timeout_s
        old_conc = config.actor_creation_concurrency
        config.actor_schedule_timeout_s = 0.4
        config.actor_creation_concurrency = 1
        a1, a2 = mkactor("a1"), mkactor("a2")
        try:
            async def run():
                await asyncio.gather(server._schedule_actor(a1),
                                     server._schedule_actor(a2))

            asyncio.run(asyncio.wait_for(run(), timeout=15))
        finally:
            config.actor_schedule_timeout_s = old_timeout
            config.actor_creation_concurrency = old_conc
        assert a1.state == "ALIVE"
        # pre-fix: a2 sat 0.7s at the gate against a 0.4s deadline, its
        # first transient retry re-checked the clock and it went DEAD
        assert a2.state == "ALIVE", a2.death_cause
        assert attempts["a2"] == 2


class TestPubsubGapDetection:
    def test_subscribe_reports_dropped_floor(self):
        """gcs/server.py: when the bounded pubsub ring evicts events, a
        Subscribe reply must carry the dropped floor so a subscriber
        whose cursor predates it knows it can never replay the gap."""
        import asyncio

        from ray_tpu._private.gcs.server import GcsServer

        server = GcsServer.__new__(GcsServer)
        server.pubsub = {}
        server._pubsub_seq = 0
        server._pubsub_waiters = None
        server.pubsub_dropped = {}
        for i in range(10_005):  # ring maxlen is 10_000 → evicts 5
            server._publish("actor_state", f"a{i}")

        async def run():
            return await server.Subscribe("actor_state", after_seq=2,
                                          timeout_s=0)

        rep = asyncio.run(run())
        assert rep["events"]
        # seqs 1..5 were evicted; the floor is the NEWEST dropped seq
        assert rep["dropped_floor"] == 5  # pre-fix: KeyError

    def test_actor_hub_gap_wakes_every_watcher(self):
        """core_worker.py: a cursor below the publisher's dropped floor
        means a DEAD/restart transition may be unreplayable — every
        watcher must be woken (changed=True) instead of hanging."""
        import asyncio

        from ray_tpu._private.core_worker import _ActorStateHub

        class FakeGcs:
            def __init__(self):
                self.calls = 0

            async def acall(self, method, **kw):
                assert method == "Subscribe"
                self.calls += 1
                if self.calls == 1:
                    # ring rolled far past the subscriber's cursor and
                    # the watched actor's event is NOT in the window
                    return {"events": [], "next_seq": 120,
                            "dropped_floor": 100}
                await asyncio.sleep(30)  # park: no further events
                return {"events": [], "next_seq": 120}

        class FakeCore:
            _shutdown = False
            gcs = FakeGcs()

        async def run():
            hub = _ActorStateHub(FakeCore())
            hub._seq = 7  # cursor far below the floor
            ev = hub.watch("actor-x")
            # pre-fix: no events → no wake → this times out forever
            await asyncio.wait_for(ev.wait(), timeout=5)
            assert hub._seq >= 100  # cursor resynced past the gap
            hub._task.cancel()

        asyncio.run(run())


class TestCollectiveShapeMismatch:
    @pytest.mark.stress
    def test_mismatched_shape_allgather_falls_back(self, ray_start_regular):
        """objstore_group.py: ranks arriving at the channel rendezvous
        with different shapes must meet on a shape-independent key and
        fall back to the object path — pre-fix each rank waited on its
        own shape-suffixed key and timed out at 120s."""
        import numpy as np

        from ray_tpu.util import collective as col  # noqa: F401

        @ray_tpu.remote
        class Worker:
            def __init__(self, rank, world):
                self.rank, self.world = rank, world

            def run(self):
                from ray_tpu.util import collective as c

                c.init_collective_group(
                    self.world, self.rank, backend="objstore",
                    group_name="gmismatch")
                n = 4 if self.rank == 0 else 8
                out = c.allgather(
                    np.full((n,), float(self.rank)),
                    group_name="gmismatch")
                c.destroy_collective_group("gmismatch")
                return [o.shape for o in out]

        ws = [Worker.remote(i, 2) for i in range(2)]
        # pre-fix this raises after the 120s-per-rank rendezvous timeout
        outs = ray_tpu.get([w.run.remote() for w in ws], timeout=110)
        assert outs == [[(4,), (8,)], [(4,), (8,)]]

    @pytest.mark.stress
    def test_mismatch_after_matching_warmup_and_size_split(
            self, ray_start_regular):
        """The harder divergence cases: (a) ranks whose (shape, dtype)
        channels are already CACHED from a matching warm-up op still
        agree per-op when a later op mismatches (pre-fix the cache-hit
        rank skipped the rendezvous its peer blocked in); (b) ranks
        straddling the size threshold (one above, one below) also
        agree. The per-op meta exchange makes routing group-agreed."""
        import numpy as np

        @ray_tpu.remote
        class Worker:
            def __init__(self, rank, world):
                self.rank, self.world = rank, world

            def run(self):
                from ray_tpu.util import collective as c

                c.init_collective_group(
                    self.world, self.rank, backend="objstore",
                    group_name="gwarm")
                out = []
                # 1) matching warm-up: channels for (8,) now cached
                r = c.allgather(np.full((8,), 1.0 + self.rank),
                                group_name="gwarm")
                out.append([o.shape for o in r])
                # 2) mismatch AFTER warm-up: rank 0 reuses the cached
                #    shape, rank 1 arrives with a new one
                n = 8 if self.rank == 0 else 16
                r = c.allgather(np.full((n,), 2.0), group_name="gwarm")
                out.append([o.shape for o in r])
                # 3) matching again: the channel plane still works
                #    (caches/seq not wedged by the fallback)
                r = c.allreduce(np.full((8,), 1.0), group_name="gwarm")
                out.append(float(r[0]))
                # 4) size split: same nominal op, one rank under the
                #    2 MiB channel cap and one far over it
                m = 64 if self.rank == 0 else (3 << 20) // 8
                r = c.allgather(np.zeros((m,)), group_name="gwarm")
                out.append([o.shape for o in r])
                c.destroy_collective_group("gwarm")
                return out

        ws = [Worker.remote(i, 2) for i in range(2)]
        outs = ray_tpu.get([w.run.remote() for w in ws], timeout=110)
        big = (3 << 20) // 8
        for o in outs:
            assert o[0] == [(8,), (8,)]
            assert o[1] == [(8,), (16,)]
            assert o[2] == 2.0
            assert o[3] == [(64,), (big,)]


class TestServeStreamBackpressure:
    def test_stream_cap_rejects_before_first_yield(self):
        """serve/controller.py: streams draw from a separate budget
        strictly below the request cap, and reject at the cap BEFORE the
        first yield — so long-lived streams can never starve unary
        traffic of every replica slot."""
        from ray_tpu._private.serialization import dumps_function
        from ray_tpu.serve.controller import Replica, _Rejected

        class Svc:
            def gen(self, n):
                for i in range(n):
                    yield i

            def unary(self, x):
                return x

        # Replica is an actor class; drive the underlying callable
        rep = Replica._cls(dumps_function(Svc), (), {},
                           max_ongoing_requests=2)  # → stream budget = 1
        g1 = rep.handle_request_streaming("gen", (100,), {})
        assert next(g1) == 0  # stream 1 live, holding its slot

        g2 = rep.handle_request_streaming("gen", (100,), {})
        with pytest.raises(RuntimeError, match="stream capacity"):
            next(g2)  # pre-fix: both streams admitted, filling the cap

        # unary traffic still finds a slot while the stream lives
        # (pre-fix: two live streams → every slot gone → _Rejected)
        out = rep.handle_request_with_rejection("unary", (7,), {})
        assert not isinstance(out, _Rejected)
        assert out == 7

        # stream end releases both budgets
        g1.close()
        assert rep._streams == 0 and rep._ongoing == 0
        g3 = rep.handle_request_streaming("gen", (3,), {})
        assert list(g3) == [0, 1, 2]
        assert rep._streams == 0 and rep._ongoing == 0
