"""Serve tests (reference strategy: python/ray/serve/tests — 153 files;
here: deploy/route/handle, replicas, batching, reconfigure, HTTP proxy)."""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture
def serve_cluster(ray_start_regular):
    yield
    serve.shutdown()


class TestDeployment:
    def test_function_deployment(self, serve_cluster):
        @serve.deployment
        def doubler(x):
            return x * 2

        h = serve.run(doubler.bind())
        assert h.remote(21).result() == 42

    def test_class_deployment_with_state(self, serve_cluster):
        @serve.deployment(num_replicas=1)
        class Counter:
            def __init__(self, start):
                self.n = start

            def incr(self, k):
                self.n += k
                return self.n

        h = serve.run(Counter.bind(100))
        assert h.incr.remote(5).result() == 105
        assert h.incr.remote(5).result() == 110

    def test_multiple_replicas_route(self, serve_cluster):
        @serve.deployment(num_replicas=2)
        class Who:
            def __init__(self):
                import os

                self.pid = os.getpid()

            def __call__(self, _):
                return self.pid

        h = serve.run(Who.bind())
        pids = {h.remote(None).result() for _ in range(20)}
        assert len(pids) == 2  # both replicas served traffic

    def test_options_override(self, serve_cluster):
        @serve.deployment
        def f(x):
            return x

        d = f.options(name="custom", num_replicas=1)
        h = serve.run(d.bind())
        assert h.remote(7).result() == 7
        assert "custom" in serve.status()["deployments"]

    def test_get_app_handle_and_delete(self, serve_cluster):
        @serve.deployment(name="app1")
        def f(x):
            return x + 1

        serve.run(f.bind())
        h = serve.get_app_handle("app1")
        assert h.remote(1).result() == 2
        serve.delete("app1")
        with pytest.raises(ValueError):
            serve.get_app_handle("app1")

    def test_error_propagates(self, serve_cluster):
        @serve.deployment
        def bad(x):
            raise ValueError("boom")

        h = serve.run(bad.bind())
        with pytest.raises(Exception, match="boom"):
            h.remote(1).result()


class TestBatching:
    def test_batch_collects_concurrent_calls(self, serve_cluster):
        @serve.deployment(max_ongoing_requests=16)
        class Model:
            @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.2)
            def predict(self, xs):
                # returns batch size with each result to observe batching
                return [(x, len(xs)) for x in xs]

        h = serve.run(Model.bind())
        results = []
        threads = [
            threading.Thread(target=lambda i=i: results.append(h.predict.remote(i).result()), daemon=True)
            for i in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert sorted(r[0] for r in results) == list(range(8))
        assert max(r[1] for r in results) > 1  # at least one real batch formed

    def test_batch_free_function(self):
        calls = []

        @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.1)
        def predict(xs):
            calls.append(len(xs))
            return [x * 10 for x in xs]

        outs = []
        threads = [
            threading.Thread(target=lambda i=i: outs.append(predict(i)), daemon=True) for i in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert sorted(outs) == [0, 10, 20, 30]


class TestHTTPProxy:
    def test_http_roundtrip(self, serve_cluster):
        @serve.deployment(name="adder")
        def adder(payload):
            return payload["a"] + payload["b"]

        serve.run(adder.bind())
        port = serve.start_http_proxy(port=0)
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/adder",
                data=json.dumps({"a": 2, "b": 3}).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=30) as resp:
                body = json.loads(resp.read())
            assert body["result"] == 5
        finally:
            serve.stop_http_proxy()


class TestModelServing:
    def test_jax_model_replica(self, serve_cluster):
        """A model-on-TPU-style replica: jitted forward under batching
        (BASELINE.md 'Serve BERT-base replicas with dynamic batching'
        shape of workload, tiny here)."""

        @serve.deployment(max_ongoing_requests=8)
        class TinyLM:
            def __init__(self):
                import jax

                import ray_tpu.models.transformer as T

                self.cfg = T.config("debug")
                self.params = T.init_params(self.cfg, jax.random.key(0))
                import functools

                self.fwd = jax.jit(
                    functools.partial(T.forward, self.cfg)
                )

            @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.05)
            def predict(self, token_lists):
                import jax.numpy as jnp
                import numpy as np

                toks = jnp.asarray(np.stack(token_lists).astype(np.int32))
                logits = self.fwd(self.params, toks)
                return [np.asarray(l[-1]).argmax().item() for l in logits]

        h = serve.run(TinyLM.bind())
        tokens = np.ones(16, dtype=np.int32)
        out = h.predict.remote(tokens).result(timeout=120)
        assert isinstance(out, int)


    def test_replica_profile_hook(self, serve_cluster, tmp_path):
        """``serve.profile_start`` / ``profile_stop`` trace every replica
        of a deployment: the trace is of the replica's process, with the
        handler span of a traced request in it. The hook is off the
        request path: neither a handle nor the HTTP proxy reaches it."""
        from ray_tpu import observability as obs

        @serve.deployment(name="matmul", num_replicas=2)
        class Matmul:
            def __call__(self, n):
                import jax.numpy as jnp

                return float((jnp.ones((n, n)) @ jnp.ones((n, n))).sum())

        h = serve.run(Matmul.bind())
        for _ in range(4):  # loads JAX in both replicas
            assert h.remote(8).result(timeout=120) == 512.0
        assert serve.profile_stop("matmul") == ["", ""]

        # the public doors: a request named like the hook starts nothing
        with pytest.raises(Exception):
            h.profile_start.remote(str(tmp_path / "door")).result(timeout=60)
        port = serve.start_http_proxy(port=0)
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/matmul/profile_start",
                data=json.dumps(str(tmp_path / "door")).encode())
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(req, timeout=60)
            assert err.value.code >= 400
        finally:
            serve.stop_http_proxy()
        assert not (tmp_path / "door").exists()
        assert serve.profile_stop("matmul") == ["", ""]

        assert serve.profile_start("matmul", str(tmp_path)) == 2
        obs.configure(enabled=True, sample_rate=1.0)
        try:
            with obs.span("profiled") as root:
                for _ in range(4):
                    assert h.remote(16).result(timeout=120) == 4096.0
        finally:
            obs.configure(enabled=False)
        paths = serve.profile_stop("matmul")
        assert [os.path.relpath(p, tmp_path).split(os.sep)[0]
                for p in paths] == ["replica_0", "replica_1"]
        assert all(p.endswith(".xplane.pb") for p in paths)
        assert serve.profile_stop("matmul") == ["", ""]

        from jax.profiler import ProfileData

        trace_ids = {
            dict(e.stats)["trace_id"] for path in paths
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.endswith("handle_request_with_rejection")}
        assert trace_ids == {root.trace_id}


class TestReplicaSideRejection:
    """VERDICT r4 item 5 (reference: replica.py:1630
    handle_request_with_rejection): the replica enforces
    max_ongoing_requests itself and rejects at capacity; handles retry
    with backoff on another replica. Two competing handles — which each
    believe they have the full caller-side budget — must not overload a
    replica."""

    def test_two_handles_never_exceed_replica_cap(self, serve_cluster):
        from ray_tpu.serve.controller import get_app_handle

        @serve.deployment(name="capped", num_replicas=2,
                          max_ongoing_requests=2)
        class Slow:
            def __call__(self, x):
                time.sleep(0.3)
                return x

        serve.run(Slow.bind(), name="capped")
        h1 = get_app_handle("capped")
        h2 = get_app_handle("capped")

        results, errors = [], []

        def _fire(handle, val):
            try:
                results.append(handle.remote(val).result(timeout=120))
            except Exception as e:  # noqa: BLE001
                errors.append(repr(e))

        threads = []
        for i in range(8):
            for h in (h1, h2):
                t = threading.Thread(target=_fire, args=(h, i), daemon=True)
                t.start()
                threads.append(t)
        for t in threads:
            t.join(timeout=180)
        assert not errors, errors
        assert len(results) == 16
        # the replicas' own accounting: peak concurrency never above cap
        for actor in h1._rs.actors:
            stats = ray_tpu.get(actor.ongoing_stats.remote(), timeout=30)
            assert stats["peak"] <= stats["max"], stats
            assert stats["ongoing"] == 0, stats
        serve.delete("capped")

    def test_rejection_raises_when_saturated_past_deadline(
            self, serve_cluster):
        from ray_tpu.serve.controller import get_app_handle

        @serve.deployment(name="tiny_cap", num_replicas=1,
                          max_ongoing_requests=1)
        class Busy:
            def __call__(self):
                time.sleep(15.0)
                return "done"

        serve.run(Busy.bind(), name="tiny_cap")
        h = get_app_handle("tiny_cap")
        first = h.remote()
        time.sleep(1.0)  # let the first request occupy the only slot
        h2 = get_app_handle("tiny_cap")
        with pytest.raises(RuntimeError, match="overloaded"):
            h2.remote().result(timeout=6.0)
        assert first.result(timeout=90) == "done"
        serve.delete("tiny_cap")
