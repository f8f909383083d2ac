"""Serve front-door SLO tests (PR 12, ROADMAP item 2).

The contract under test (README "Serve front door"):

- deadline-exceeded → HTTP **504** with a structured JSON error body /
  gRPC ``DEADLINE_EXCEEDED``; the per-request deadline rides from
  ingress through the handle to the replica (no fixed per-hop waits);
- overload → HTTP **503 + Retry-After** *before the first response
  byte* / gRPC ``RESOURCE_EXHAUSTED``;
- replica death mid-stream → the documented terminal error frame
  ``{"error": {...}, "terminal": true}`` then a clean close (HTTP) /
  ``UNAVAILABLE`` after the partial messages (gRPC) — never a hung
  connection;
- replica death on a unary request → transparent retry on a surviving
  replica;
- the tier-1 smoke soak: the whole front door under a real node drain
  plus autoscaler resize, gated on ZERO app-visible errors and a
  bounded p99.
"""

import http.client
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve import slo

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


# ONE cluster + serve controller for the whole module (per-test
# deployments use unique names, proxies bind port=0 per test): a
# per-test init/shutdown costs ~4s x 15 tests of tier-1 wall clock.
# TestServeSoakSmoke runs FIRST in this file — it builds its own
# 2-node cluster and must start from an unconnected driver, i.e.
# before this fixture first instantiates.
@pytest.fixture(scope="module")
def serve_cluster():
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield
    try:
        serve.shutdown()
    except Exception:  # noqa: BLE001 — teardown is best-effort
        pass
    ray_tpu.shutdown()


def _post(port, path, payload=None, timeout_s=None, read_timeout=30):
    headers = {"Content-Type": "application/json"}
    if timeout_s is not None:
        headers[slo.TIMEOUT_HEADER] = str(timeout_s)
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode() if payload is not None else b"{}",
        headers=headers)
    with urllib.request.urlopen(req, timeout=read_timeout) as resp:
        return resp.status, json.loads(resp.read())


# =====================================================================
# The tier-1 SLO gate: smoke-scale soak under a real node drain +
# autoscaler resize (full scale: scale_bench.py serve_soak)
# =====================================================================
class TestServeSoakSmoke:
    def test_soak_smoke_slo_budget(self):
        import scale_bench

        out = scale_bench.bench_serve_soak(
            8, duration_s=6.0, workload="synthetic",
            max_tokens=8, token_sleep_s=0.02, request_timeout_s=10.0,
            min_replicas=2, max_replicas=3, target_ongoing=2.0,
            drain_deadline_s=5.0)
        # the SLO budget, enforced: ZERO app-visible errors (sheds are
        # clean 503+Retry-After and clients absorbed them), while one
        # of the two nodes drained and the autoscaler resized
        assert out["app_errors"] == 0, out
        assert out["terminal_frames"] == 0, out
        assert out["ok"] > 20, out
        assert out["drain"]["drained"] is True, out
        assert out["replicas"]["autoscaled"] is True, out
        # bounded p99: generous for a 1-CPU CI box, but a bound — a
        # churn-induced stall (the pre-PR proxy hung requests for up to
        # 120s) fails loudly
        assert out["p99_ms"] is not None and out["p99_ms"] < 8000, out
        # deadline machinery stayed quiet: nothing hit the 504 path
        assert out["deadline_504"] == 0, out


# =====================================================================
# Deadlines
# =====================================================================
class TestDeadline:
    def test_http_deadline_exceeded_is_504_with_structured_body(
            self, serve_cluster):
        @serve.deployment(name="slow")
        def slow(_):
            time.sleep(5.0)
            return "done"

        serve.run(slow.bind())
        port = serve.start_http_proxy(port=0)
        try:
            t0 = time.monotonic()
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(port, "/slow", {"x": 1}, timeout_s=1.0)
            took = time.monotonic() - t0
            assert ei.value.code == 504
            body = json.loads(ei.value.read())
            assert body["error"]["code"] == "deadline_exceeded"
            assert body["error"]["retryable"] is False
            # the deadline, not some hard-coded 120s wait, bounded this
            assert took < 8.0, took
        finally:
            serve.stop_http_proxy()

    def test_handle_timeout_s_option_raises_deadline_error(
            self, serve_cluster):
        @serve.deployment(name="slow2")
        def slow2():
            time.sleep(5.0)
            return "done"

        h = serve.run(slow2.bind())
        with pytest.raises(slo.DeadlineExceededError):
            h.options(timeout_s=0.8).remote().result()

    def test_replica_sees_request_deadline(self, serve_cluster):
        @serve.deployment(name="introspect")
        def introspect():
            d = serve.request_deadline()
            return None if d is None else d.remaining()

        h = serve.run(introspect.bind())
        remaining = h.options(timeout_s=30.0).remote().result(timeout=30)
        assert remaining is not None and 0 < remaining <= 30.0
        # without a deadline the contextvar reads empty
        assert h.remote().result(timeout=30) is None

    def test_private_methods_unreachable_over_http(self, serve_cluster):
        """The front door enforces the same underscore guard the
        in-process handle does — private/dunder replica methods 404."""
        @serve.deployment(name="guarded")
        class Guarded:
            def __call__(self, _):
                return "public"

            def _secret(self, _):
                return "private"

        serve.run(Guarded.bind(), name="guarded")
        port = serve.start_http_proxy(port=0)
        try:
            status, body = _post(port, "/guarded", {"x": 1})
            assert status == 200 and body["result"] == "public"
            for path in ("/guarded/_secret", "/guarded/__reduce__",
                         "/guarded/__init__"):
                with pytest.raises(urllib.error.HTTPError) as ei:
                    _post(port, path, {"x": 1})
                assert ei.value.code == 404, path
        finally:
            serve.stop_http_proxy()

    def test_batch_wait_past_deadline_is_504(self, serve_cluster):
        """A deadline expiring INSIDE a @serve.batch wait surfaces as
        the documented 504, not a 500 internal (futures.TimeoutError is
        not the builtin on 3.10 and must not leak as 'internal')."""
        @serve.deployment(name="batchy", max_ongoing_requests=8)
        class Batchy:
            # a lone request waits out most of the window; a 1s request
            # deadline expires inside it
            @serve.batch(max_batch_size=64, batch_wait_timeout_s=30.0)
            def predict(self, xs):
                return [x for x in xs]

            def __call__(self, x):
                return self.predict(x)

        serve.run(Batchy.bind(), name="batchy")
        port = serve.start_http_proxy(port=0)
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(port, "/batchy", {"x": 1}, timeout_s=1.0)
            assert ei.value.code == 504
            body = json.loads(ei.value.read())
            assert body["error"]["code"] == "deadline_exceeded"
        finally:
            serve.stop_http_proxy()

    def test_dead_on_arrival_deadline_rejected_at_replica(
            self, serve_cluster):
        """A request whose budget died in flight is NOT executed."""
        calls = []

        @serve.deployment(name="doa")
        def doa():
            calls.append(1)
            return "ran"

        h = serve.run(doa.bind())
        h.remote().result(timeout=30)  # warm path: one real call
        d = slo.Deadline(0.001)
        time.sleep(0.05)  # expire it before submit
        with pytest.raises(slo.DeadlineExceededError):
            h._call("__call__", (), {}, deadline=d).result(timeout=30)


# =====================================================================
# Load shedding
# =====================================================================
class TestLoadShedding:
    def test_admission_controller_shed_and_fifo(self):
        ac = slo.AdmissionController(max_inflight=1, max_queue_depth=0)
        ac.admit(slo.Deadline(5))
        with pytest.raises(slo.OverloadedError) as ei:
            ac.admit(slo.Deadline(5))
        assert ei.value.retry_after_s > 0
        ac.release()
        ac.admit(slo.Deadline(5))  # freed slot admits again
        ac.release()
        st = ac.stats()
        assert st["shed_depth"] == 1 and st["admitted"] == 2

    def test_admission_queue_wait_hands_off_slot(self):
        ac = slo.AdmissionController(max_inflight=1, max_queue_depth=4,
                                     queue_wait_s=5.0)
        ac.admit(slo.Deadline(10))
        got = []
        t = threading.Thread(
            target=lambda: (ac.admit(slo.Deadline(10)), got.append(1)),
            daemon=True)
        t.start()
        time.sleep(0.2)
        assert not got  # queued, not admitted
        ac.release()
        t.join(timeout=5)
        assert got  # FIFO handoff on release
        ac.release()

    def test_http_503_with_retry_after_before_first_byte(
            self, serve_cluster):
        @serve.deployment(name="busy", max_ongoing_requests=4)
        def busy(_):
            time.sleep(2.0)
            return "ok"

        serve.run(busy.bind())
        port = serve.start_http_proxy(port=0, max_inflight=1,
                                      max_queue_depth=0)
        try:
            occupier = threading.Thread(
                target=lambda: _post(port, "/busy", {"x": 0},
                                     timeout_s=20, read_timeout=30),
                daemon=True)
            occupier.start()
            time.sleep(0.5)  # the only admission slot is now held
            t0 = time.monotonic()
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(port, "/busy", {"x": 1}, timeout_s=20)
            took = time.monotonic() - t0
            assert ei.value.code == 503
            # Retry-After + structured body, and the shed is IMMEDIATE
            # (depth exceeded — not after burning the queue-wait budget)
            assert ei.value.headers.get("Retry-After") is not None
            body = json.loads(ei.value.read())
            assert body["error"]["code"] == "overloaded"
            assert body["error"]["retryable"] is True
            assert took < 2.0, took
            occupier.join(timeout=30)
        finally:
            serve.stop_http_proxy()

    def test_replica_saturation_maps_to_typed_overload(
            self, serve_cluster):
        """All replicas at max_ongoing past the deadline budget → the
        typed OverloadedError (still a RuntimeError for old callers)."""
        @serve.deployment(name="tiny", num_replicas=1,
                          max_ongoing_requests=1)
        def tiny():
            time.sleep(5.0)
            return "done"

        h = serve.run(tiny.bind())
        first = h.remote()
        time.sleep(0.8)
        with pytest.raises(slo.OverloadedError):
            h.remote().result(timeout=3.0)
        assert first.result(timeout=30) == "done"


# =====================================================================
# A streamed answer over HTTP: values that left the replica in one call
# still come one JSON line a value, in order; errors keep their frames
# =====================================================================
class TestStreamedAnswer:
    @staticmethod
    def _stream(port, path, payload, timeout_s=30):
        """(status, the answer's JSON lines in order)."""
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        try:
            conn.request("POST", path, body=json.dumps(payload),
                         headers={"Content-Type": "application/json",
                                  slo.TIMEOUT_HEADER: str(timeout_s)})
            resp = conn.getresponse()
            return resp.status, [json.loads(line) for line in
                                 resp.read().decode().splitlines()
                                 if line.strip()]
        finally:
            conn.close()

    def test_bursts_come_one_line_a_value(self, serve_cluster):
        @serve.deployment(name="bursts", num_replicas=1)
        class Bursts:
            def gen(self, req):
                for burst in range(req["bursts"]):
                    for i in range(10):  # ten values without a pause
                        yield {"i": 10 * burst + i}
                    if req.get("fail_after") == burst:
                        raise ValueError("broke between bursts")
                    time.sleep(req["pause"])

        serve.run(Bursts.bind(), name="bursts")
        port = serve.start_http_proxy(port=0)
        try:
            before = serve.http_proxy_stats()
            status, lines = self._stream(
                port, "/bursts/gen", {"bursts": 2, "pause": 0.2})
            assert status == 200
            assert lines == [{"i": i} for i in range(20)]
            after = serve.http_proxy_stats()
            assert after["stream_items"] - before["stream_items"] == 20
            assert after["stream_forward_s"] > before["stream_forward_s"]
            assert after["ok"] == before["ok"] + 1

            # the generator's error: every value before it, then ONE
            # terminal frame, then a clean end
            status, lines = self._stream(
                port, "/bursts/gen",
                {"bursts": 2, "pause": 0.2, "fail_after": 1})
            assert status == 200
            assert lines[:-1] == [{"i": i} for i in range(20)]
            assert lines[-1]["terminal"] is True
            assert lines[-1]["error"]["code"] == "internal"
            assert "broke between bursts" in lines[-1]["error"]["message"]

            # the deadline passes mid-stream, between two bursts: what
            # was yielded in time, then the 504's terminal frame
            errors = serve.http_proxy_stats()["stream_terminal_errors"]
            status, lines = self._stream(
                port, "/bursts/gen", {"bursts": 3, "pause": 2.0},
                timeout_s=1.0)
            assert status == 200
            assert lines[:-1] == [{"i": i} for i in range(10)]
            assert lines[-1]["terminal"] is True
            assert lines[-1]["error"]["code"] == "deadline_exceeded"
            stats = serve.http_proxy_stats()
            assert stats["stream_terminal_errors"] == errors + 1
            assert stats["stream_items"] - after["stream_items"] == 30
        finally:
            serve.stop_http_proxy()


# =====================================================================
# Replica death: mid-stream terminal frame, unary transparent retry
# =====================================================================
class TestReplicaDeath:
    def test_mid_stream_death_yields_terminal_frame_no_hang(
            self, serve_cluster):
        @serve.deployment(name="streamer", num_replicas=1)
        class Streamer:
            def gen(self, _):
                for i in range(200):
                    time.sleep(0.05)
                    yield {"i": i}

        serve.run(Streamer.bind(), name="streamer")
        h = serve.get_app_handle("streamer")
        port = serve.start_http_proxy(port=0)
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=30)
            conn.request("POST", "/streamer/gen",
                         body=json.dumps({"p": 1}),
                         headers={"Content-Type": "application/json",
                                  slo.TIMEOUT_HEADER: "30"})
            resp = conn.getresponse()
            assert resp.status == 200
            lines = []
            killed = False
            t0 = time.monotonic()
            while True:
                line = resp.readline()
                if not line:
                    break  # clean end of chunked stream
                line = line.strip()
                if not line:
                    continue
                lines.append(json.loads(line))
                if len(lines) == 3 and not killed:
                    ray_tpu.kill(h._rs.actors[0])
                    killed = True
                assert time.monotonic() - t0 < 25, "stream hung"
            conn.close()
            assert killed
            # data frames, then EXACTLY the documented terminal frame
            assert lines[0] == {"i": 0}
            terminal = lines[-1]
            assert terminal.get("terminal") is True
            assert terminal["error"]["code"] == "replica_died"
            # everything before the terminal frame is ordered data
            for j, frame in enumerate(lines[:-1]):
                assert frame == {"i": j}
        finally:
            serve.stop_http_proxy()

    def test_unary_death_transparent_retry(self, serve_cluster,
                                           tmp_path):
        marker = str(tmp_path / "died_once")

        @serve.deployment(name="flaky", num_replicas=2)
        class Flaky:
            def __call__(self, _):
                import os as _os

                # exactly one replica hard-dies mid-request; the marker
                # file makes the fault one-shot across the fleet
                try:
                    fd = _os.open(marker,
                                  _os.O_CREAT | _os.O_EXCL | _os.O_WRONLY)
                    _os.close(fd)
                    _os._exit(1)
                except FileExistsError:
                    pass
                return _os.getpid()

        h = serve.run(Flaky.bind(), name="flaky")
        # the response resolves despite the replica dying mid-call:
        # transparent re-dispatch onto the survivor
        out = h.options(timeout_s=60).remote({"x": 1}).result(timeout=60)
        assert isinstance(out, int)
        assert os.path.exists(marker)

    def test_unary_death_no_retry_when_not_idempotent(
            self, serve_cluster, tmp_path):
        marker = str(tmp_path / "died_once_nr")

        @serve.deployment(name="flaky_nr", num_replicas=2)
        class FlakyNR:
            def __call__(self, _):
                import os as _os

                try:
                    fd = _os.open(marker,
                                  _os.O_CREAT | _os.O_EXCL | _os.O_WRONLY)
                    _os.close(fd)
                    _os._exit(1)
                except FileExistsError:
                    pass
                return _os.getpid()

        h = serve.run(FlakyNR.bind(), name="flaky_nr")
        # drive requests until one lands on the dying replica; with
        # retry_on_failure=False that one must surface the failure
        saw_failure = False
        for _ in range(20):
            resp = h.options(timeout_s=30).remote({"x": 1})
            resp.retry_on_failure = False
            try:
                resp.result(timeout=30)
            except Exception:  # noqa: BLE001 — the surfaced death
                saw_failure = True
                break
        assert saw_failure


# =====================================================================
# gRPC parity
# =====================================================================
class TestGrpcParity:
    def _proxy(self, **kw):
        import grpc  # noqa: F401 — skip cleanly when absent

        return serve.start_grpc_proxy(port=0, **kw)

    def test_deadline_exceeded_status(self, serve_cluster):
        import grpc

        @serve.deployment(name="gslow")
        def gslow(_):
            time.sleep(5.0)
            return b"done"

        serve.run(gslow.bind())
        port = self._proxy()
        try:
            ch = grpc.insecure_channel(f"127.0.0.1:{port}")
            call = ch.unary_unary("/gslow/__call__")
            with pytest.raises(grpc.RpcError) as ei:
                call(b"x", timeout=1.0)
            assert ei.value.code() in (
                grpc.StatusCode.DEADLINE_EXCEEDED,)
            ch.close()
        finally:
            serve.stop_grpc_proxy()

    def test_shed_maps_to_resource_exhausted(self, serve_cluster):
        import grpc

        @serve.deployment(name="gbusy")
        def gbusy(_):
            time.sleep(2.0)
            return b"ok"

        serve.run(gbusy.bind())
        port = self._proxy(max_inflight=1, max_queue_depth=0)
        try:
            ch = grpc.insecure_channel(f"127.0.0.1:{port}")
            call = ch.unary_unary("/gbusy/__call__")
            occupier = threading.Thread(
                target=lambda: call(b"a", timeout=30), daemon=True)
            occupier.start()
            time.sleep(0.5)
            with pytest.raises(grpc.RpcError) as ei:
                call(b"b", timeout=10)
            assert ei.value.code() == grpc.StatusCode.RESOURCE_EXHAUSTED
            occupier.join(timeout=30)
            ch.close()
        finally:
            serve.stop_grpc_proxy()

    def test_unknown_deployment_not_found(self, serve_cluster):
        import grpc

        port = self._proxy()
        try:
            ch = grpc.insecure_channel(f"127.0.0.1:{port}")
            with pytest.raises(grpc.RpcError) as ei:
                ch.unary_unary("/nosuch/__call__")(b"x", timeout=10)
            assert ei.value.code() == grpc.StatusCode.NOT_FOUND
            ch.close()
        finally:
            serve.stop_grpc_proxy()

    def test_mid_stream_death_maps_to_unavailable(self, serve_cluster):
        import grpc

        @serve.deployment(name="gstream", num_replicas=1)
        class GStream:
            def gen(self, _):
                for i in range(200):
                    time.sleep(0.05)
                    yield json.dumps({"i": i})

        serve.run(GStream.bind(), name="gstream")
        h = serve.get_app_handle("gstream")
        port = self._proxy()
        try:
            ch = grpc.insecure_channel(f"127.0.0.1:{port}")
            stream = ch.unary_stream("/gstream/gen")
            got = []
            with pytest.raises(grpc.RpcError) as ei:
                for msg in stream(b"x", timeout=30):
                    got.append(msg)
                    if len(got) == 3:
                        ray_tpu.kill(h._rs.actors[0])
            assert ei.value.code() == grpc.StatusCode.UNAVAILABLE
            assert len(got) >= 3  # partial messages delivered first
            ch.close()
        finally:
            serve.stop_grpc_proxy()


