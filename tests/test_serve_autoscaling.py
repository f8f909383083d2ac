"""Serve production features: queue-depth autoscaling, streamed responses,
long-poll handle updates, async deployments, asyncio HTTP ingress
(reference: autoscaling_state.py:340, long_poll.py:318, replica.py:1630,
proxy.py:1098)."""

import http.client
import json
import threading
import time

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture
def serve_cluster():
    ray_tpu.init(num_cpus=6, ignore_reinit_error=True)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def _replica_count(name: str) -> int:
    from ray_tpu.serve.controller import _controller

    snap = ray_tpu.get(_controller().get_deployment.remote(name), timeout=30)
    return len(snap["replicas"]) if snap else 0


def test_autoscales_up_and_down(serve_cluster):
    @serve.deployment(
        max_ongoing_requests=2,
        autoscaling_config={
            "min_replicas": 1,
            "max_replicas": 4,
            "target_ongoing_requests": 2,
            "upscale_delay_s": 0.2,
            "downscale_delay_s": 1.0,
        },
    )
    class Slow:
        def __call__(self, _):
            time.sleep(0.4)
            return 1

    h = serve.run(Slow.bind())
    assert _replica_count("Slow") == 1

    # sustained load: 16 concurrent in-flight requests -> desired 8 -> cap 4
    stop = threading.Event()
    done = []

    def pump():
        while not stop.is_set():
            rs = [h.remote(None) for _ in range(16)]
            done.extend(r.result(timeout=60) for r in rs)

    t = threading.Thread(target=pump, daemon=True)
    t.start()
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and _replica_count("Slow") < 4:
        time.sleep(0.3)
    scaled_up = _replica_count("Slow")
    stop.set()
    t.join(timeout=60)
    assert scaled_up == 4, f"expected scale to 4 replicas, got {scaled_up}"
    assert all(v == 1 for v in done) and done

    # idle: back down to min_replicas
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and _replica_count("Slow") > 1:
        time.sleep(0.3)
    assert _replica_count("Slow") == 1


def test_streaming_deployment_handle(serve_cluster):
    @serve.deployment
    class Tokens:
        def generate(self, n):
            for i in range(n):
                yield f"token-{i}"

    h = serve.run(Tokens.bind())
    gen = h.generate.remote(5)
    vals = [ray_tpu.get(r, timeout=60) for r in gen]
    assert vals == [f"token-{i}" for i in range(5)]


def test_async_deployment_callable(serve_cluster):
    @serve.deployment
    class AsyncEcho:
        async def __call__(self, x):
            import asyncio

            await asyncio.sleep(0.05)
            return {"echo": x}

    h = serve.run(AsyncEcho.bind())
    assert h.remote("hi").result(timeout=60) == {"echo": "hi"}


def test_http_proxy_basic_and_streaming(serve_cluster):
    @serve.deployment
    def square(x):
        return x * x

    @serve.deployment(name="stream")
    def stream(n):
        for i in range(n):
            yield {"i": i}

    serve.run(square.bind())
    serve.run(stream.bind())
    port = serve.start_http_proxy(port=0)

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("POST", "/square", body=json.dumps(7))
    resp = conn.getresponse()
    assert resp.status == 200
    assert json.loads(resp.read())["result"] == 49

    conn.request("POST", "/stream", body=json.dumps(4))
    resp = conn.getresponse()
    assert resp.status == 200
    lines = [json.loads(l) for l in resp.read().decode().strip().splitlines()]
    assert lines == [{"i": i} for i in range(4)]
    conn.close()

    # the front door counts what it forwards: the chunks of a streamed
    # answer, the seconds of its own work on them, its process's CPU seconds
    before = serve.http_proxy_stats()
    assert before["stream_items"] == 4 and before["stream_forward_s"] > 0
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("POST", "/stream", body=json.dumps(3))
    assert len(conn.getresponse().read().decode().strip().splitlines()) == 3
    conn.close()
    after = serve.http_proxy_stats()
    assert after["stream_items"] == 7
    assert after["stream_forward_s"] > before["stream_forward_s"]
    assert after["process_cpu_s"] > before["process_cpu_s"] > 0
    assert after["ok"] == before["ok"] + 1

    # unknown deployment -> 404
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("POST", "/nope", body=json.dumps(1))
    assert conn.getresponse().status == 404
    conn.close()
