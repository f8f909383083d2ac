"""Host-side micro-benchmarks of the runtime (no device work).

    python bench.py --micro-smoke   data plane: puts/gets/channel/allreduce
                                    plus payload-copy counters (tier-1 runs it)
    python bench.py --serve-micro   Serve front door: unary RTT and streaming
                                    through the HTTP proxy over real sockets
    python bench.py --obs-micro     flight-recorder overhead, off and on

Each mode prints one ``*_JSON`` / ``# `` line. These are timings of host
code on whatever CPU runs them; none is a device metric. The check that the
program runs on the chip is ``chip_smoke.py``; the benchmark of cells on the
chip is ROADMAP "Speed" item 1 and does not exist yet. Without a flag this
script does nothing and exits non-zero.
"""

from __future__ import annotations

import json
import os
import sys
import time


def _force_cpu_jax() -> None:
    """These modes measure host code: hold this process's JAX to the CPU,
    so that it never takes the chip from a worker."""
    import jax

    jax.config.update("jax_platforms", "cpu")


def _run_micro_smoke() -> None:
    """CPU-only data-plane smoke (<60s): puts/gets/channel/allreduce plus
    the payload-copy counters, so a copy regression on the zero-copy put
    path fails loudly in tier-1 instead of silently halving bandwidth."""
    _force_cpu_jax()
    import numpy as np

    import ray_tpu
    from ray_tpu._private import serialization as ser
    from ray_tpu.experimental import TensorChannel

    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    out: dict = {}
    arr = np.zeros((512, 512), np.float32)  # 1 MiB

    def rate(fn, n):
        for _ in range(3):
            fn()
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        return round(n / (time.perf_counter() - t0), 1)

    before = ser.copy_stats()
    out["put_1mb_ops_s"] = rate(lambda: ray_tpu.put(arr), 50)
    after = ser.copy_stats()
    out["put_payload_copies"] = (
        after["copies"]["put"] - before["copies"]["put"])
    ref = ray_tpu.put(arr)
    out["get_1mb_ops_s"] = rate(lambda: ray_tpu.get(ref), 50)
    after2 = ser.copy_stats()
    out["get_payload_copies_per_op"] = round(
        (after2["copies"]["get"] - after["copies"]["get"]) / 53.0, 2)

    tch = TensorChannel((512, 512), "float32")
    trd = tch.reader()

    def chan_op():
        tch.write(arr)
        trd.read_view()
        trd.release()

    out["tensor_channel_1mb_ops_s"] = rate(chan_op, 100)
    tch.close()

    @ray_tpu.remote
    class Rank:
        def __init__(self, rank, world):
            from ray_tpu.util import collective as col

            col.init_collective_group(
                world, rank, backend="objstore", group_name="smoke")
            self.arr = np.ones(4 * (1 << 20) // 4, np.float32)

        def step(self, iters):
            import time as _t

            from ray_tpu.util import collective as col

            t0 = _t.perf_counter()
            for _ in range(iters):
                col.allreduce(self.arr, group_name="smoke")
            return _t.perf_counter() - t0

    ranks = [Rank.remote(i, 2) for i in range(2)]
    ray_tpu.get([r.step.remote(1) for r in ranks])
    times = ray_tpu.get([r.step.remote(5) for r in ranks])
    out["allreduce_4mb_2rank_gb_s"] = round(
        4 * (1 << 20) * 5 / max(times) / 1e9, 3)
    ray_tpu.shutdown()
    print("MICRO_SMOKE_JSON " + json.dumps(out))


def _run_obs_micro() -> None:
    """Flight-recorder overhead micro (PR 20): the cost of lifecycle
    marks with the recorder OFF (the default every hot path pays), ON
    (one ring append), plus the task-sampling decision and a full-ring
    ``dump_now``. The disabled number is the one the overhead-guard
    test budgets — instrumentation nobody asked for must be ~free."""
    import tempfile

    from ray_tpu.observability import dump as obs_dump
    from ray_tpu.observability import events as obs_events
    from ray_tpu.observability import timeline

    out: dict = {}
    n_off = 1_000_000
    timeline.configure(enabled=False)
    t0 = time.perf_counter()
    for _ in range(n_off):
        timeline.mark_actor("bench_actor", "submit")
    out["mark_disabled_ns"] = round(
        (time.perf_counter() - t0) / n_off * 1e9, 1)

    timeline.configure(enabled=True, task_sample=1.0)
    n_on = 100_000
    t0 = time.perf_counter()
    for _ in range(n_on):
        timeline.mark_actor("bench_actor", "submit")
    out["mark_enabled_us"] = round(
        (time.perf_counter() - t0) / n_on * 1e6, 2)
    t0 = time.perf_counter()
    for _ in range(n_on):
        timeline.task_sampled("aabbccdd" * 4)
    out["task_sampled_ns"] = round(
        (time.perf_counter() - t0) / n_on * 1e9, 1)
    out["overhead_ratio"] = round(
        out["mark_enabled_us"] * 1e3 / max(out["mark_disabled_ns"], 0.1),
        1)

    # dump latency with the ring at capacity (the failure-path cost)
    with tempfile.TemporaryDirectory() as d:
        os.environ["RAY_TPU_DEBUG_DIR"] = d
        try:
            t0 = time.perf_counter()
            path = obs_dump.dump_now("bench", force=True)
            out["dump_full_ring_ms"] = round(
                (time.perf_counter() - t0) * 1e3, 2)
            out["dump_shard_kb"] = round(
                os.path.getsize(path) / 1024.0, 1) if path else None
        finally:
            os.environ.pop("RAY_TPU_DEBUG_DIR", None)
    out["ring_events"] = len(obs_events.local_events())
    timeline.configure(enabled=False)
    print("OBS_MICRO_JSON " + json.dumps(out))


def _run_serve_micro() -> None:
    """Serve front-door dispatch micro (PR 12): unary RTT and streaming
    chunk throughput through the HTTP proxy, measured end to end over
    real sockets. Merged into MICROBENCH.json as ``serve_proxy`` (the
    round-10 before/after row: the pre-PR proxy burned 2-3 executor-
    thread hops per request and one PER CHUNK on streams; dispatch now
    rides the proxy's event loop straight into the fastpath-coded RPC
    plane)."""
    import http.client
    import statistics
    import threading

    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init(num_cpus=4)

    @serve.deployment(name="echo", max_ongoing_requests=64)
    def echo(x):
        return x

    @serve.deployment(name="chunks", max_ongoing_requests=64)
    def chunks(n):
        for i in range(int(n)):
            yield {"i": i}

    @serve.deployment(name="tokens", max_ongoing_requests=64)
    def tokens(n):
        # LLM-shaped stream: one yield per decode step (~30 ms/token)
        for i in range(int(n)):
            time.sleep(0.03)
            yield {"t": i}

    @serve.deployment(name="prefill", max_ongoing_requests=64)
    def prefill(n):
        # cold-start stream shape: a prefill-length pause, then tokens.
        # NOTHING is buffered before the first yield, so the consumer's
        # wait for the first byte really blocks — the shape that
        # serializes on a thread-pool proxy (5 default-executor threads
        # on a 1-CPU box) and that loop-native dispatch rides for free.
        time.sleep(0.25)
        for i in range(int(n)):
            yield {"t": i}

    serve.run(echo.bind())
    serve.run(chunks.bind(), name="chunks")
    serve.run(tokens.bind(), name="tokens")
    serve.run(prefill.bind(), name="prefill")
    port = serve.start_http_proxy(port=0)

    def post(conn, path, payload):
        conn.request("POST", path, body=json.dumps(payload),
                     headers={"Content-Type": "application/json"})
        return conn.getresponse()

    out = {}
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        for _ in range(20):  # warm: replica start + handle resolution
            post(conn, "/echo", {"w": 1}).read()
        # -- unary sequential RTT --------------------------------------
        lats = []
        for i in range(300):
            t0 = time.perf_counter()
            post(conn, "/echo", {"i": i}).read()
            lats.append((time.perf_counter() - t0) * 1000)
        lats.sort()
        out["unary_rtt_p50_ms"] = round(statistics.median(lats), 2)
        out["unary_rtt_p99_ms"] = round(lats[int(len(lats) * 0.99) - 1], 2)
        # -- unary concurrent throughput -------------------------------
        n_threads, per = 32, 20
        done = []

        def worker():
            c = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
            for i in range(per):
                post(c, "/echo", {"i": i}).read()
            c.close()
            done.append(1)

        t0 = time.perf_counter()
        ts = [threading.Thread(target=worker, daemon=True)
              for _ in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        wall = time.perf_counter() - t0
        assert len(done) == n_threads
        out["unary_concurrent_rps"] = round(n_threads * per / wall, 1)
        # -- streaming chunk throughput (8 concurrent streams) ---------
        n_streams, n_chunks = 8, 50
        stream_walls = []

        def stream_worker():
            c = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
            t0 = time.perf_counter()
            resp = post(c, "/chunks", n_chunks)
            got = 0
            while True:
                line = resp.readline()
                if not line:
                    break
                if line.strip():
                    got += 1
            assert got == n_chunks, got
            stream_walls.append(time.perf_counter() - t0)
            c.close()

        ts = [threading.Thread(target=stream_worker, daemon=True)
              for _ in range(n_streams)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        wall = time.perf_counter() - t0
        assert len(stream_walls) == n_streams
        out["stream_chunks_per_s"] = round(n_streams * n_chunks / wall, 1)
        out["stream_wall_p50_ms"] = round(
            statistics.median(stream_walls) * 1000, 1)
        # -- 32 concurrent SLOW (LLM-shaped) streams -------------------
        # 20 tokens x 30 ms = 600 ms nominal per stream. Each in-flight
        # token wait held an executor thread in the pre-PR proxy — with
        # the default pool (~cpu+4 threads) 32 streams serialize; loop-
        # native dispatch keeps every stream at its nominal latency.
        n_slow, n_tok = 32, 20
        slow_walls = []

        def slow_worker():
            c = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
            t0 = time.perf_counter()
            resp = post(c, "/tokens", n_tok)
            got = 0
            while True:
                line = resp.readline()
                if not line:
                    break
                if line.strip():
                    got += 1
            assert got == n_tok, got
            slow_walls.append(time.perf_counter() - t0)
            c.close()

        ts = [threading.Thread(target=slow_worker, daemon=True)
              for _ in range(n_slow)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=240)
        assert len(slow_walls) == n_slow
        slow_walls.sort()
        out["slow_stream_nominal_ms"] = n_tok * 30
        out["slow_stream_32x_wall_p50_ms"] = round(
            statistics.median(slow_walls) * 1000, 1)
        out["slow_stream_32x_wall_p99_ms"] = round(
            slow_walls[int(n_slow * 0.99) - 1] * 1000, 1)
        # -- 48 concurrent cold-start streams: time to first byte ------
        # 250 ms nominal prefill before the first token; the first-byte
        # wait cannot be hidden by producer-side buffering
        n_cold = 48
        ttfb = []

        def cold_worker():
            c = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
            t0 = time.perf_counter()
            resp = post(c, "/prefill", 3)
            line = resp.readline()
            assert line
            ttfb.append(time.perf_counter() - t0)
            resp.read()
            c.close()

        ts = [threading.Thread(target=cold_worker, daemon=True)
              for _ in range(n_cold)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=240)
        assert len(ttfb) == n_cold
        ttfb.sort()
        out["cold_stream_nominal_first_byte_ms"] = 250
        out["cold_stream_48x_first_byte_p50_ms"] = round(
            statistics.median(ttfb) * 1000, 1)
        out["cold_stream_48x_first_byte_p99_ms"] = round(
            ttfb[int(n_cold * 0.99) - 1] * 1000, 1)
        conn.close()
    finally:
        serve.stop_http_proxy()
        serve.shutdown()
        ray_tpu.shutdown()

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "MICROBENCH.json")
    try:
        with open(path) as f:
            detail = json.load(f)
    except (OSError, ValueError):
        detail = {}
    detail["serve_proxy"] = out
    with open(path, "w") as f:
        json.dump(detail, f, indent=1)
    print("# serve_proxy " + json.dumps(out))


def main() -> None:
    if "--micro-smoke" in sys.argv:
        _run_micro_smoke()
    elif "--serve-micro" in sys.argv:
        _run_serve_micro()
    elif "--obs-micro" in sys.argv:
        _run_obs_micro()
    else:
        sys.exit(__doc__)


if __name__ == "__main__":
    main()
