"""Runner of the serve cells: HTTP front door -> Serve replica -> engine.

This process is the Serve driver: it starts the cluster, deploys one replica
(which takes the chip), starts the HTTP proxy and plays the cell's traffic
against it from one event loop. It never initialises a JAX backend; the
device, its memory, the profiler and the reference check are the replica's,
through ``replica.py``'s added methods. The profiler's file is read here,
once the cluster is down: nothing then shares the interpreter with the
reduction, and the reduction takes nothing from the window it describes.

The deployment and the proxy run with the limits the program ships (60 s
deadline, 256 in flight, queue depth 128). The HTTP front door takes one
positional payload, so a request cannot carry ``max_tokens``: the number of
new tokens is the deployment's, fixed per cell by its traffic file.
"""

from __future__ import annotations

import os
import re
import threading
import time

import numpy as np

from benchmarks import (harness, loadgen, program_spans, replica,
                        stream_spans, trace_reduce)

WARMUP_TIMEOUT_S = 540  # the first request of a bucket compiles
# What the cluster's processes read at their start, set before ray_tpu.init.
# A caller waits this long for a PENDING actor to answer (the program ships
# 180 s), and the Serve controller calls a replica's health_check while the
# replica is still in __init__: a cold replica of the largest configuration
# builds its weights past 180 s. 600 s is the program's own limit on an
# actor's creation (actor_creation_timeout_s), so the controller's wait
# (300 s in ServeController.deploy) is what now ends a replica that never
# starts. The environment and not ``_system_config``: the latter reaches the
# driver alone (the raylet hands its workers its own values; PR 51)
CLUSTER_ENV = {"RAY_TPU_ACTOR_WAIT_ALIVE_TIMEOUT_S": "600"}
# an end-to-end metric so named is that percentile of the first-token times
# (a failed request counting as the worst) or of the pooled inter-token gaps
PERCENTILE_METRIC = re.compile(r"^(ttft|itl)_p(\d+)_ms$")


def _engine_delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in
            ("admitted", "finished", "failed", "steps", "tokens_out")}


class Deployed:
    """One replica behind the front door, warmed for one traffic mix."""

    def __init__(self, cell: dict, args: dict):
        self.cell, self.args = cell, args
        self.conf, self.traffic, self.toy = \
            cell["config"], cell["traffic"], cell["toy"]
        self.sv = dict(self.conf["serve"], **(
            self.traffic.get("toy_serve", {}) if self.toy else {}))
        self.cfg = harness.model_config(self.conf)
        self.n_new = int(self.traffic["new_tokens"])
        self.tok = replica.IdTokenizer()
        self.problems = []

    def __enter__(self):
        try:
            self._start()
        except BaseException:
            self.__exit__(None, None, None)
            raise
        return self

    def __exit__(self, *exc):
        import ray_tpu
        from ray_tpu import serve

        serve.shutdown()
        ray_tpu.shutdown()

    def _start(self):
        import ray_tpu
        from ray_tpu import serve
        from ray_tpu.llm import LLMConfig, SamplingParams

        cell, args, traffic, tok = self.cell, self.args, self.traffic, self.tok
        os.environ.update(CLUSTER_ENV)
        ray_tpu.init(num_tpus=1 if self.toy else None, log_to_driver=False)
        deadline = time.monotonic() + 20
        while not (resources := ray_tpu.cluster_resources()) and \
                time.monotonic() < deadline:
            time.sleep(0.05)  # init returns before the raylet has registered
        if resources.get("TPU", 0) < 1:
            raise RuntimeError(f"the node advertises no TPU ({resources}): a "
                               f"replica asking for one would wait forever")
        t0 = time.perf_counter()
        self.handle = handle = serve.run(replica.build_application(LLMConfig(
            model=self.cfg, max_len=self.sv["max_len"],
            cache_slots=self.sv["cache_slots"],
            continuous_batching=self.sv["continuous_batching"],
            resources=self.sv["resources"], tokenizer=tok, seed=args["seed"],
            sampling=SamplingParams(max_tokens=self.n_new)), self.conf))
        self.port = serve.start_http_proxy(port=0)
        self.replica_start_s = time.perf_counter() - t0
        device = handle.bench_device.remote().result()
        harness.require_chips(device, cell["chips"], self.toy)
        harness.say("serve", replica_start_s=round(self.replica_start_s, 2),
                    device=device, port=self.port)

        # warm every shape the traffic uses, and no other: one prompt per
        # prefill bucket through the handle with two new tokens (prefill,
        # install, decode and sampling compile), then one whole request
        # through the front door (the proxy resolves its handle)
        rng = np.random.default_rng(args["seed"] + 7)
        t0 = time.perf_counter()
        for n in traffic["warmup_prompt_tokens"]:
            prompt = tok.decode(rng.integers(0, self.cfg.vocab_size, n))
            pieces = [ray_tpu.get(r, timeout=WARMUP_TIMEOUT_S) for r in
                      handle.generate_stream.remote(prompt, max_tokens=2)]
            if len(tok.encode("".join(pieces))) != 2:
                self.problems.append(f"warm-up of {n} tokens gave {pieces}")
        rec = loadgen.one_request(
            self.port, traffic["path"], rng.integers(
                0, self.cfg.vocab_size, traffic["warmup_prompt_tokens"][0]),
            timeout_s=WARMUP_TIMEOUT_S)
        if rec.status != 200 or len(tok.encode(rec.text)) != self.n_new:
            self.problems.append(f"front-door warm-up: status {rec.status}, "
                                 f"{rec.error or rec.text[:200]}")
        self.warmup_s = time.perf_counter() - t0
        self.check = handle.bench_reference_check.remote(
            args["seed"] + 1, traffic["reference_prompt_tokens"],
            traffic["reference_new_tokens"]).result()
        if not self.check["ok"]:
            self.problems.append(
                f"the engine differs from the reference: {self.check}")
        self.stats_warm = handle.engine_stats.remote().result()
        harness.say("serve", warmup_s=round(self.warmup_s, 2),
                    compile_s=round(self.stats_warm["compile_s"], 2),
                    cache_hits=self.stats_warm["cache_hits"],
                    cache_misses=self.stats_warm["cache_misses"],
                    reference_check=self.check)

    def measure(self, traffic: dict, seed: int, seconds: float,
                trace: bool = False) -> dict:
        """Play one window of ``traffic``; counters are snapshotted when the
        window opens and closes, the profiler runs inside it."""
        from ray_tpu import serve

        handle, args = self.handle, self.args
        schedule = loadgen.make_schedule(traffic, seed, seconds,
                                         self.cfg.vocab_size)
        marks, failed = {}, []
        opened = threading.Event()
        trace_dir = os.path.join(args["out_dir"], "trace")

        def on_open():
            try:
                marks["open_wall"] = time.time()
                marks["engine_open"] = handle.engine_stats.remote().result()
                marks["proxy_open"] = serve.http_proxy_stats()
                if trace:
                    time.sleep(float(traffic.get("trace_after_s", 5.0)))
                    handle.bench_profile_start.remote(trace_dir).result()
                    time.sleep(float(traffic.get("trace_s", 5.0)))
                    marks["trace"] = handle.bench_profile_stop.remote(
                        trace_dir).result()
            except Exception as e:  # noqa: BLE001 — raised below, by measure
                failed.append(e)
            finally:
                opened.set()

        def on_close():
            marks["engine_close"] = handle.engine_stats.remote().result()
            marks["proxy_close"] = serve.http_proxy_stats()

        closer = threading.Timer(
            float(traffic.get("ramp_s", 0.0)) + seconds, on_close)
        closer.daemon = True
        closer.start()
        played = loadgen.play(self.port, traffic, schedule, seconds,
                              on_open=on_open)
        # no timed wait: a stop that returns late is late, and a trace
        # dropped for it made a line without busy_s (PR 31). The child's own
        # limit in run.py bounds both
        closer.join()
        opened.wait()
        if failed:
            raise RuntimeError(
                f"the window's opening failed (counters"
                f"{', the profiler' if trace else ''}): {failed[0]!r}"
            ) from failed[0]
        return account(self, traffic, schedule, played, marks)


def account(dep: Deployed, traffic: dict, schedule: dict, played: dict,
            marks: dict) -> dict:
    """From what the clients saw and the counters' deltas to the numbers of
    one window."""
    tok, n_new, vocab = dep.tok, dep.n_new, dep.cfg.vocab_size
    problems = []
    t_open, t_close = played["t_open"], played["t_close"]
    records = [r for r in played["records"]
               if r.index >= schedule["n_ramp"] and r.due < t_close]
    ok, ttft, gaps, tokens_in_window = [], [], [], 0
    answers = {}
    for r in played["records"]:
        tokens_in_window += sum(t_open <= t < t_close for t in r.chunk_times)
    for r in records:
        ids = []
        if r.status == 200 and r.error is None:
            try:
                ids = tok.encode(r.text)
            except ValueError:
                ids = []
        good = (len(ids) == n_new and len(r.chunk_times) == n_new
                and all(0 <= i < vocab for i in ids))
        ok.append(good)
        if good:
            ttft.append(r.chunk_times[0] - r.due)
            gaps.extend(np.diff(r.chunk_times).tolist())
            answers[r.index] = r.text
        elif len(problems) < 8:
            problems.append(f"request {r.index}: status {r.status}, "
                            f"{len(r.chunk_times)} chunks, "
                            f"{r.error or r.text[:300]}")
    n_failed = len(ok) - sum(ok)
    if traffic["loop"] == "closed" and \
            len(played["records"]) >= len(schedule["prompts"]):
        problems.append("the closed loop ran out of prompts: raise the "
                        "mix's max_requests_per_s")
    # a failed, shed or timed-out request counts as the worst
    ttft_all = ttft + [max(max(ttft, default=0.0), 60.0)] * n_failed
    same = [answers[i] for i in schedule["repeats"] if i in answers]
    if len(set(same)) > 1:
        problems.append("equal prompts, greedy, gave different answers")
    if traffic.get("repeat_every") and len(same) < 2:
        # answers decoded in different slots beside different neighbours:
        # the one check on what the batched decode step emits under load
        problems.append(f"{len(same)} answer(s) to the repeated prompt came "
                        f"back: equal prompts were not compared")
    window_s = t_close - t_open
    half = t_open + window_s / 2
    return {
        "problems": problems, "attempted": len(records), "failed": n_failed,
        "open_wall": marks["open_wall"], "trace": marks.get("trace", {}),
        "ttft_all_s": ttft_all,
        "out_tokens_per_s": tokens_in_window / window_s,
        "ttft_s": ttft, "gaps_s": gaps, "late_s": played["late_s"],
        # a growing backlog shows as a first token that comes later in the
        # second half of the window than in the first
        "ttft_mean_halves_s": [
            float(np.mean([r.chunk_times[0] - r.due for r in records
                           if r.chunk_times and (r.due < half) == first]
                          or [0.0])) for first in (True, False)],
        "engine": _engine_delta(marks["engine_close"], marks["engine_open"]),
        "proxy": {k: marks["proxy_close"].get(k, 0) - marks["proxy_open"].get(k, 0)
                  for k in ("requests", "ok", "shed", "deadline_exceeded")},
        # the pump's clocks, both processes' CPU seconds and the front
        # door's own work over the window; no end-to-end metric reads it
        "stream_path": stream_spans.window_counters(
            marks["engine_open"], marks["engine_close"],
            marks["proxy_open"], marks["proxy_close"], window_s),
        "window_s": window_s, "n_requests": len(records),
    }


def read_trace(stopped: dict, sample_to: str = "") -> dict:
    """From the file the replica's profiler wrote to the numbers of the
    traced window and the program's spans, loaded once for both. A file
    without a device operation is a failed run, not a line without
    ``busy_s``."""
    t0 = time.perf_counter()
    loaded = trace_reduce.load_xplane(
        trace_reduce.find_xplane(stopped["trace_dir"]))
    summary = trace_reduce.reduce_trace(loaded, sample_to)
    if not summary:
        raise RuntimeError(
            f"the trace under {stopped['trace_dir']} holds no device "
            f"operation: planes {sorted(loaded['devices'])}, "
            f"{len(loaded['program_spans'])} program spans")
    summary["program_spans"] = parsed = program_spans.from_trace(loaded)
    idle = program_spans.idle_by_span(parsed)
    if idle:
        # by the engine span the pump was in; trace_reduce's own gaps know
        # the benchmark's spans only, and a serve cell opens none
        summary["idle_gaps"] = sorted(
            ([n, s] for n, s in idle.items() if s > 0), key=lambda g: -g[1])
    harness.say("trace", stop_s=round(stopped["stop_s"], 2),
                reduce_s=round(time.perf_counter() - t0, 2),
                busy_s=summary["busy_s"], window_s=summary["window_s"],
                **program_spans.describe(parsed, idle))
    return summary


def run(cell: dict, args: dict) -> dict:
    from ray_tpu.accelerators.tpu import jax_backend_is_up

    with Deployed(cell, args) as dep:
        handle = dep.handle
        win = dep.measure(dep.traffic, args["seed"], args["seconds"],
                          trace=args["trace"])
        stats_end = handle.engine_stats.remote().result()
        device = handle.bench_device.remote().result()
        backend_up = jax_backend_is_up()
    stopped = win.pop("trace")
    trace = read_trace(stopped, args.get("sample_to", "")) \
        if args["trace"] else {}
    problems = dep.problems + win.pop("problems")
    if backend_up:
        problems.append("the driver (and its proxy) initialised a JAX backend")
    if stats_end["failed"]:
        problems.append(f"the engine counts {stats_end['failed']} failed")
    stats_warm = dep.stats_warm
    compiled_in_window = (stats_end["cache_hits"] + stats_end["cache_misses"]
                          - stats_warm["cache_hits"] - stats_warm["cache_misses"])
    if compiled_in_window:
        problems.append(f"{compiled_in_window} compilation(s) after warm-up")
    # the cell's percentile metrics, by name: ttft_p80_ms, itl_p99_ms, ...
    for m in cell["end_to_end"]:
        named = PERCENTILE_METRIC.match(m["name"])
        if named:
            values = win["ttft_all_s" if named[1] == "ttft" else "gaps_s"]
            win[m["name"]] = harness.percentile(values, int(named[2])) * 1e3 \
                if values else None
    if win["gaps_s"]:
        # where the tail's percentiles sit: a gap that holds a prefill is a
        # step plus that bucket's prefill, so the tail is a staircase
        harness.say("serve", n_gaps=len(win["gaps_s"]), itl_ms_at={
            str(q): round(harness.percentile(win["gaps_s"], q) * 1e3, 2)
            for q in (50, 90, 92, 93, 94, 95, 96, 97, 98, 99, 99.5, 99.9)})
    counters = dict(
        win, setup_s=win["open_wall"] - args["t0_wall"],
        replica_start_s=dep.replica_start_s, warmup_s=dep.warmup_s,
        compile_s=stats_warm["compile_s"], cache_hits=stats_warm["cache_hits"],
        cache_misses=stats_warm["cache_misses"],
        engine_whole_run=_engine_delta(stats_end, stats_warm),
        slots=dep.sv["cache_slots"], max_active=stats_end["max_active"],
        new_tokens=dep.n_new, reference_check=dep.check)
    return {
        "correct": not problems and win["failed"] == 0, "problems": problems,
        "attempted": win["attempted"], "failed": win["failed"],
        "device": {k: device[k] for k in
                   ("platform", "kind", "count", "memory_peak_bytes")},
        "counters": counters, "trace": trace,
    }
