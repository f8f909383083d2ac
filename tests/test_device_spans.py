"""Device spans: the serve engine's host loop, the worker's stream loop and
``observability.tracing.span`` on the clock of the JAX profiler's trace
(``tracing.device_span``), and the program's profiler hook
(``_private/profiling.py``) that takes such a trace.

One toy run of each engine (the scheduler over its slots, and over
`PagedBatcher`'s pages) under the hook is traced once for the module, with
one answer streamed through the replica's `text_deltas`; the
trace is read back with the benchmark's own reader
(``benchmarks/program_spans.py``), so the names the program writes and the
names the benchmark reads are held together here. CPU, no cluster.
"""

import os
import subprocess
import sys
import time

import pytest

import jax
import jax.numpy as jnp

from benchmarks import harness, program_spans, stream_spans
from ray_tpu._private import profiling, streaming
from ray_tpu._private.ids import TaskID
from ray_tpu._private.workers import default_worker
from ray_tpu.llm import serving
from ray_tpu.models import continuous_batching, transformer as T
from ray_tpu.models.continuous_batching import ContinuousBatcher
from ray_tpu.models.decoding import SamplingParams
from ray_tpu.models.paged_kv import PagedBatcher
from ray_tpu.observability import schema, tracing

SLOTS = 2
STREAMED_PROMPT = [7, 8, 9, 10]  # by its length its admit is told apart
ENGINE_CHILDREN_OF_STEP = (schema.ENGINE_DECODE_DISPATCH,
                           schema.ENGINE_SAMPLE_SYNC, schema.ENGINE_EMIT)
ENGINE_CHILDREN_OF_ADMIT = (schema.ENGINE_PREFILL_DISPATCH,
                            schema.ENGINE_INSTALL_DISPATCH,
                            schema.ENGINE_FIRST_TOKEN_SYNC)


class _AckingClient:
    """Stands for the caller's end of a stream: acknowledges every item of
    every call (`calls`: StreamingYield with its items' indices,
    StreamingDone with its count)."""

    def __init__(self):
        self.calls = []

    def call(self, method, **kwargs):
        if method == "StreamingYield":
            self.calls.append((method, [i[1] for i in kwargs["items"]]))
            return {i[0]: {"ok": True, "pending": 0}
                    for i in kwargs["items"]}
        self.calls.append((method, kwargs["count"]))
        return {"ok": True}


class _IdTokenizer:
    def decode(self, ids) -> str:
        return " ".join(map(str, ids)) + " "


class _NoWorker:
    def set_task_context(self, task_id, actor_id):
        pass


def _stream_two_items(client):
    """The worker's streaming loop itself, with the caller's end faked."""
    with pytest.MonkeyPatch.context() as m:
        m.setattr(default_worker.worker_mod, "global_worker", _NoWorker())
        m.setattr(streaming, "get_client", lambda addr: client)
        m.setattr(streaming, "_senders", {})
        reply = default_worker._execute_streaming(
            lambda: iter(["7 ", "8 "]), [], {}, TaskID.from_random(),
            "stream", ("127.0.0.1", 1))
    assert reply == {"returns": [], "streaming_done": 2}


@pytest.fixture(scope="module", params=[ContinuousBatcher, PagedBatcher],
                ids=["slots", "pages"])
def traced(request, tmp_path_factory):
    """{"parsed": the trace as program_spans reads it, "bridge": the context
    of a recorded span(), "stream": the fake stream client, "deltas": the
    text of the streamed answer, piece by piece}. Once for each
    kind of cache: the spans are the scheduler's, whatever keeps the rows."""
    cfg = T.config("debug", dtype=jnp.float32, param_dtype=jnp.float32)
    params = T.init_params(cfg, jax.random.key(0))
    cb = request.param(cfg, params, max_len=64, slots=SLOTS)
    sp = SamplingParams(max_tokens=6)
    logdir = str(tmp_path_factory.mktemp("xprof"))
    client = _AckingClient()
    patch = pytest.MonkeyPatch()
    # the pump books its clocks every few passes: a short trace of few
    # passes has to hold some bookings
    patch.setattr(continuous_batching, "BOOK_EVERY", 4)
    try:
        cb.submit([1, 2, 3], sp).result(timeout=120)  # compile outside
        profiling.start_tpu_profile(logdir)
        try:
            time.sleep(0.25)  # the pump has nothing: engine.idle
            # more requests than slots, at once: the later ones queue
            futs = [cb.submit([4 + i, 5, 6], sp) for i in range(3 * SLOTS)]
            for f in futs:
                f.result(timeout=120)
            _stream_two_items(client)
            deltas = list(serving.text_deltas(
                _IdTokenizer(), cb.submit_stream(STREAMED_PROMPT, sp)))
            tracing.configure(enabled=True, sample_rate=1.0)
            try:
                with tracing.span("ray_tpu.test.bridged") as bridge:
                    pass
            finally:
                tracing.configure(enabled=False)
            with tracing.span("ray_tpu.test.untraced") as untraced:
                pass
            assert untraced is None
        finally:
            # the pump has left its last step and closed its spans before
            # the trace ends: a span still open at the stop is not recorded,
            # and its children would stand alone
            cb.shutdown()
            path = profiling.stop_tpu_profile()
    finally:
        cb.shutdown()
        patch.undo()
    assert path.startswith(logdir) and path.endswith(".xplane.pb")
    return {"parsed": program_spans.parse(path), "bridge": bridge,
            "stream": client, "deltas": deltas}


def _queued_admits(parsed):
    """The admits of the requests that were submitted at once."""
    return [a for a in program_spans.named(parsed, schema.ENGINE_ADMIT)
            if a[4]["prompt_len"] != len(STREAMED_PROMPT)]


def _inside(child, parent):
    return parent[1] <= child[1] and \
        child[1] + child[2] <= parent[1] + parent[2] and child[3] == parent[3]


@pytest.mark.parametrize("name", sorted(schema.DEVICE_SPANS))
def test_every_site_is_in_the_trace_with_its_stats(traced, name):
    found = program_spans.named(traced["parsed"], name)
    assert found, f"no {name} in the trace"
    want = {s.split(" ")[0] for s in schema.DEVICE_SPANS[name].split(", ") if s}
    for span in found:
        assert set(span[4]) == want and span[2] >= 0


def test_names_are_the_ones_the_benchmark_reads():
    ours = set(schema.DEVICE_SPANS)
    read = {v for module in (program_spans, stream_spans)
            for k, v in vars(module).items()
            if k.isupper() and isinstance(v, str)
            and v.count(".") == 2 and not v.endswith(".")}
    assert read <= ours and program_spans.STEP == schema.ENGINE_STEP
    assert all(n.startswith(program_spans.PREFIX) for n in ours)
    assert {n for n in ours if not n.startswith(program_spans.ENGINE)} == {
        schema.REPLICA_DETOKENIZE, schema.WORKER_STREAM_YIELD,
        schema.WORKER_STREAM_RPC}
    assert stream_spans.DETOKENIZE == schema.REPLICA_DETOKENIZE
    assert stream_spans.STREAM_RPC == schema.WORKER_STREAM_RPC
    assert stream_spans.EMIT == schema.ENGINE_EMIT


def test_a_step_holds_its_children_on_the_pump_thread(traced):
    parsed = traced["parsed"]
    line = program_spans.pump_line(parsed)
    steps = program_spans.named(parsed, schema.ENGINE_STEP, line)
    assert steps == program_spans.named(parsed, schema.ENGINE_STEP)
    decoding = [s for s in steps if any(
        _inside(c, s) for c in program_spans.named(
            parsed, schema.ENGINE_DECODE_DISPATCH))]
    assert len(decoding) >= 6  # max_tokens
    for name in ENGINE_CHILDREN_OF_STEP:
        children = program_spans.named(parsed, name)
        assert all(any(_inside(c, s) for s in steps) for c in children), name
        assert len(children) == len(decoding)
    # numbered as the engine counts them, and every engine span is the pump's
    numbers = [s[4]["step"] for s in steps]
    assert numbers == sorted(numbers)
    assert {s[3] for s in parsed["spans"]
            if s[0].startswith(program_spans.ENGINE)} == {line}
    assert program_spans.named(parsed, schema.ENGINE_IDLE, line)


def test_a_dispatch_says_whether_it_went_ahead_of_the_read(traced):
    """One decode step is kept in flight: a dispatch is `ahead` when the step
    before it is still unread, and that step is then read in the same
    `engine.step`, AFTER the dispatch. A dispatch is not ahead only behind
    an admit (the loop is drained for one)."""
    parsed = traced["parsed"]
    steps = program_spans.named(parsed, schema.ENGINE_STEP)
    admits = program_spans.named(parsed, schema.ENGINE_ADMIT)
    syncs = program_spans.named(parsed, schema.ENGINE_SAMPLE_SYNC)
    dispatches = program_spans.named(parsed, schema.ENGINE_DECODE_DISPATCH)
    assert {d[4]["ahead"] for d in dispatches} == {0, 1}
    for dispatch in dispatches:
        step = next(s for s in steps if _inside(dispatch, s))
        read = [c for c in syncs if _inside(c, step)]
        if dispatch[4]["ahead"]:
            assert len(read) == 1 and read[0][1] >= dispatch[1] + dispatch[2]
        else:
            assert any(_inside(a, step) for a in admits)
            assert all(c[1] + c[2] <= dispatch[1] for c in read)
    # (3 waves of SLOTS requests, 6 tokens each: 5 steps a wave, 4 ahead)
    assert sum(d[4]["ahead"] for d in dispatches) >= \
        len(dispatches) - len(admits) > 0


def test_an_admit_holds_its_three_children_inside_a_step(traced):
    parsed = traced["parsed"]
    admits = _queued_admits(parsed)
    steps = program_spans.named(parsed, schema.ENGINE_STEP)
    assert len(admits) == 3 * SLOTS
    for admit in admits:
        assert any(_inside(admit, s) for s in steps)
        for name in ENGINE_CHILDREN_OF_ADMIT:
            inside = [c for c in program_spans.named(parsed, name)
                      if _inside(c, admit)]
            assert len(inside) == 1, name
        assert admit[4]["bucket"] == 16 and admit[4]["prompt_len"] == 3


def test_queued_ms_grows_when_the_slots_are_full(traced):
    admits = _queued_admits(traced["parsed"])
    waits = [a[4]["queued_ms"] for a in admits]  # in order of admission
    assert all(w >= 0 for w in waits)
    # the first SLOTS found a free slot; the last waited for whole requests
    assert min(waits[-SLOTS:]) > max(waits[:SLOTS])
    assert program_spans.stat_median(
        traced["parsed"], schema.ENGINE_ADMIT, "queued_ms") > 0


def test_the_readers_numbers_on_this_trace(traced):
    parsed = traced["parsed"]
    host = program_spans.host_ms_per_step(parsed)
    steps = program_spans.named(parsed, schema.ENGINE_STEP)
    longest = max(s[2] for s in steps) / 1e6
    assert 0 < host <= longest
    assert 0 < program_spans.step_period_ms(parsed) <= longest
    assert program_spans.mean_ms(parsed, schema.ENGINE_ADMIT) > 0
    idle = program_spans.idle_by_span(parsed)
    assert idle and set(idle) <= {
        n[len(program_spans.ENGINE):] for n in schema.DEVICE_SPANS} | {
        "unattributed"}
    assert 0 <= program_spans.idle_attributed_share(parsed) <= 100


def test_one_stream_yield_span_for_each_item(traced):
    spans = program_spans.named(traced["parsed"], schema.WORKER_STREAM_YIELD)
    assert len(spans) == 2 and spans[0][1] + spans[0][2] <= spans[1][1]
    # every item went once, in order, and the end behind the last of them
    calls = traced["stream"].calls
    assert calls[-1] == ("StreamingDone", 2)
    assert all(method == "StreamingYield" for method, _ in calls[:-1])
    assert [i for _, indices in calls[:-1] for i in indices] == [0, 1]


def test_a_stream_rpc_is_one_call_of_the_senders_thread(traced):
    parsed = traced["parsed"]
    assert schema.DEVICE_SPANS[schema.WORKER_STREAM_RPC] == "items, bytes"
    yields = program_spans.named(parsed, schema.WORKER_STREAM_YIELD)
    rpcs = program_spans.named(parsed, schema.WORKER_STREAM_RPC)
    sent = [indices for method, indices in traced["stream"].calls
            if method == "StreamingYield"]
    # one span a CALL with the items it carried, not on a yielding thread
    assert [r[4]["items"] for r in rpcs] == [len(i) for i in sent]
    assert all(r[4]["bytes"] > 0 for r in rpcs)
    assert {r[3] for r in rpcs}.isdisjoint({y[3] for y in yields})
    # a yield serialises and hands over; no call starts before its first item
    assert rpcs[0][1] >= yields[0][1]
    ctx = {"cell": {}, "counters": {}, "device": {},
           "trace": {"program_spans": parsed}}
    read = harness.load_reader("tput_stream_items_per_call").read
    assert read(ctx) == pytest.approx(2 / len(sent))
    # a trace from before the calls carried a count: nothing to read
    before = dict(parsed, spans=[
        s[:4] + [{"bytes": s[4]["bytes"]}]
        if s[0] == schema.WORKER_STREAM_RPC else s for s in parsed["spans"]])
    assert read(dict(ctx, trace={"program_spans": before})) is None
    assert read(dict(ctx, trace={})) is None
    sample = program_spans.load_sample(os.path.join(
        harness.HERE, "tests", "data", "serve_chat_spans_sample.json.gz"))
    assert program_spans.named(sample, schema.WORKER_STREAM_YIELD)
    assert read(dict(ctx, trace={"program_spans": sample})) is None


def test_one_detokenize_span_a_token_with_ids_growing_by_one(traced):
    parsed = traced["parsed"]
    spans = program_spans.named(parsed, schema.REPLICA_DETOKENIZE)
    assert traced["deltas"] and len(spans) == len(traced["deltas"]) == 6
    assert [s[4]["ids"] for s in spans] == [1, 2, 3, 4, 5, 6]
    assert all(s[4]["backlog"] >= 0 for s in spans)
    # on the thread that asked, not on the pump's
    assert {s[3] for s in spans}.isdisjoint({program_spans.pump_line(parsed)})
    # each id was emitted by the pump before its handler decoded it
    handoffs = stream_spans.handoffs_ms(parsed)
    assert handoffs and all(h >= 0 for h in handoffs)
    assert len(handoffs) == sum(s[4]["backlog"] == 0 for s in spans[1:])
    assert stream_spans.handoff_ms_p50(parsed) >= 0


def test_a_detokenize_span_says_how_few_ids_its_turn_decoded(traced):
    """`decoded`, the ids a turn hands to `decode`, is the counter that says
    the window engaged: the first id alone, then the id before (twice: once
    to be subtracted) and the new one, while `ids` counts the whole answer."""
    stats = [s[4] for s in program_spans.named(
        traced["parsed"], schema.REPLICA_DETOKENIZE)]
    assert [s["decoded"] for s in stats] == [1, 3, 3, 3, 3, 3]
    assert stats[-1]["ids"] == 6 > stats[-1]["decoded"]


def test_a_trace_holds_the_pumps_counters_of_its_own_window(traced):
    """`engine.step` carries the pump's clocks as last booked: what two
    bookings in a trace say between them is what `ContinuousBatcher.stats`
    says over the same passes."""
    parsed = traced["parsed"]
    steps = program_spans.named(parsed, schema.ENGINE_STEP)
    for key in ("pump_step_s", "pump_sync_s", "pump_cpu_s"):
        values = [s[4][key] for s in steps]
        assert values == sorted(values) and values[-1] > values[0] >= 0, key
    c = stream_spans.traced_counters(parsed)
    assert 0 < c["steps"] <= steps[-1][4]["step"] - steps[0][4]["step"]
    assert 0 <= c["pump_sync_s"] <= c["pump_step_s"] <= c["window_s"]
    assert 0 <= c["pump_cpu_s"] <= c["pump_step_s"]
    assert stream_spans.pump_cpu_ms_per_step(c) > 0
    wait = stream_spans.pump_wait_ms_per_step(c)
    assert wait + stream_spans.pump_cpu_ms_per_step(c) == pytest.approx(
        (c["pump_step_s"] - c["pump_sync_s"]) / c["steps"] * 1e3)
    share = stream_spans.idle_stream_work_share(parsed)
    assert share is None or 0 <= share["idle"] <= 100


def test_span_joins_the_device_trace_only_when_it_records(traced):
    parsed, ctx = traced["parsed"], traced["bridge"]
    assert ctx is not None and ctx.sampled
    bridged = program_spans.named(parsed, "ray_tpu.test.bridged")
    assert [s[4] for s in bridged] == [
        {"trace_id": ctx.trace_id, "span_id": ctx.span_id}]
    # with tracing off span() returns before it opens anything
    assert program_spans.named(parsed, "ray_tpu.test.untraced") == []


def test_a_failed_start_leaves_no_session(tmp_path, monkeypatch):
    def refuse(*args, **kwargs):
        raise RuntimeError("the profiler refused to start")

    with monkeypatch.context() as m:
        m.setattr(jax.profiler, "start_trace", refuse)
        with pytest.raises(RuntimeError, match="refused"):
            profiling.start_tpu_profile(str(tmp_path / "a"))
    assert profiling.stop_tpu_profile() == ""  # nothing to stop
    with profiling.tpu_profile(str(tmp_path / "b")):
        (jnp.ones((8, 8)) @ jnp.ones((8, 8))).block_until_ready()
    assert profiling._Profile._xplanes(str(tmp_path / "b"))


def test_a_failed_stop_is_not_left_running(tmp_path, monkeypatch):
    real_stop = jax.profiler.stop_trace

    def fail():
        raise RuntimeError("export failed")

    profiling.start_tpu_profile(str(tmp_path / "a"))
    with pytest.raises(RuntimeError, match="already running"):
        profiling.start_tpu_profile(str(tmp_path / "a2"))
    with monkeypatch.context() as m:
        m.setattr(jax.profiler, "stop_trace", fail)
        with pytest.raises(RuntimeError, match="export failed"):
            profiling.stop_tpu_profile()
    with pytest.raises(RuntimeError, match="No profile started"):
        real_stop()  # JAX holds no session either
    profiling.start_tpu_profile(str(tmp_path / "b"))
    assert profiling.stop_tpu_profile().startswith(str(tmp_path / "b"))


def test_a_profile_that_writes_nothing_raises(tmp_path, monkeypatch):
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    profiling.start_tpu_profile(str(tmp_path))
    with pytest.raises(profiling.ProfileError, match="no .xplane.pb"):
        profiling.stop_tpu_profile()
    assert profiling.stop_tpu_profile() == ""


def test_the_driver_side_never_imports_jax():
    """A process that has not loaded JAX (the driver, the proxy) opens spans
    of both kinds without loading it."""
    code = (
        "import sys\n"
        "import ray_tpu\n"
        "from ray_tpu.observability import tracing\n"
        "tracing.configure(enabled=True)\n"
        "with tracing.span('driver.side') as ctx:\n"
        "    assert ctx is not None\n"
        "with tracing.device_span('ray_tpu.engine.step', step=1):\n"
        "    pass\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        p for p in (os.path.dirname(os.path.dirname(os.path.abspath(
            __file__))), os.environ.get("PYTHONPATH")) if p))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
