"""A streamed token's way from the pump to the front door, stage by stage:
arithmetic over a parsed trace (``program_spans.from_trace``'s structure) and
over the stream path's counters.

The way (``ray_tpu/observability/schema.py``): the pump's ``engine.emit``
puts a step's ids into their streams' queues; each stream's handler thread
takes its id and decodes a window of the answer's last ids (since PR 57:
``llm/serving.text_deltas``, not the whole answer again a token;
``replica.detokenize`` [ids, decoded, backlog]); the worker's stream loop sends what is new as one generator item
(``worker.stream_yield``), whose ``worker.stream_rpc`` child is the blocking
call to the caller: the part of a yield in which the handler thread holds no
GIL. The pump's own clocks (``ContinuousBatcher.stats``: ``pump_step_s``,
``pump_sync_s``, ``pump_cpu_s``) ride on every ``engine.step`` span as last
booked, so two bookings in a trace give the counters of the time between
them (``traced_counters``); ``window_counters`` gives the same,
and both processes' CPU seconds and the front door's own, from the snapshots
a runner takes when a window opens and closes (``stream_counters.py``: the
numbers with the profiler off).

Pure functions, None where the trace or the snapshots have no such span or
key: a checkout of the program from before these spans. The names are
repeated here for that reason; ``tests/test_device_spans.py`` holds them to
``schema.py``'s.
"""

from __future__ import annotations

import bisect
import statistics

from benchmarks import program_spans, trace_reduce

DETOKENIZE = "ray_tpu.replica.detokenize"
STREAM_RPC = "ray_tpu.worker.stream_rpc"
EMIT = "ray_tpu.engine.emit"
PUMP_CLOCKS = ("pump_step_s", "pump_sync_s", "pump_cpu_s")


def handoffs_ms(parsed: dict) -> list:
    """Per ``replica.detokenize`` span that found nothing more waiting
    (``backlog`` 0, so the id it decodes is the newest of its stream): its
    start less the start of the pump's latest ``engine.emit`` before it. How
    long an id that exists waits for its handler thread to run. A stream's
    first id comes out of its admit, not out of an emit, and is left out."""
    emits = [s[1] for s in program_spans.named(
        parsed, EMIT, program_spans.pump_line(parsed))]
    out = []
    for s in program_spans.named(parsed, DETOKENIZE):
        i = bisect.bisect_right(emits, s[1]) - 1
        if i >= 0 and s[4].get("backlog") == 0 and s[4].get("ids", 0) > 1:
            out.append((s[1] - emits[i]) / 1e6)
    return out


def handoff_ms_p50(parsed: dict):
    found = handoffs_ms(parsed)
    return statistics.median(found) if found else None


def stream_work(parsed: dict) -> list:
    """Merged intervals in which at least one handler thread does the stream
    path's own work under the GIL: inside a ``replica.detokenize``, or inside
    a ``worker.stream_yield`` but outside its ``stream_rpc`` child."""
    by_line = {}
    for s in parsed["spans"]:
        if s[0] in (DETOKENIZE, program_spans.STREAM_YIELD, STREAM_RPC):
            by_line.setdefault(s[3], {}).setdefault(s[0], []).append(
                [s[1], s[1] + s[2]])
    work = []
    for spans in by_line.values():
        work += spans.get(DETOKENIZE, [])
        work += trace_reduce.subtract(
            trace_reduce.union(spans.get(program_spans.STREAM_YIELD, [])),
            trace_reduce.union(spans.get(STREAM_RPC, [])))
    return trace_reduce.union(work)


def _covered(intervals: list, by: list) -> float:
    """Length of ``intervals`` (merged) that ``by`` (merged) covers."""
    return trace_reduce.length(intervals) - trace_reduce.length(
        trace_reduce.subtract(intervals, by))


def idle_stream_work_share(parsed: dict):
    """{"idle": of the device's idle time in the traced window, the percent
    during which ``stream_work`` goes on; "window": the same of the whole
    traced window, the baseline the first has to be read against}. None
    without a device operation or where no yield has its call told apart."""
    if not parsed["busy"] or not program_spans.named(parsed, STREAM_RPC):
        return None  # all of a yield would read as work
    work = stream_work(parsed)
    idle = covered = window = window_covered = 0.0
    for device, busy in parsed["busy"].items():
        gaps = [[a, b] for (_, a), (b, _) in zip(busy, busy[1:]) if b > a]
        idle += trace_reduce.length(gaps)
        covered += _covered(gaps, work)
        window += parsed["window"][device][1] - parsed["window"][device][0]
        window_covered += _covered([list(parsed["window"][device])], work)
    if not idle or not window:
        return None
    return {"idle": 100.0 * covered / idle,
            "window": 100.0 * window_covered / window}


def traced_counters(parsed: dict):
    """The pump's clocks and the decode steps they cover between two of the
    pump's bookings, the first and the last the trace shows, and the seconds
    between the two. The pump books its clocks every few passes
    (``ContinuousBatcher._book``): the first ``engine.step`` after a booking
    shows new clocks, and its ``step`` is the count they cover. None where
    the steps carry no clocks or the trace holds fewer than two bookings."""
    steps = [s for s in program_spans.named(
        parsed, program_spans.STEP, program_spans.pump_line(parsed))
        if all(k in s[4] for k in PUMP_CLOCKS)]
    booked = [b for a, b in zip(steps, steps[1:])
              if b[4]["pump_step_s"] != a[4]["pump_step_s"]]
    if len(booked) < 2 or booked[-1][4]["step"] == booked[0][4]["step"]:
        return None
    first, last = booked[0], booked[-1]
    out = {k: last[4][k] - first[4][k] for k in PUMP_CLOCKS}
    out.update(steps=last[4]["step"] - first[4]["step"],
               window_s=(last[1] - first[1]) / 1e9)
    return out


def window_counters(engine_open: dict, engine_close: dict, proxy_open: dict,
                    proxy_close: dict, window_s: float):
    """The same from ``engine_stats`` and ``http_proxy_stats`` as a runner
    snapshots them at a window's two ends, with the two processes' CPU
    seconds and the front door's own counters; None where the program keeps
    no such counter."""
    if not all(k in engine_close for k in PUMP_CLOCKS) or \
            "stream_forward_s" not in proxy_close:
        return None
    out = {k: engine_close[k] - engine_open[k]
           for k in PUMP_CLOCKS + ("steps",)}
    out.update(
        replica_cpu_s=engine_close["process_cpu_s"] - engine_open["process_cpu_s"],
        frontdoor_cpu_s=proxy_close["process_cpu_s"] - proxy_open["process_cpu_s"],
        stream_items=proxy_close["stream_items"] - proxy_open["stream_items"],
        stream_forward_s=proxy_close["stream_forward_s"]
        - proxy_open["stream_forward_s"],
        window_s=window_s)
    return out


def pump_cpu_ms_per_step(c: dict):
    """What the pump thread's own Python costs a decode step."""
    return 1e3 * c["pump_cpu_s"] / c["steps"] if c and c["steps"] else None


def pump_wait_ms_per_step(c: dict):
    """The pump ready and not running: its passes' wall time less the waits
    for the device less its own CPU time, a decode step."""
    if not c or not c["steps"]:
        return None
    return 1e3 * (c["pump_step_s"] - c["pump_sync_s"] - c["pump_cpu_s"]) \
        / c["steps"]


def cpu_share(cpu_s: float, window_s: float):
    """Percent of ONE core: 100 in a Python process is a saturated GIL."""
    return 100.0 * cpu_s / window_s if window_s else None


def proxy_forward_ms_per_item(c: dict):
    if not c or not c.get("stream_items"):
        return None
    return 1e3 * c["stream_forward_s"] / c["stream_items"]


def read_counter(ctx, fn):
    """``fn(traced_counters)`` of this run's trace; None without one."""
    c = program_spans.read(ctx, traced_counters)
    return fn(c) if c else None
