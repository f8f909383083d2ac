"""The program's own spans in a run's profiler trace, and the device's idle
time by the span that covers it.

The serve engine, the replica and the worker's stream loop open
``jax.profiler.TraceAnnotation``s named ``ray_tpu.<layer>.<site>``
(``ray_tpu/observability/schema.py`` has the vocabulary; the names the
readers need are repeated here, because this file also runs over a checkout
of the program that has no such span, and a test holds the two together).
They land on the host plane of the same ``.xplane.pb`` as the device's
operations, on the same clock. ``trace_reduce`` reads only the benchmark's
own ``bench.`` spans; this file reads the program's, with their stats and
the thread (line of the host plane) they were opened on.

Two stages, like ``trace_reduce``, whose ``load_xplane`` reads the file once
for both: ``from_trace`` takes the spans and the device's busy intervals out
of what it loaded, and everything else is arithmetic on that structure,
checked on a recorded sample under ``tests/data/``. Every number
is None where the trace has no such span (the parent of the PR that added
the spans, a cell without an engine), so the harness leaves the metric out.
"""

from __future__ import annotations

import bisect
import gzip
import json
import statistics
import sys
from collections import defaultdict

from benchmarks import trace_reduce

PREFIX = trace_reduce.PROGRAM_SPAN_PREFIX
ENGINE = "ray_tpu.engine."
IDLE = ENGINE + "idle"
STEP = ENGINE + "step"
ADMIT = ENGINE + "admit"
FIRST_TOKEN_SYNC = ENGINE + "first_token_sync"
DECODE_DISPATCH = ENGINE + "decode_dispatch"
SAMPLE_SYNC = ENGINE + "sample_sync"
STREAM_YIELD = "ray_tpu.worker.stream_yield"


def parse(path: str) -> dict:
    """``from_trace`` of an ``.xplane.pb``."""
    return from_trace(trace_reduce.load_xplane(path))


def from_trace(trace: dict) -> dict:
    """{"spans": [[name, start_ns, dur_ns, line, {stat: value}]],
    "busy": {device: [[start, end]]}, "window": {device: [start, end]}} of
    a trace as ``trace_reduce.load_xplane`` loaded it."""
    spans = sorted(trace["program_spans"], key=lambda s: (s[1], -s[2]))
    return dict(device_intervals(trace), spans=spans)


def device_intervals(trace: dict) -> dict:
    """Busy intervals and traced window per device, as ``trace_reduce.
    summarize`` takes them: an operation that only holds others (a
    ``while``) is not work, and the window runs from the first operation's
    start to the last one's end."""
    busy, window = {}, {}
    for name, dev in trace["devices"].items():
        ops = [o for o in dev["ops"] if o[2] > 0]
        if not ops:
            continue
        busy[name] = trace_reduce.union(
            [o[1], o[1] + o[2]] for o in ops
            if not o[0].startswith(trace_reduce.CONTROL_PREFIXES))
        window[name] = [min(o[1] for o in ops), max(o[1] + o[2] for o in ops)]
    return {"busy": busy, "window": window}


def save_sample(parsed: dict, path: str, seconds: float = 1.0) -> None:
    """The first ``seconds`` of a parsed trace, small enough to keep."""
    t0 = min((w[0] for w in parsed["window"].values()), default=0)
    end = t0 + seconds * 1e9
    out = {"spans": [s for s in parsed["spans"] if s[1] + s[2] <= end],
           "busy": {d: [i for i in iv if i[1] <= end]
                    for d, iv in parsed["busy"].items()},
           "window": {d: [w[0], min(w[1], end)]
                      for d, w in parsed["window"].items()}}
    with gzip.open(path, "wt") as f:
        json.dump(out, f, separators=(",", ":"))


def load_sample(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def load(ctx) -> dict | None:
    """The parsed trace of this run, which its runner left in the reduced
    trace under ``program_spans``, or None where the run has none."""
    return (ctx.get("trace") or {}).get("program_spans")


def describe(parsed: dict, idle: dict | None) -> dict:
    """What a traced run says of its spans on stderr: how many, the idle
    seconds by span (``idle_by_span``'s), and the step period."""
    periods = step_periods_ms(parsed)
    return dict(
        n_spans=len(parsed["spans"]),
        idle_s_by_span={k: round(v, 4) for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])} if idle else None,
        step_period_ms={"n": len(periods),
                        "median": round(statistics.median(periods), 3),
                        "mean": round(statistics.fmean(periods), 3)}
        if periods else None)


def named(parsed: dict, name: str, line=None) -> list:
    return [s for s in parsed["spans"]
            if s[0] == name and (line is None or s[3] == line)]


def pump_line(parsed: dict):
    """The line that carries the engine's steps: its pump thread."""
    lines = [s[3] for s in named(parsed, STEP)]
    return statistics.mode(lines) if lines else None


def innermost_segments(spans) -> list:
    """Disjoint ``[start, end, name]`` pieces of one thread's time, each
    named by the innermost span open in it. Spans of one thread nest."""
    out, stack, cur = [], [], 0  # stack of [name, end]

    def close_until(t):
        nonlocal cur
        while stack and stack[-1][1] <= t:
            name, end = stack.pop()
            if end > cur:
                out.append([cur, end, name])
                cur = end

    for name, start, dur, *_ in sorted(spans, key=lambda s: (s[1], -s[2])):
        close_until(start)
        if stack and start > cur:
            out.append([cur, start, stack[-1][0]])
        cur = max(cur, start)
        stack.append([name, start + dur])
    close_until(float("inf"))
    return out


def idle_by_span(parsed: dict) -> dict | None:
    """Seconds of device idle time inside the traced window, by the engine
    span that covers them: each gap between busy intervals is split among
    the innermost ``ray_tpu.engine.*`` spans of the pump thread that overlap
    it, and what none covers is ``unattributed``. None without a device
    operation or without an engine span."""
    line = pump_line(parsed)
    if line is None or not parsed["busy"]:
        return None
    segments = innermost_segments(
        s for s in parsed["spans"] if s[3] == line and s[0].startswith(ENGINE))
    starts = [s[0] for s in segments]
    out = defaultdict(float)
    n_dev = len(parsed["busy"])
    for busy in parsed["busy"].values():
        for (_, a), (b, _) in zip(busy, busy[1:]):
            covered = 0.0
            i = max(bisect.bisect_right(starts, a) - 1, 0)
            while i < len(segments) and segments[i][0] < b:
                part = min(b, segments[i][1]) - max(a, segments[i][0])
                if part > 0:
                    out[segments[i][2][len(ENGINE):]] += part / 1e9 / n_dev
                    covered += part
                i += 1
            out["unattributed"] += (b - a - covered) / 1e9 / n_dev
    return dict(out)


def idle_attributed_share(parsed: dict):
    idle = idle_by_span(parsed)
    total = sum(idle.values()) if idle else 0.0
    if not total:
        return None
    return 100.0 * (total - idle["unattributed"]) / total


def decode_steps(parsed: dict) -> list:
    """The pump thread's steps that reached the decode dispatch, each with
    the durations of its two host syncs: [start, dur, sync_ns]."""
    line = pump_line(parsed)
    if line is None:
        return []
    steps = named(parsed, STEP, line)
    starts = [s[1] for s in steps]
    inside = defaultdict(lambda: [False, 0.0])
    for name in (DECODE_DISPATCH, SAMPLE_SYNC, FIRST_TOKEN_SYNC):
        for s in named(parsed, name, line):
            i = bisect.bisect_right(starts, s[1]) - 1
            if i >= 0 and s[1] < steps[i][1] + steps[i][2]:
                if name == DECODE_DISPATCH:
                    inside[i][0] = True
                else:
                    inside[i][1] += s[2]
    return [[steps[i][1], steps[i][2], inside[i][1]]
            for i in range(len(steps)) if inside[i][0]]


def step_periods_ms(parsed: dict) -> list:
    """Times between the starts of consecutive decode steps with no
    ``engine.idle`` between them: the loop's period while it has work."""
    line = pump_line(parsed)
    steps = decode_steps(parsed)
    all_starts = [s[1] for s in named(parsed, STEP, line)]
    idles = [s[1] for s in named(parsed, IDLE, line)]
    periods = []
    for (a, *_), (b, *_) in zip(steps, steps[1:]):
        consecutive = bisect.bisect_right(all_starts, a) == \
            bisect.bisect_left(all_starts, b)
        if consecutive and bisect.bisect_left(idles, a) == \
                bisect.bisect_left(idles, b):
            periods.append((b - a) / 1e6)
    return periods


def step_period_ms(parsed: dict):
    """The median period: a step that admits nobody."""
    periods = step_periods_ms(parsed)
    return statistics.median(periods) if periods else None


def host_ms_per_step(parsed: dict):
    """Median over decode steps of the step less its two host syncs (the
    waits for the device): what the host does itself each step."""
    steps = decode_steps(parsed)
    if not steps:
        return None
    return statistics.median((dur - sync) / 1e6 for _, dur, sync in steps)


def mean_ms(parsed: dict, name: str):
    found = named(parsed, name)
    return statistics.fmean(s[2] for s in found) / 1e6 if found else None


def stat_median(parsed: dict, name: str, stat: str):
    values = [s[4][stat] for s in named(parsed, name) if stat in s[4]]
    return statistics.median(values) if values else None


def read(ctx, fn, *args):
    """``fn(parsed, *args)`` on this run's trace; None without one."""
    parsed = load(ctx)
    return fn(parsed, *args) if parsed else None


if __name__ == "__main__":  # python -m benchmarks.program_spans <trace dir> <sample.json.gz>
    save_sample(parse(trace_reduce.find_xplane(sys.argv[1])), sys.argv[2])
