"""From a profiler trace (``.xplane.pb``) to numbers.

Two stages, so that the arithmetic can be checked on a small recorded trace
(``tests/data/``) with nothing but Python:

1. ``load_xplane`` reads the file with ``jax.profiler.ProfileData`` into a
   plain structure: per device the operations of the ``XLA Ops`` line and the
   programs of the ``XLA Modules`` line, the benchmark's own host spans
   (``jax.profiler.TraceAnnotation`` names that start with ``bench.``) and
   the program's (``ray_tpu.``, for ``program_spans.py``: one pass over the
   file serves both). Times are nanoseconds on the trace's clock.
2. ``summarize`` reduces that structure: busy time as the union of the
   intervals in which an operation ran, self time per operation (a ``while``
   does not count its body twice), idle gaps named by the host span that
   covers them, time per program, collective time.

On a CPU (``--toy``) the XLA operations are events of host threads that carry
an ``hlo_op`` stat; they are read as one device named ``cpu`` so that the toy
run drives the same code. Such a summary is never a device number.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
from collections import defaultdict

SPAN_PREFIX = "bench."
PROGRAM_SPAN_PREFIX = "ray_tpu."
COLLECTIVE_PREFIXES = ("all-reduce", "all-gather", "reduce-scatter",
                       "all-to-all", "collective-permute",
                       "collective-broadcast")
# parents whose duration is their children's: never leaf work themselves
CONTROL_PREFIXES = ("while", "conditional", "call")


def _xplanes(trace_dir: str) -> list:
    return glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))


def has_xplane(trace_dir: str) -> bool:
    return bool(_xplanes(trace_dir))


def find_xplane(trace_dir: str) -> str:
    paths = _xplanes(trace_dir)
    if not paths:
        seen = [os.path.join(r, f) for r, _d, fs in os.walk(trace_dir)
                for f in fs]
        raise FileNotFoundError(
            f"no .xplane.pb under {trace_dir}; it holds {seen[:20]}")
    return max(paths, key=os.path.getmtime)


def short_name(text: str) -> str:
    """``%fusion.616 = bf16[4,2048,14336]{...} fusion(...)`` (the device
    plane names an operation by its whole HLO line) -> ``fusion.616``."""
    return text.split(" = ", 1)[0].lstrip("%")


def op_tag(text: str) -> str:
    """What the reduction needs to know of an operation beyond its name. A
    Pallas kernel is a ``tpu_custom_call`` whose HLO name says nothing of
    the kernel (``closed_call.9``, ``checkpoint.21``: ops/attention.py gives
    its pallas_calls no name), so it is tagged with its operand and result
    counts: ``tpu_custom_call/3in/2out``. Other operations carry their
    result type, for a breakdown a reader can follow."""
    if 'custom_call_target="tpu_custom_call"' in text:
        operands = text.split("operand_layout_constraints={", 1)[-1].split("}}", 1)[0]
        n_in = operands.count("[")
        result = text.split(" = ", 1)[-1].split(" custom-call(", 1)[0]
        return f"tpu_custom_call/{n_in}in/{result.count('[')}out"
    if " = " not in text:
        return ""
    return text.split(" = ", 1)[1].split("{", 1)[0].split(" ", 1)[0].lstrip("(")


def load_xplane(path: str) -> dict:
    """{"devices": {name: {"ops": [[name, start, dur, tag]],
    "programs": [[name, start, dur]], "async": [[name, start, dur]],
    "other_lines": {line: n_events}}}, "spans": [[name, start, dur]],
    "program_spans": [[name, start, dur, line, {stat: value}]]}. ``line``
    numbers the host planes' lines: one per thread, and the only identity a
    thread has in the trace (every Python thread's line is named
    ``python``)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans, program_spans, cpu_ops, line_no = {}, [], [], [], 0
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"ops": [], "programs": [], "async": [], "other_lines": {}}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev["ops"] = [[short_name(e.name), e.start_ns,
                                   e.duration_ns, op_tag(e.name)]
                                  for e in line.events]
                elif line.name == "XLA Modules":
                    dev["programs"] = [[e.name, e.start_ns, e.duration_ns]
                                       for e in line.events]
                else:
                    events = list(line.events)
                    dev["other_lines"][line.name] = len(events)
                    dev["async"].extend(
                        [short_name(e.name), e.start_ns, e.duration_ns]
                        for e in events
                        if short_name(e.name).startswith(COLLECTIVE_PREFIXES))
            devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                line_no += 1
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append([e.name, e.start_ns, e.duration_ns])
                    elif e.name.startswith(PROGRAM_SPAN_PREFIX):
                        program_spans.append(
                            [e.name, e.start_ns, e.duration_ns, line_no,
                             {k: v for k, v in e.stats}])
                    elif line.name.startswith("tf_XLA") and e.duration_ns > 0 \
                            and any(k == "hlo_op" for k, _ in e.stats):
                        cpu_ops.append([e.name, e.start_ns, e.duration_ns, ""])
    if not devices and cpu_ops:
        devices["cpu"] = {"ops": sorted(cpu_ops, key=lambda o: o[1]),
                          "programs": [], "async": [], "other_lines": {}}
    spans.sort(key=lambda s: s[1])
    return {"devices": devices, "spans": spans,
            "program_spans": program_spans}


def save_sample(trace: dict, path: str, max_ops: int = 4000) -> None:
    """A cut of a loaded trace small enough to keep in the repository."""
    out = {"devices": {}, "spans": trace["spans"][:400]}
    for name, dev in trace["devices"].items():
        ops = dev["ops"][:max_ops]
        end = ops[-1][1] + ops[-1][2] if ops else 0
        out["devices"][name] = {
            "ops": ops,
            "programs": [p for p in dev["programs"] if p[1] + p[2] <= end],
            "async": [a for a in dev["async"] if a[1] + a[2] <= end],
            "other_lines": dev["other_lines"]}
    with gzip.open(path, "wt") as f:
        json.dump(out, f, separators=(",", ":"))


def load_sample(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def union(intervals):
    """Merged, sorted [start, end) intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def length(merged) -> float:
    return float(sum(end - start for start, end in merged))


def subtract(merged_a, merged_b):
    """The part of ``merged_a`` that no interval of ``merged_b`` covers."""
    out, j = [], 0
    for start, end in merged_a:
        cur = start
        while j < len(merged_b) and merged_b[j][1] <= cur:
            j += 1
        k = j
        while k < len(merged_b) and merged_b[k][0] < end:
            if merged_b[k][0] > cur:
                out.append([cur, merged_b[k][0]])
            cur = max(cur, merged_b[k][1])
            k += 1
        if cur < end:
            out.append([cur, end])
    return out


def op_kind(name: str) -> str:
    """``fusion.123`` -> ``fusion``: the operation without its number."""
    return re.sub(r"[.\-_]?\d+$", "", name.split(" ")[0]) or name


def self_times(ops) -> dict:
    """Seconds per operation name, children subtracted from their parents
    (events of one line nest, they do not cross)."""
    total = defaultdict(float)
    stack = []  # [end, name, self_ns]
    for name, start, dur, _prog in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][0] <= start:
            end, n, s = stack.pop()
            total[n] += s
        if stack:
            stack[-1][2] -= dur
        stack.append([start + dur, name, dur])
    for end, n, s in stack:
        total[n] += s
    return {n: max(s, 0.0) / 1e9 for n, s in total.items()}


def _span_at(spans, start, end):
    """The innermost (shortest) benchmark span that covers at least half of
    the gap [start, end)."""
    best, best_dur = None, None
    for name, s, d in spans:
        if s >= end:
            break
        cover = min(end, s + d) - max(start, s)
        if cover >= 0.5 * (end - start) and (best is None or d < best_dur):
            best, best_dur = name, d
    return "unattributed" if best is None else best[len(SPAN_PREFIX):]


def program_name(name: str) -> str:
    """``jit__decode_impl(1234)`` -> ``_decode_impl``."""
    name = re.sub(r"\(\d+\)$", "", name)
    return name[4:] if name.startswith("jit_") else name


def _with_program(ops, programs):
    """Operations renamed ``<program>/<operation>``: two programs both have
    a ``fusion.163``, and they are not the same work."""
    if not programs:
        return ops
    programs = sorted(programs, key=lambda p: p[1])
    starts = [p[1] for p in programs]
    out = []
    for name, start, dur, tag in ops:
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start < programs[i][1] + programs[i][2]:
            name = f"{program_name(programs[i][0])}/{name}"
        out.append([name, start, dur, tag])
    return out


def summarize(trace: dict, top: int = 10) -> dict:
    """Numbers of one traced window. Seconds throughout."""
    per_device, op_self, programs = {}, defaultdict(float), defaultdict(list)
    tags = {}
    gaps_by_span = defaultdict(float)
    coll_total = coll_exposed = 0.0
    n_dev = max(len(trace["devices"]), 1)
    for dev_name, dev in trace["devices"].items():
        ops = _with_program(
            [o for o in dev["ops"] if o[2] > 0], dev["programs"])
        if not ops:
            continue
        leaves = [o for o in ops
                  if not o[0].rsplit("/", 1)[-1].startswith(CONTROL_PREFIXES)]
        busy = union([o[1], o[1] + o[2]] for o in leaves)
        w0 = min(o[1] for o in ops)
        w1 = max(o[1] + o[2] for o in ops)
        per_device[dev_name] = {"busy_s": length(busy) / 1e9,
                                "window_s": (w1 - w0) / 1e9,
                                "n_ops": len(ops)}
        for name, s in self_times(ops).items():
            op_self[name] += s / n_dev
        tags.update((o[0], o[3]) for o in ops)
        for (_, end_a), (start_b, _) in zip(busy, busy[1:]):
            gaps_by_span[_span_at(trace["spans"], end_a, start_b)] += \
                (start_b - end_a) / 1e9 / n_dev
        for name, start, dur in dev["programs"]:
            programs[program_name(name)].append(dur / 1e9)
        # collectives: what the core's own line shows blocks the core;
        # what only an async line shows was hidden behind compute
        def is_collective(o):
            return o[0].rsplit("/", 1)[-1].startswith(COLLECTIVE_PREFIXES)

        coll_core = union([o[1], o[1] + o[2]] for o in leaves
                          if is_collective(o))
        coll_async = union([a[1], a[1] + a[2]] for a in dev.get("async", []))
        compute = union([o[1], o[1] + o[2]] for o in leaves
                        if not is_collective(o))
        all_coll = union([list(i) for i in coll_core + coll_async])
        coll_total += length(all_coll) / 1e9 / n_dev
        coll_exposed += length(subtract(all_coll, compute)) / 1e9 / n_dev
    if not per_device:
        return {}
    by_kind, by_tag = defaultdict(float), defaultdict(float)
    for name, s in op_self.items():
        by_kind[op_kind(name.rsplit("/", 1)[-1])] += s
        if tags.get(name, "").startswith("tpu_custom_call"):
            by_tag[tags[name]] += s
    program_stats = {}
    for name, durs in programs.items():
        durs.sort()
        program_stats[name] = {"count": len(durs), "total_s": sum(durs),
                               "p50_s": durs[len(durs) // 2]}
    return {
        "busy_s": sum(d["busy_s"] for d in per_device.values()) / len(per_device),
        "window_s": max(d["window_s"] for d in per_device.values()),
        "per_device": per_device,
        "op_self_s": dict(op_self),
        "kernel_self_s": dict(by_tag),
        "device_ops": [[f"{n} {tags.get(n, '')}".strip(), s] for n, s in sorted(
            op_self.items(), key=lambda kv: -kv[1])[:top]],
        "device_op_kinds": [[n, s] for n, s in sorted(
            by_kind.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, s] for n, s in sorted(
            gaps_by_span.items(), key=lambda kv: -kv[1])[:top]],
        "programs": program_stats,
        "collective_s": coll_total,
        "collective_exposed_s": coll_exposed,
        "spans": _span_totals(trace["spans"]),
    }


def _span_totals(spans) -> dict:
    out = defaultdict(lambda: {"count": 0, "total_s": 0.0})
    for name, _s, d in spans:
        out[name[len(SPAN_PREFIX):]]["count"] += 1
        out[name[len(SPAN_PREFIX):]]["total_s"] += d / 1e9
    return dict(out)


def kernel_self_s(summary: dict, tag_prefixes) -> float:
    """Self seconds of the Pallas kernels whose tag starts with a prefix."""
    return sum(s for tag, s in summary.get("kernel_self_s", {}).items()
               if tag.startswith(tuple(tag_prefixes)))


def reduce_dir(trace_dir: str, sample_to: str = "") -> dict:
    return reduce_trace(load_xplane(find_xplane(trace_dir)), sample_to)


def reduce_trace(trace: dict, sample_to: str = "") -> dict:
    """The summary of a loaded trace, empty where no operation ran on a
    device; a cut of the trace is kept at ``sample_to`` if that is given."""
    if sample_to:
        os.makedirs(os.path.dirname(sample_to), exist_ok=True)
        save_sample(trace, sample_to)
    summary = summarize(trace)
    if summary:
        summary["lines_seen"] = {n: d["other_lines"]
                                 for n, d in trace["devices"].items()}
    return summary
