"""Device time by the ``jax.named_scope`` an operation was traced under.

The device trace names an operation by its HLO line and carries nothing else
(``%fusion.31 = bf16[...] fusion(...), kind=kOutput, calls=...``: no
``metadata``, no stat but its duration; my chip run, PR 30), so a scope does
not reach the trace by itself. It does reach the COMPILED program's text:
every instruction there ends in ``metadata={op_name="jit(_decode_impl)/jit(
main)/while/body/cca.attend/attend_cached/dot_general" ...}``, under the name
the trace uses for it. So the replica, which holds the compiled programs, maps
operation names to scopes once before the window (``op_scopes``, part of the
reference check's record), and the readers add up ``trace_reduce``'s self
seconds over the operations of a scope (``ms_per_run``).

A fusion is one operation with one ``op_name``, its root's: where XLA fused
the end of one scope into the start of the next, that time is the root's
scope's. Operations under none of ``SCOPES`` are not counted anywhere here.
"""

from __future__ import annotations

import re

from benchmarks import readers

# outermost first: an operation under cca.attend/attend_cached is cca.attend's
SCOPES = ("cca.project", "cca.conv", "cca.attend", "zaya.router", "moe_router",
          "moe_experts", "lm_head", "sample")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*\bop_name=\"([^\"]*)\"")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")


def scope_of(op_name: str, scopes=SCOPES):
    """The first of ``scopes`` on the path ``op_name`` (outermost wins)."""
    parts = op_name.split("/")
    found = [parts.index(s) for s in scopes if s in parts]
    return parts[min(found)] if found else None


def op_scopes(hlo_text: str, scopes=SCOPES) -> dict:
    """{scope: [operation names]} of a compiled program's text. The
    instructions inside a fused computation are left out: the device runs,
    and the trace names, the fusion that calls it."""
    out, fused = {}, False
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            fused = "fused_computation" in head[1]
        m = None if fused else _INSTRUCTION.match(line)
        if m:
            scope = scope_of(m[2], scopes)
            if scope:
                out.setdefault(scope, []).append(m[1])
    return out


def seconds_by_scope(ctx, program_pattern: str):
    """{scope: self seconds over the traced window} of the programs whose
    name holds ``program_pattern``; None where the run recorded no map for
    it (another runner, the parent of the PR that added the scopes) or the
    trace has none of its operations."""
    trace = ctx["trace"]
    mapped = (ctx["counters"].get("reference_check") or {}).get(
        "op_scopes", {}).get(program_pattern)
    if not trace or not mapped:
        return None
    scope_at = {op: scope for scope, ops in mapped.items() for op in ops}
    out = {}
    for name, seconds in trace.get("op_self_s", {}).items():
        program, _, op = name.rpartition("/")
        if program_pattern in program and op in scope_at:
            out[scope_at[op]] = out.get(scope_at[op], 0.0) + seconds
    return out or None


def ms_per_run(ctx, program_pattern: str, scopes):
    """Self milliseconds of the operations under ``scopes`` per traced run of
    the program; None where nothing of them was traced."""
    by_scope = seconds_by_scope(ctx, program_pattern)
    program = readers.program(ctx, program_pattern)
    if not by_scope or not program:
        return None
    seconds = sum(by_scope.get(s, 0.0) for s in scopes)
    return seconds * 1e3 / program["count"] if seconds else None
