"""RC009 — observability-name conformance.

The flight-recorder pipeline (event bus → GCS aggregator → obsdump) is
only queryable because names are *finite*: every ``record_event`` type
must be declared in ``ray_tpu/observability/schema.py`` and span/metric
names must come from a fixed vocabulary, not per-call string building.
Two failure shapes this rule catches:

1. **Undeclared event type** — ``record_event("task_stat", ...)`` with
   a literal type missing from ``EVENT_TYPES``: the event ships, lands
   in rings and dumps, and silently matches no query, timeline builder
   or obsdump lane. (Variables as the type are allowed — tests drive
   the bus generically — only literals are checked against the schema.)
   The same holds for a set-up phase: a literal name passed to
   ``setup_phase`` / ``record_setup_phase`` must be a key of
   ``SETUP_PHASES`` (at run time an undeclared name raises; this finds it
   before the site ever runs).
2. **Dynamic name** — an f-string / ``.format`` / ``%`` / string
   concatenation as the *name* of an event, span or metric:
   unbounded-cardinality names explode Prometheus label sets and the
   aggregator's per-name indexes, and obsdump can't give a stable lane
   to a name that embeds a request id. Build names once in an interned
   table (see ``observability/collective.py::_span_name``) instead.
"""

from __future__ import annotations

import ast
import os
from typing import Dict, List, Optional, Set

from tools.raycheck.rules import Finding, SourceModule, const_str

# resolved call target -> which argument carries the name
#   (position index; the kwarg fallbacks below cover keyword style)
_EVENT_CALLS = {
    "ray_tpu.observability.events.record_event",
    "ray_tpu.observability.record_event",
}
# resolved call target -> the schema dict whose keys its literal name is held
# to, beside the dynamic-name check every name call gets
_SETUP_CALLS = {
    "ray_tpu.observability.timeline.setup_phase",
    "ray_tpu.observability.timeline.record_setup_phase",
    "ray_tpu.observability.setup_phase",
    "ray_tpu.observability.record_setup_phase",
}
_NAME_CALLS = {
    "ray_tpu.observability.tracing.span",
    "ray_tpu.observability.span",
    "ray_tpu.observability.tracing.record_span",
    "ray_tpu.util.metrics.get_histogram",
    "ray_tpu.util.metrics.Counter",
    "ray_tpu.util.metrics.Gauge",
    "ray_tpu.util.metrics.Histogram",
    "ray_tpu.observability.dump.counter_sample",
    "ray_tpu.observability.counter_sample",
}
_NAME_KWARGS = ("name", "etype")

_SCHEMA_RELPATH = "ray_tpu/observability/schema.py"


def _resolve(mod: SourceModule, func: ast.expr) -> Optional[str]:
    """Dotted call target with the head resolved through this file's
    imports: ``obs_events.record_event`` (via ``from
    ray_tpu.observability import events as obs_events``) resolves to
    ``ray_tpu.observability.events.record_event``."""
    parts: List[str] = []
    node = func
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    head = node.id
    parts.append(head)
    parts.reverse()
    real = mod.from_imports.get(head) or mod.import_aliases.get(head)
    if real is not None:
        parts[0:1] = real.split(".")
    return ".".join(parts)


def _is_dynamic(node: ast.expr) -> bool:
    """True for name expressions BUILT at the call site: f-strings,
    ``.format``, ``%``, and string concatenation. Plain names,
    attributes and calls are fine — those are lookups into a table
    someone owns, which is exactly the sanctioned pattern."""
    if isinstance(node, ast.JoinedStr):
        return any(isinstance(v, ast.FormattedValue) for v in node.values)
    if isinstance(node, ast.Call) and \
            isinstance(node.func, ast.Attribute) and \
            node.func.attr == "format" and \
            isinstance(node.func.value, (ast.Constant, ast.JoinedStr)):
        return True
    if isinstance(node, ast.BinOp) and \
            isinstance(node.op, (ast.Mod, ast.Add)):
        for side in (node.left, node.right):
            if const_str(side) is not None or \
                    isinstance(side, ast.JoinedStr):
                return True
    return False


def _schema_keys(modules: List[SourceModule],
                 table: str = "EVENT_TYPES") -> Optional[Set[str]]:
    """The declared keys of ``table`` (``EVENT_TYPES``, ``SETUP_PHASES``),
    from the analyzed module set
    when schema.py is in it, else from disk next to the analyzed tree.
    None (skip membership checks) when the schema can't be found —
    raycheck must stay runnable on partial trees."""
    tree = None
    for mod in modules:
        if mod.relpath == _SCHEMA_RELPATH:
            tree = mod.tree
            break
    if tree is None:
        for mod in modules:
            idx = mod.path.replace(os.sep, "/").rfind("/" + mod.relpath)
            if idx < 0:
                continue
            candidate = os.path.join(mod.path[:idx], _SCHEMA_RELPATH)
            try:
                with open(candidate) as f:
                    tree = ast.parse(f.read(), filename=candidate)
            except (OSError, SyntaxError):
                continue
            break
    if tree is None:
        return None
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == table
                for t in node.targets) and \
                isinstance(node.value, ast.Dict):
            keys = {k.value for k in node.value.keys
                    if isinstance(k, ast.Constant)
                    and isinstance(k.value, str)}
            return keys or None
    return None


def _name_arg(call: ast.Call) -> Optional[ast.expr]:
    if call.args:
        return call.args[0]
    for kw in call.keywords:
        if kw.arg in _NAME_KWARGS:
            return kw.value
    return None


def check_rc009(modules: List[SourceModule]) -> List[Finding]:
    declared = _schema_keys(modules)
    phases = _schema_keys(modules, "SETUP_PHASES")
    out: List[Finding] = []
    for mod in modules:
        for node in mod.all_nodes:
            if not isinstance(node, ast.Call):
                continue
            target = _resolve(mod, node.func)
            if target is None:
                continue
            is_event = target in _EVENT_CALLS
            is_setup = target in _SETUP_CALLS
            if not (is_event or is_setup or target in _NAME_CALLS):
                continue
            arg = _name_arg(node)
            if arg is None:
                continue
            if _is_dynamic(arg):
                out.append(Finding(
                    "RC009", mod.relpath, node.lineno, mod.scope_of(node),
                    f"dynamically built name passed to "
                    f"{target.rsplit('.', 1)[-1]}() — unbounded name "
                    f"cardinality breaks event queries, Prometheus "
                    f"labels and obsdump lanes; intern the name in a "
                    f"module-level table instead",
                    f"dynamic-name:{target.rsplit('.', 1)[-1]}"))
                continue
            if is_event and declared is not None:
                literal = const_str(arg)
                if literal is not None and literal not in declared:
                    out.append(Finding(
                        "RC009", mod.relpath, node.lineno,
                        mod.scope_of(node),
                        f"record_event type {literal!r} is not declared "
                        f"in ray_tpu/observability/schema.py EVENT_TYPES"
                        f" — undeclared events match no query, timeline "
                        f"or obsdump lane",
                        f"undeclared-event:{literal}"))
            if is_setup and phases is not None:
                literal = const_str(arg)
                if literal is not None and literal not in phases:
                    out.append(Finding(
                        "RC009", mod.relpath, node.lineno,
                        mod.scope_of(node),
                        f"set-up phase {literal!r} is not declared in "
                        f"ray_tpu/observability/schema.py SETUP_PHASES — "
                        f"the site raises when it runs",
                        f"undeclared-phase:{literal}"))
    return out
