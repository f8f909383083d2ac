"""raycheck core: source model, finding type, suppression, rule registry.

Every rule produces :class:`Finding` objects with a *fingerprint* that is
stable under line drift (rule id + path + enclosing scope + a short
normalized detail token) so the checked-in baseline survives unrelated
edits to the same file.

Suppression:
    # raycheck: disable=RC001            on the flagged line
    # raycheck: disable=RC001,RC004      several rules at once
    # raycheck: disable-file=RC003       anywhere in the file, whole file

Rules RC004 (determinism) and RC005 (thread hygiene) live in this module;
RC001/RC002/RC003 are big enough to get their own files (loopcheck.py,
lockgraph.py, rpccontract.py).
"""

from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Set, Tuple

_SUPPRESS_RE = re.compile(r"#.*?raycheck:\s*disable=([A-Z0-9, ]+)")
_SUPPRESS_FILE_RE = re.compile(r"#.*?raycheck:\s*disable-file=([A-Z0-9, ]+)")


@dataclass
class Finding:
    rule: str
    path: str  # repo-relative, forward slashes
    line: int
    scope: str  # dotted enclosing scope ("Class.method", "<module>")
    message: str
    detail: str  # short normalized token for the fingerprint
    # interprocedural findings carry the call chain (root..site) that
    # makes them reachable — surfaced by --json and in render()
    chain: Tuple[str, ...] = ()

    @property
    def fingerprint(self) -> str:
        # deliberately line-number-free AND chain-free: drift-stable
        return f"{self.rule}|{self.path}|{self.scope}|{self.detail}"

    def render(self) -> str:
        base = (f"{self.path}:{self.line}: {self.rule} [{self.scope}] "
                f"{self.message}")
        if self.chain:
            base += f"\n    via: {' -> '.join(self.chain)}"
        return base

    def as_json(self) -> dict:
        return {
            "rule": self.rule,
            "fingerprint": self.fingerprint,
            "path": self.path,
            "line": self.line,
            "scope": self.scope,
            "message": self.message,
            "detail": self.detail,
            "chain": list(self.chain),
        }


class SourceModule:
    """One parsed file plus everything the rules need to query it."""

    def __init__(self, path: str, relpath: str, source: str):
        self.path = path
        self.relpath = relpath.replace(os.sep, "/")
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=path)
        self.modname = self.relpath[:-3].replace("/", ".") \
            if self.relpath.endswith(".py") else self.relpath
        # line -> suppressed rule ids; plus file-wide suppressions
        self.suppressed: Dict[int, Set[str]] = {}
        self.file_suppressed: Set[str] = set()
        for i, ln in enumerate(self.lines, start=1):
            m = _SUPPRESS_RE.search(ln)
            if m:
                self.suppressed.setdefault(i, set()).update(
                    r.strip() for r in m.group(1).split(",") if r.strip())
            m = _SUPPRESS_FILE_RE.search(ln)
            if m:
                self.file_suppressed.update(
                    r.strip() for r in m.group(1).split(",") if r.strip())
        # scope map: every node gets its dotted enclosing scope
        self._scopes: Dict[ast.AST, str] = {}
        self._annotate_scopes(self.tree, [])
        # import aliases: local name -> real module ("t" -> "time")
        self.import_aliases: Dict[str, str] = {}
        # from-imports: local name -> "module.attr" ("sleep" -> "time.sleep")
        self.from_imports: Dict[str, str] = {}
        # one flattened pre-order walk, shared by every rule (the
        # analysis phases re-walk each tree many times; the list rides
        # the content-hash cache so warm runs skip even this)
        self.all_nodes: List[ast.AST] = list(ast.walk(self.tree))
        for node in self.all_nodes:
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.import_aliases[a.asname or a.name.split(".")[0]] = a.name
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    self.from_imports[a.asname or a.name] = \
                        f"{node.module}.{a.name}"

    def _annotate_scopes(self, node: ast.AST, stack: List[str]) -> None:
        name = getattr(node, "name", None)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            stack = stack + [name]
        self._scopes[node] = ".".join(stack) or "<module>"
        for child in ast.iter_child_nodes(node):
            self._annotate_scopes(child, stack)

    def scope_of(self, node: ast.AST) -> str:
        return self._scopes.get(node, "<module>")

    def is_suppressed(self, rule: str, line: int) -> bool:
        if rule in self.file_suppressed:
            return True
        if rule in self.suppressed.get(line, set()):
            return True
        # a comment-ONLY line directly above also suppresses (room for a
        # justification too long for the flagged line itself)
        if rule in self.suppressed.get(line - 1, set()) and \
                1 <= line - 1 <= len(self.lines) and \
                self.lines[line - 2].lstrip().startswith("#"):
            return True
        return False

    def line_has_comment(self, line: int) -> bool:
        if 1 <= line <= len(self.lines):
            return "#" in self.lines[line - 1]
        return False

    # -- resolution helpers -------------------------------------------
    def resolves_to(self, node: ast.expr, module: str,
                    attr: Optional[str] = None) -> bool:
        """True when ``node`` is a reference to ``module[.attr]`` under
        this file's imports (handles ``import time as t`` and
        ``from time import sleep``)."""
        dotted = dotted_name(node)
        if dotted is None:
            return False
        want = module if attr is None else f"{module}.{attr}"
        if dotted == want:
            return True
        head, _, rest = dotted.partition(".")
        real = self.import_aliases.get(head)
        if real is not None:
            full = real if not rest else f"{real}.{rest}"
            if full == want:
                return True
        if dotted in self.from_imports and self.from_imports[dotted] == want:
            return True
        return False


def dotted_name(node: ast.expr) -> Optional[str]:
    """``self.gcs.call`` -> "self.gcs.call"; None for non-name shapes."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    elif isinstance(node, ast.Call):
        parts.append("()")
    else:
        return None
    return ".".join(reversed(parts))


def terminal_attr(node: ast.expr) -> Optional[str]:
    """Method name of a call target: ``a.b.call`` -> "call"."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def receiver_name(node: ast.expr) -> Optional[str]:
    """Last name component of a call receiver: ``self.gcs.call`` -> "gcs"."""
    if isinstance(node, ast.Attribute):
        v = node.value
        if isinstance(v, ast.Attribute):
            return v.attr
        if isinstance(v, ast.Name):
            return v.id
    return None


def call_kwarg(call: ast.Call, name: str) -> Optional[ast.expr]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def const_str(node: Optional[ast.expr]) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def is_true(node: Optional[ast.expr]) -> bool:
    return isinstance(node, ast.Constant) and node.value is True


# =====================================================================
# RC004 — determinism: seeded-chaos and test code must not depend on
# process-global randomness, wall-clock time, or silently swallowed
# errors.
# =====================================================================

_DET_RANDOM_FNS = {
    "random", "choice", "randint", "uniform", "shuffle", "sample",
    "randrange", "gauss", "betavariate", "expovariate",
}
_SHUTDOWN_FN_RE = re.compile(
    r"^(close|stop|shutdown|exit|teardown|cleanup|kill|terminate|"
    r"__del__|__exit__|atexit.*|.*_teardown|.*_shutdown|.*_cleanup)$")


def _rc004_scope(mod: SourceModule) -> Tuple[bool, bool]:
    """(full_scope, tests) — full_scope enables every RC004 check
    (chaos.py / drain.py / tests, plus the serve/llm request path and
    rllib: the front door is chaos-tested under seeded churn and RL
    runs are seed-reproducible by contract — worker_seed fan-out — so
    unseeded randomness or silently swallowed errors there break soak
    replay and hide shed/retry bugs); elsewhere only the
    swallowed-exception check applies, and only inside shutdown-path
    functions."""
    base = os.path.basename(mod.relpath)
    in_tests = "tests/" in mod.relpath or base.startswith("test_") \
        or base == "conftest.py"
    in_serve = mod.relpath.startswith(
        ("ray_tpu/serve/", "ray_tpu/llm/", "ray_tpu/rllib/"))
    return (base in ("chaos.py", "drain.py") or in_tests or in_serve), \
        in_tests


def check_rc004(modules: List[SourceModule]) -> List[Finding]:
    out: List[Finding] = []
    for mod in modules:
        full, in_tests = _rc004_scope(mod)
        base = os.path.basename(mod.relpath)
        for node in mod.all_nodes:
            # unseeded process-global randomness
            if full and isinstance(node, ast.Call):
                fn = node.func
                # both spellings: random.choice(...) and
                # `from random import choice; choice(...)`
                rand_fn = None
                if isinstance(fn, ast.Attribute) and \
                        fn.attr in _DET_RANDOM_FNS and \
                        mod.resolves_to(fn, "random", fn.attr):
                    rand_fn = fn.attr
                elif isinstance(fn, ast.Name):
                    target = mod.from_imports.get(fn.id, "")
                    if target.startswith("random.") and \
                            target.split(".", 1)[1] in _DET_RANDOM_FNS:
                        rand_fn = target.split(".", 1)[1]
                if rand_fn is not None:
                    out.append(Finding(
                        "RC004", mod.relpath, node.lineno, mod.scope_of(node),
                        f"unseeded process-global random.{rand_fn}() — "
                        f"seeded chaos/tests must use a random.Random(seed) "
                        f"instance", f"random.{rand_fn}"))
                elif mod.resolves_to(fn, "random", "Random") and \
                        not node.args and not node.keywords:
                    out.append(Finding(
                        "RC004", mod.relpath, node.lineno, mod.scope_of(node),
                        "random.Random() without a seed — pass an explicit "
                        "seed for reproducible runs", "random.Random()"))
                # wall-clock decisions inside seeded injectors
                elif base in ("chaos.py", "drain.py") and \
                        mod.resolves_to(fn, "time", "time"):
                    out.append(Finding(
                        "RC004", mod.relpath, node.lineno, mod.scope_of(node),
                        "time.time() in a seeded injector — use "
                        "time.monotonic() for intervals/deadlines "
                        "(wall-clock jumps break determinism)", "time.time"))
            # swallowed exceptions
            if isinstance(node, ast.ExceptHandler):
                scope = mod.scope_of(node)
                fn_name = scope.rsplit(".", 1)[-1]
                applies = full or _SHUTDOWN_FN_RE.match(fn_name)
                if not applies:
                    continue
                broad = node.type is None or (
                    isinstance(node.type, ast.Name)
                    and node.type.id in ("Exception", "BaseException"))
                body_is_pass = len(node.body) == 1 and \
                    isinstance(node.body[0], ast.Pass)
                if broad and body_is_pass and \
                        not mod.line_has_comment(node.lineno) and \
                        not mod.line_has_comment(node.body[0].lineno):
                    what = "bare except:" if node.type is None else \
                        f"except {node.type.id}:"
                    out.append(Finding(
                        "RC004", mod.relpath, node.lineno, scope,
                        f"{what} pass silently swallows errors — log it, "
                        f"narrow the type, or add a justification comment "
                        f"on the except/pass line", "swallow"))
    return out


# =====================================================================
# RC005 — thread hygiene: every Thread states its daemon-ness; a class
# that stores a thread and exposes stop()/close()/shutdown() must join
# it there.
# =====================================================================

def _is_thread_ctor(mod: SourceModule, call: ast.Call) -> bool:
    fn = call.func
    if mod.resolves_to(fn, "threading", "Thread"):
        return True
    return isinstance(fn, ast.Name) and \
        mod.from_imports.get(fn.id) == "threading.Thread"


def check_rc005(modules: List[SourceModule]) -> List[Finding]:
    out: List[Finding] = []
    for mod in modules:
        for node in mod.all_nodes:
            if isinstance(node, ast.Call) and _is_thread_ctor(mod, node):
                if call_kwarg(node, "daemon") is None:
                    out.append(Finding(
                        "RC005", mod.relpath, node.lineno, mod.scope_of(node),
                        "threading.Thread(...) without an explicit daemon= — "
                        "state the lifecycle decision at the creation site",
                        "thread-no-daemon"))
            if isinstance(node, ast.ClassDef):
                out.extend(_rc005_missing_join(mod, node))
    return out


def _rc005_missing_join(mod: SourceModule, cls: ast.ClassDef) -> List[Finding]:
    stores_thread = False
    for node in ast.walk(cls):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call) \
                and _is_thread_ctor(mod, node.value):
            for tgt in node.targets:
                if isinstance(tgt, ast.Attribute) and \
                        isinstance(tgt.value, ast.Name) and \
                        tgt.value.id == "self":
                    stores_thread = True
    if not stores_thread:
        return []
    out: List[Finding] = []
    for item in cls.body:
        if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)) and \
                item.name in ("stop", "close", "shutdown"):
            joins = any(
                isinstance(n, ast.Call) and terminal_attr(n.func) == "join"
                for n in ast.walk(item))
            if not joins:
                out.append(Finding(
                    "RC005", mod.relpath, item.lineno,
                    mod.scope_of(item),
                    f"{cls.name}.{item.name}() does not join the thread "
                    f"this class stores — a stop path that skips join "
                    f"leaks the thread past shutdown",
                    f"missing-join:{item.name}"))
    return out


# =====================================================================
# registry — filled out by __init__ side imports in api.collect()
# =====================================================================

RuleFn = Callable[[List[SourceModule]], List[Finding]]

RULE_DOCS: Dict[str, str] = {
    "RC001": "loop-blocking: blocking calls inside async def bodies and "
             "(whole-program call-graph reachable from) inline=True RPC "
             "handlers",
    "RC002": "lock-order: lock-acquisition cycles and blocking calls made "
             "while holding a module-level lock",
    "RC003": "rpc-contract: RPC call sites with no registered handler; "
             "explicitly registered handlers never called",
    "RC004": "determinism: unseeded randomness, wall-clock decisions in "
             "seeded injectors, silently swallowed exceptions",
    "RC005": "thread-hygiene: Thread without explicit daemon=; stop/close "
             "paths that do not join a stored thread",
    "RC006": "resource-lifecycle: CFG path-sensitive acquire/release — "
             "locks, RpcClient/channel/arena handles, started threads "
             "must be released/closed/joined on every exit path",
    "RC007": "lockset-race: attributes written in one thread context "
             "(io/exec/thread) and accessed from another with no common "
             "lock",
    "RC008": "protocol-conformance: actor/node-drain/lease/pg state "
             "assignments verified against checked-in transition tables",
    "RC009": "obs-conformance: record_event types and set-up phase names "
             "must be declared in "
             "observability/schema.py; event/span/metric names must not "
             "be built with f-strings/format/concat at the call site",
}

# rules that consume the whole-program call graph (built once per run)
_GRAPH_RULES = {"RC001", "RC007"}


def builtin_rules() -> Dict[str, RuleFn]:
    from tools.raycheck.lifecycle import check_rc006
    from tools.raycheck.lockgraph import check_rc002
    from tools.raycheck.lockset import check_rc007
    from tools.raycheck.loopcheck import check_rc001
    from tools.raycheck.obsconform import check_rc009
    from tools.raycheck.protocol import check_rc008
    from tools.raycheck.rpccontract import check_rc003

    return {
        "RC001": check_rc001,
        "RC002": check_rc002,
        "RC003": check_rc003,
        "RC004": check_rc004,
        "RC005": check_rc005,
        "RC006": check_rc006,
        "RC007": check_rc007,
        "RC008": check_rc008,
        "RC009": check_rc009,
    }


def discover_files(paths: List[str]) -> List[str]:
    files: List[str] = []
    for p in paths:
        if os.path.isfile(p):
            files.append(p)
        else:
            for dirpath, dirnames, filenames in os.walk(p):
                dirnames[:] = [d for d in dirnames
                               if d not in ("__pycache__", "_build",
                                            ".git", ".venv")]
                for f in sorted(filenames):
                    if f.endswith(".py"):
                        files.append(os.path.join(dirpath, f))
    return sorted(set(files))


def load_modules(paths: List[str], root: Optional[str] = None,
                 use_cache: bool = False,
                 contents: Optional[Dict[str, bytes]] = None,
                 ) -> List[SourceModule]:
    """Parse every .py file under ``paths`` (files or directories).

    With ``use_cache=True``, per-file :class:`SourceModule` objects are
    memoised in ``<root>/.raycheck_cache/`` keyed by content digest +
    analyzer-source fingerprint (see cache.py) — a hit skips the parse
    and annotation passes and is byte-equivalent to a cold build.
    ``contents`` optionally supplies pre-read file bytes (path ->
    bytes); when given it is also the *complete* file list, so the
    caller's digest sweep and the analysis see exactly the same inputs
    (no second discovery racing tree mutations).
    """
    root = root or os.getcwd()
    files = list(contents) if contents is not None \
        else discover_files(paths)
    cache = None
    if use_cache:
        from tools.raycheck.cache import Cache
        cache = Cache(root)
    mods: List[SourceModule] = []
    for f in sorted(set(files)):
        try:
            raw = contents.get(f) if contents is not None else None
            if raw is None:
                with open(f, "rb") as fh:
                    raw = fh.read()
            rel = os.path.relpath(f, root)
            mod = cache.get(rel, raw) if cache is not None else None
            if mod is None:
                mod = SourceModule(f, rel, raw.decode("utf-8"))
                if cache is not None:
                    cache.put(rel, raw, mod)
            mods.append(mod)
        except (SyntaxError, UnicodeDecodeError, OSError):
            continue  # non-parseable files are out of scope, not findings
    if cache is not None:
        cache.prune()
    return mods


def analyze(modules: List[SourceModule],
            rules: Optional[List[str]] = None) -> List[Finding]:
    """Run the selected rules and drop suppressed findings."""
    registry = builtin_rules()
    wanted = rules or sorted(registry)
    by_path = {m.relpath: m for m in modules}
    graph = None
    if any(r in _GRAPH_RULES for r in wanted):
        from tools.raycheck import callgraph as cg_mod
        graph = cg_mod.build(modules)
    findings: List[Finding] = []
    for rid in wanted:
        fn = registry[rid]
        got = fn(modules, graph) if rid in _GRAPH_RULES \
            else fn(modules)
        for f in got:
            mod = by_path.get(f.path)
            if mod is not None and mod.is_suppressed(f.rule, f.line):
                continue
            findings.append(f)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings
