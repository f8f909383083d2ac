"""Tests for tools/raycheck — the distributed-runtime static analysis
suite — and for the RAY_TPU_DEBUG_LOCKS dynamic lock-order proxy that
validates RC002's static model at runtime.

Each rule gets positive / negative / suppressed fixtures; the live-tree
test is the tier-1 wiring: `python -m tools.raycheck ray_tpu/ tests/`
must stay clean (zero non-baselined findings) on every commit.
"""

import json
import os
import subprocess
import sys
import textwrap
import threading

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools.raycheck import run  # noqa: E402
from tools.raycheck import baseline as baseline_mod  # noqa: E402
from tools.raycheck.rules import analyze, load_modules  # noqa: E402


def _scan(tmp_path, relpath, source, rules=None):
    p = tmp_path / relpath
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(source))
    mods = load_modules([str(tmp_path)], root=str(tmp_path))
    return analyze(mods, rules=rules)


def _details(findings):
    return [(f.rule, f.detail) for f in findings]


# =====================================================================
# RC001 — loop-blocking
# =====================================================================

class TestRC001:
    def test_flags_sleep_in_async_def(self, tmp_path):
        fs = _scan(tmp_path, "mod.py", """
            import time

            async def handler():
                time.sleep(1)
        """, rules=["RC001"])
        assert _details(fs) == [("RC001", "async:time.sleep")]

    def test_flags_sync_rpc_and_run_coro_in_async_def(self, tmp_path):
        fs = _scan(tmp_path, "mod.py", """
            async def push(self):
                self.gcs.call("Heartbeat")
                self._loop_thread.run_coro(something())
        """, rules=["RC001"])
        assert ("RC001", "async:sync-rpc.call") in _details(fs)
        assert ("RC001", "async:run_coro") in _details(fs)

    def test_flags_inline_handler_direct_and_transitive(self, tmp_path):
        fs = _scan(tmp_path, "mod.py", """
            import time

            class Server:
                def __init__(self, srv):
                    srv.register("Fast", self._fast, inline=True)

                def _fast(self):
                    return self._helper()

                def _helper(self):
                    time.sleep(0.5)  # reachable from the inline handler
        """, rules=["RC001"])
        assert _details(fs) == [("RC001", "inline:time.sleep")]
        assert "reached via Server._helper" in fs[0].message

    def test_flags_bare_handle_result_in_async_def(self, tmp_path):
        """A CollectiveHandle.result() without a timeout waits behind
        the group's whole async op queue — on loop code that is an
        unbounded park, exactly the shape RC001 exists for."""
        fs = _scan(tmp_path, "mod.py", """
            async def on_drain(self, handle):
                return handle.result()
        """, rules=["RC001"])
        assert _details(fs) == [("RC001", "async:handle.result")]

    def test_handle_result_with_timeout_is_clean(self, tmp_path):
        fs = _scan(tmp_path, "mod.py", """
            async def on_drain(self, handle):
                return handle.result(timeout=5.0)
        """, rules=["RC001"])
        assert fs == []

    def test_handle_result_reachable_from_inline_handler(self, tmp_path):
        fs = _scan(tmp_path, "mod.py", """
            def finish(handle):
                return handle.result()

            class Server:
                def __init__(self, srv):
                    srv.register("Sync", self._sync, inline=True)

                def _sync(self, handle):
                    return finish(handle)
        """, rules=["RC001"])
        assert ("RC001", "inline:handle.result") in _details(fs)

    def test_awaited_wait_is_not_blocking(self, tmp_path):
        fs = _scan(tmp_path, "mod.py", """
            import asyncio

            async def watcher(ev):
                await asyncio.wait_for(ev.wait(), timeout=5.0)
                await ev.wait()
        """, rules=["RC001"])
        assert fs == []

    def test_non_inline_sync_handler_not_flagged(self, tmp_path):
        # sync handlers without inline=True run on the executor: blocking
        # is legal there
        fs = _scan(tmp_path, "mod.py", """
            import time

            class Server:
                def __init__(self, srv):
                    srv.register("Slow", self._slow)

                def _slow(self):
                    time.sleep(0.5)
        """, rules=["RC001"])
        assert fs == []

    def test_suppression(self, tmp_path):
        fs = _scan(tmp_path, "mod.py", """
            import time

            async def handler():
                time.sleep(1)  # raycheck: disable=RC001
        """, rules=["RC001"])
        assert fs == []


class TestRC001ServePath:
    """PR-12 sweep: the serve/llm request path must never wait without a
    timeout — every wait derives from the per-request deadline."""

    def test_untimeouted_result_on_serve_path(self, tmp_path):
        fs = _scan(tmp_path, "ray_tpu/serve/thing.py", """
            def call(handle):
                return handle.remote().result()
        """, rules=["RC001"])
        assert _details(fs) == [("RC001", "servepath:result")]

    def test_untimeouted_get_and_wait_on_llm_path(self, tmp_path):
        fs = _scan(tmp_path, "ray_tpu/llm/thing.py", """
            import ray_tpu

            def resolve(ref, ev):
                ev.wait()
                return ray_tpu.get(ref)
        """, rules=["RC001"])
        ds = _details(fs)
        assert ("RC001", "servepath:get") in ds
        assert ("RC001", "servepath:wait") in ds

    def test_bounded_waits_not_flagged(self, tmp_path):
        fs = _scan(tmp_path, "ray_tpu/serve/thing.py", """
            import ray_tpu

            def call(handle, ref, ev, fut):
                ev.wait(timeout=5)
                fut.result(5)
                ray_tpu.get(ref, timeout=3)
                return handle.remote().result(timeout=2)
        """, rules=["RC001"])
        assert fs == []

    def test_same_code_off_serve_path_not_flagged(self, tmp_path):
        fs = _scan(tmp_path, "ray_tpu/util/thing.py", """
            def call(handle):
                return handle.remote().result()
        """, rules=["RC001"])
        assert fs == []

    def test_suppression_with_justification(self, tmp_path):
        fs = _scan(tmp_path, "ray_tpu/serve/thing.py", """
            def call(fut):
                # raycheck: disable=RC001 — done-callback, fut resolved
                return fut.result()
        """, rules=["RC001"])
        assert fs == []


# =====================================================================
# RC002 — lock-order
# =====================================================================

class TestRC002:
    def test_cycle_detected(self, tmp_path):
        fs = _scan(tmp_path, "_private/mod.py", """
            import threading

            A = threading.Lock()
            B = threading.Lock()

            def one():
                with A:
                    with B:
                        pass

            def two():
                with B:
                    with A:
                        pass
        """, rules=["RC002"])
        assert any(d.startswith("cycle:") for _, d in _details(fs))

    def test_consistent_order_is_clean(self, tmp_path):
        fs = _scan(tmp_path, "_private/mod.py", """
            import threading

            A = threading.Lock()
            B = threading.Lock()

            def one():
                with A:
                    with B:
                        pass

            def two():
                with A:
                    with B:
                        pass
        """, rules=["RC002"])
        assert fs == []

    def test_reentrant_same_lock_is_not_a_cycle(self, tmp_path):
        # matches the dynamic model: re-entrant RLock nesting is legal
        fs = _scan(tmp_path, "_private/mod.py", """
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.RLock()

                def outer(self):
                    with self._lock:
                        with self._lock:
                            pass
        """, rules=["RC002"])
        assert fs == []

    def test_pr7_livelock_shape_close_under_module_lock(self, tmp_path):
        fs = _scan(tmp_path, "_private/mod.py", """
            import threading

            _cache_lock = threading.Lock()
            _cache = {}

            def clear():
                with _cache_lock:
                    for c in _cache.values():
                        c.close()
                    _cache.clear()
        """, rules=["RC002"])
        assert _details(fs) == [("RC002", "hold-call:close")]

    def test_bare_acquire_release_spelling_also_flagged(self, tmp_path):
        # the with-less respelling of the PR-7 pattern must not evade
        # the rule
        fs = _scan(tmp_path, "_private/mod.py", """
            import threading

            _cache_lock = threading.Lock()
            _cache = {}

            def clear():
                _cache_lock.acquire()
                for c in _cache.values():
                    c.close()
                _cache_lock.release()
        """, rules=["RC002"])
        assert ("RC002", "hold-call:close") in _details(fs)

    def test_bare_acquire_released_before_call_is_clean(self, tmp_path):
        fs = _scan(tmp_path, "_private/mod.py", """
            import threading

            _cache_lock = threading.Lock()
            _cache = {}

            def clear():
                _cache_lock.acquire()
                clients = list(_cache.values())
                _cache.clear()
                _cache_lock.release()
                for c in clients:
                    c.close()
        """, rules=["RC002"])
        assert fs == []

    def test_snapshot_then_close_is_clean(self, tmp_path):
        fs = _scan(tmp_path, "_private/mod.py", """
            import threading

            _cache_lock = threading.Lock()
            _cache = {}

            def clear():
                with _cache_lock:
                    clients = list(_cache.values())
                    _cache.clear()
                for c in clients:
                    c.close()
        """, rules=["RC002"])
        assert fs == []

    def test_outside_private_not_scanned(self, tmp_path):
        fs = _scan(tmp_path, "public/mod.py", """
            import threading

            L = threading.Lock()

            def f(c):
                with L:
                    c.close()
        """, rules=["RC002"])
        assert fs == []

    def test_suppression(self, tmp_path):
        fs = _scan(tmp_path, "_private/mod.py", """
            import threading

            L = threading.Lock()

            def f(c):
                with L:
                    c.close()  # raycheck: disable=RC002
        """, rules=["RC002"])
        assert fs == []


# =====================================================================
# RC003 — rpc-contract
# =====================================================================

class TestRC003:
    def test_unregistered_call_and_unused_handler(self, tmp_path):
        fs = _scan(tmp_path, "mod.py", """
            class S:
                def __init__(self, server):
                    server.register("Ping", self._ping)
                    server.register("Orphan", self._orphan)

            def use(client):
                client.call("Ping")
                client.call("PingTypo")
        """, rules=["RC003"])
        ds = _details(fs)
        assert ("RC003", "unregistered:PingTypo") in ds
        assert ("RC003", "unused:Orphan") in ds
        assert ("RC003", "unregistered:Ping") not in ds

    def test_register_instance_sweep_counts(self, tmp_path):
        fs = _scan(tmp_path, "mod.py", """
            class Gcs:
                def __init__(self):
                    self.server.register_instance(self)

                def RegisterNode(self):
                    return 1

            def use(client):
                client.call_retrying("RegisterNode")
        """, rules=["RC003"])
        assert fs == []

    def test_dict_handler_table_counts(self, tmp_path):
        fs = _scan(tmp_path, "mod.py", """
            def start(srv):
                handlers = {"Echo": echo, "Sum": compute_sum}
                for name, fn in handlers.items():
                    srv.register(name, fn)

            def use(client):
                client.call("Echo")
        """, rules=["RC003"])
        assert fs == []

    def test_unrelated_dict_does_not_mask_typos(self, tmp_path):
        # a string-keyed dict that never flows into a register loop must
        # not absorb typo'd call sites
        fs = _scan(tmp_path, "mod.py", """
            OPTS = {"PingTypo": print}

            def use(client):
                client.call("PingTypo")
        """, rules=["RC003"])
        assert ("RC003", "unregistered:PingTypo") in _details(fs)

    def test_non_server_register_is_not_rpc(self, tmp_path):
        fs = _scan(tmp_path, "mod.py", """
            def setup(pbt, atexit):
                pbt.register("a", {"lr": 1.0})
                atexit.register("b")
        """, rules=["RC003"])
        assert fs == []

    def test_suppression(self, tmp_path):
        fs = _scan(tmp_path, "mod.py", """
            def use(client):
                client.call("Nowhere")  # raycheck: disable=RC003
        """, rules=["RC003"])
        assert fs == []


# =====================================================================
# RC004 — determinism
# =====================================================================

class TestRC004:
    def test_unseeded_random_in_chaos(self, tmp_path):
        fs = _scan(tmp_path, "chaos.py", """
            import random

            def pick(xs):
                return random.choice(xs)

            def mk():
                return random.Random()
        """, rules=["RC004"])
        ds = _details(fs)
        assert ("RC004", "random.choice") in ds
        assert ("RC004", "random.Random()") in ds

    def test_from_import_spelling_also_flagged(self, tmp_path):
        fs = _scan(tmp_path, "chaos.py", """
            from random import choice

            def pick(xs):
                return choice(xs)
        """, rules=["RC004"])
        assert _details(fs) == [("RC004", "random.choice")]

    def test_seeded_random_is_clean(self, tmp_path):
        fs = _scan(tmp_path, "chaos.py", """
            import random

            def mk(seed):
                rng = random.Random(seed)
                return rng.choice([1, 2])
        """, rules=["RC004"])
        assert fs == []

    def test_wall_clock_in_injector(self, tmp_path):
        fs = _scan(tmp_path, "chaos.py", """
            import time

            def due(deadline):
                return time.time() > deadline
        """, rules=["RC004"])
        assert _details(fs) == [("RC004", "time.time")]

    def test_monotonic_is_clean(self, tmp_path):
        fs = _scan(tmp_path, "chaos.py", """
            import time

            def due(deadline):
                return time.monotonic() > deadline
        """, rules=["RC004"])
        assert fs == []

    def test_swallowed_exception_in_tests_scope(self, tmp_path):
        fs = _scan(tmp_path, "tests/test_x.py", """
            def teardown_thing(c):
                try:
                    c.shutdown()
                except Exception:
                    pass
        """, rules=["RC004"])
        assert _details(fs) == [("RC004", "swallow")]

    def test_justification_comment_clears_swallow(self, tmp_path):
        fs = _scan(tmp_path, "tests/test_x.py", """
            def teardown_thing(c):
                try:
                    c.shutdown()
                except Exception:
                    pass  # already down: teardown is best-effort
        """, rules=["RC004"])
        assert fs == []

    def test_swallow_outside_shutdown_paths_not_flagged(self, tmp_path):
        # library code: only shutdown-shaped functions are in scope
        fs = _scan(tmp_path, "lib.py", """
            def compute(x):
                try:
                    return x()
                except Exception:
                    pass
        """, rules=["RC004"])
        assert fs == []

    def test_suppression(self, tmp_path):
        fs = _scan(tmp_path, "chaos.py", """
            import random

            def pick(xs):
                return random.choice(xs)  # raycheck: disable=RC004
        """, rules=["RC004"])
        assert fs == []

    def test_serve_path_is_full_scope(self, tmp_path):
        """PR-12 sweep: the front door is chaos-tested under seeded
        churn — unseeded routing randomness or a swallowed exception in
        the proxy/replica path breaks soak replay / hides shed bugs."""
        fs = _scan(tmp_path, "ray_tpu/serve/router.py", """
            import random

            def pick(xs):
                return random.choice(xs)

            def relay(x):
                try:
                    return x()
                except Exception:
                    pass
        """, rules=["RC004"])
        ds = _details(fs)
        assert ("RC004", "random.choice") in ds
        assert ("RC004", "swallow") in ds

    def test_llm_path_seeded_random_clean(self, tmp_path):
        fs = _scan(tmp_path, "ray_tpu/llm/sampler.py", """
            import random

            _rng = random.Random(0)

            def pick(xs):
                return _rng.choice(xs)
        """, rules=["RC004"])
        assert fs == []


# =====================================================================
# RC005 — thread hygiene
# =====================================================================

class TestRC005:
    def test_thread_without_daemon(self, tmp_path):
        fs = _scan(tmp_path, "mod.py", """
            import threading

            def go():
                threading.Thread(target=print).start()
        """, rules=["RC005"])
        assert _details(fs) == [("RC005", "thread-no-daemon")]

    def test_explicit_daemon_is_clean(self, tmp_path):
        fs = _scan(tmp_path, "mod.py", """
            import threading

            def go():
                threading.Thread(target=print, daemon=True).start()
                threading.Thread(target=print, daemon=False).start()
        """, rules=["RC005"])
        assert fs == []

    def test_stop_without_join(self, tmp_path):
        fs = _scan(tmp_path, "mod.py", """
            import threading

            class Pump:
                def __init__(self):
                    self._thread = threading.Thread(
                        target=self._run, daemon=True)

                def stop(self):
                    self._stop.set()
        """, rules=["RC005"])
        assert _details(fs) == [("RC005", "missing-join:stop")]

    def test_stop_with_join_is_clean(self, tmp_path):
        fs = _scan(tmp_path, "mod.py", """
            import threading

            class Pump:
                def __init__(self):
                    self._thread = threading.Thread(
                        target=self._run, daemon=True)

                def stop(self):
                    self._stop.set()
                    self._thread.join(timeout=5)
        """, rules=["RC005"])
        assert fs == []

    def test_suppression_on_comment_line_above(self, tmp_path):
        fs = _scan(tmp_path, "mod.py", """
            import threading

            class Pump:
                def __init__(self):
                    self._thread = threading.Thread(
                        target=self._run, daemon=True)

                # user code may never observe the stop event —
                # raycheck: disable=RC005
                def stop(self):
                    self._stop.set()
        """, rules=["RC005"])
        assert fs == []


# =====================================================================
# baseline mechanics
# =====================================================================

class TestBaseline:
    def test_baseline_hides_then_goes_stale(self, tmp_path):
        src = """
            import time

            async def handler():
                time.sleep(1)
        """
        p = tmp_path / "mod.py"
        p.write_text(textwrap.dedent(src))
        mods = load_modules([str(tmp_path)], root=str(tmp_path))
        findings = analyze(mods, rules=["RC001"])
        assert len(findings) == 1
        bl = tmp_path / "baseline.json"
        baseline_mod.save(str(bl), findings)
        new, old, stale = run([str(p)], baseline_path=str(bl),
                              rules=["RC001"], root=str(tmp_path))
        assert new == [] and len(old) == 1 and stale == []
        # fix the finding: the baseline entry must surface as stale
        p.write_text("async def handler():\n    return 1\n")
        new, old, stale = run([str(p)], baseline_path=str(bl),
                              rules=["RC001"], root=str(tmp_path))
        assert new == [] and old == [] and len(stale) == 1

    def test_checked_in_baseline_is_small(self):
        with open(os.path.join(REPO, "tools", "raycheck",
                               "baseline.json")) as f:
            data = json.load(f)
        total = sum(e.get("count", 1) for e in data["findings"])
        assert total <= 10, \
            f"baseline grew to {total} grandfathered findings (max 10) — " \
            f"fix findings instead of baselining them"


# =====================================================================
# RC001 x collective v2 — blocking shm waits must never become
# reachable from inline RPC handlers (PR-11 satellite)
# =====================================================================

class TestRC001CollectiveV2:
    def test_collective_op_from_inline_handler_is_flagged(self, tmp_path):
        """Wiring a v2 executor op into an inline handler is the exact
        regression this rule guards: every collective op rendezvouses
        with peer ranks and spins on shm counters."""
        fs = _scan(tmp_path, "mod.py", """
            class Server:
                def __init__(self, srv, group):
                    self._group = group
                    srv.register("Reduce", self._reduce, inline=True)

                def _reduce(self, arr):
                    return self._group.allreduce(arr)
        """, rules=["RC001"])
        assert ("RC001", "inline:collective.allreduce") in _details(fs)

    def test_arena_spin_reachable_from_inline_handler_is_flagged(
            self, tmp_path):
        """The arena-wait idiom (spin-then-nap on shm counters) reached
        transitively from an inline handler — the time.sleep inside the
        wait loop is the tell."""
        fs = _scan(tmp_path, "mod.py", """
            import time

            class Exec:
                def __init__(self, srv):
                    srv.register("Gather", self._gather, inline=True)

                def _gather(self):
                    self._wait_posted()
                    return 1

                def _wait_posted(self):
                    while not self._done():
                        time.sleep(0.0001)

                def _done(self):
                    return True
        """, rules=["RC001"])
        assert ("RC001", "inline:time.sleep") in _details(fs)

    def test_executor_methods_off_loop_are_clean(self, tmp_path):
        # the same executor shape invoked from plain sync code (actor
        # method, not a loop handler) is NOT a finding
        fs = _scan(tmp_path, "mod.py", """
            class Member:
                def run(self, group, arr):
                    return group.allreduce(arr)
        """, rules=["RC001"])
        assert fs == []

    def test_v2_tree_has_no_loop_reachable_shm_waits(self):
        """The shipped v2 executors themselves: zero RC001 findings —
        no blocking shm wait is reachable from any inline RPC handler
        (or async def) in the new subsystem."""
        mods = load_modules(
            [os.path.join(REPO, "ray_tpu", "util", "collective")],
            root=REPO)
        fs = [f for f in analyze(mods, rules=["RC001"])]
        assert fs == [], "\n".join(f.render() for f in fs)


# =====================================================================
# RC006 — resource lifecycle (CFG path-sensitive acquire/release)
# =====================================================================

class TestRC006:
    def test_early_return_leaks_lock(self, tmp_path):
        fs = _scan(tmp_path, "ray_tpu/m.py", """
            def f(cond):
                self_lock.acquire()
                if cond:
                    return 1
                self_lock.release()
                return 2
        """, rules=["RC006"])
        assert _details(fs) == [("RC006", "unreleased:self_lock")]

    def test_exception_path_leaks_lock(self, tmp_path):
        # work() raising escapes the function with the lock held: the
        # CFG's exception edges catch the path a happy-path reviewer
        # doesn't see
        fs = _scan(tmp_path, "ray_tpu/m.py", """
            def f():
                my_lock.acquire()
                work()
                my_lock.release()
        """, rules=["RC006"])
        assert _details(fs) == [("RC006", "unreleased:my_lock")]

    def test_try_finally_release_is_clean(self, tmp_path):
        fs = _scan(tmp_path, "ray_tpu/m.py", """
            def f():
                my_lock.acquire()
                try:
                    work()
                finally:
                    my_lock.release()
        """, rules=["RC006"])
        assert fs == []

    def test_while_true_has_no_fallthrough_exit(self, tmp_path):
        # `while True:` only exits via break/return/raise — the cond
        # node must not fabricate a normal fall-through path that
        # "leaks" the lock the in-loop return correctly releases
        # (review finding)
        fs = _scan(tmp_path, "ray_tpu/m.py", """
            def f(flag):
                my_lock.acquire()
                while True:
                    if flag:
                        my_lock.release()
                        return
        """, rules=["RC006"])
        assert fs == []

    def test_break_routes_through_finally(self, tmp_path):
        # a break out of a try/finally still runs the finally: code
        # that releases there is CORRECT and must not be flagged
        # (review finding: break/continue used to bypass finallys)
        fs = _scan(tmp_path, "ray_tpu/m.py", """
            def f(items):
                for it in items:
                    my_lock.acquire()
                    try:
                        if work(it):
                            break
                    finally:
                        my_lock.release()
        """, rules=["RC006"])
        assert fs == []

    def test_unclosed_client_on_success_path(self, tmp_path):
        fs = _scan(tmp_path, "ray_tpu/m.py", """
            def f(addr):
                c = RpcClient(addr)
                return c.call("Ping")
        """, rules=["RC006"])
        assert _details(fs) == [("RC006", "unclosed:c")]

    def test_closed_client_is_clean(self, tmp_path):
        fs = _scan(tmp_path, "ray_tpu/m.py", """
            def f(addr):
                c = RpcClient(addr)
                try:
                    return c.call("Ping")
                finally:
                    c.close()
        """, rules=["RC006"])
        assert fs == []

    def test_escaped_client_is_callers_problem(self, tmp_path):
        fs = _scan(tmp_path, "ray_tpu/m.py", """
            def f(self, addr):
                c = RpcClient(addr)
                self._clients[addr] = c
                return c
        """, rules=["RC006"])
        assert fs == []

    def test_nondaemon_thread_must_join(self, tmp_path):
        fs = _scan(tmp_path, "ray_tpu/m.py", """
            import threading

            def f():
                t = threading.Thread(target=work, daemon=False)
                t.start()
        """, rules=["RC006"])
        assert _details(fs) == [("RC006", "unjoined:t")]

    def test_joined_thread_is_clean(self, tmp_path):
        fs = _scan(tmp_path, "ray_tpu/m.py", """
            import threading

            def f():
                t = threading.Thread(target=work, daemon=False)
                t.start()
                t.join(timeout=5)
        """, rules=["RC006"])
        assert fs == []

    def test_handles_not_tracked_in_tests_tree(self, tmp_path):
        # test fixtures park cleanup in finalizers the analysis can't
        # see — handle tracking is runtime-tree only
        fs = _scan(tmp_path, "tests/test_x.py", """
            def f(addr):
                c = RpcClient(addr)
                return c.call("Ping")
        """, rules=["RC006"])
        assert fs == []

    def test_suppression(self, tmp_path):
        fs = _scan(tmp_path, "ray_tpu/m.py", """
            def f(addr):
                # process-lifetime client — raycheck: disable=RC006
                c = RpcClient(addr)
                return c.call("Ping")
        """, rules=["RC006"])
        assert fs == []


# =====================================================================
# RC007 — static lockset race detection
# =====================================================================

class TestRC007:
    SCOPED = "ray_tpu/_private/memory_store.py"

    def test_cross_context_rmw_without_lock(self, tmp_path):
        """io-loop RMW vs thread-context RMW on the same attr, no
        common lock: the Eraser shape."""
        fs = _scan(tmp_path, self.SCOPED, """
            import threading

            class Store:
                def __init__(self):
                    self._t = threading.Thread(
                        target=self._drain, daemon=True)

                async def put(self, x):
                    self.items.append(x)

                def _drain(self):
                    self.items.pop()
        """, rules=["RC007"])
        assert ("RC007", "race:items") in _details(fs)

    def test_common_lock_is_clean(self, tmp_path):
        fs = _scan(tmp_path, self.SCOPED, """
            import threading

            class Store:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._t = threading.Thread(
                        target=self._drain, daemon=True)

                async def put(self, x):
                    with self._lock:
                        self.items.append(x)

                def _drain(self):
                    with self._lock:
                        self.items.pop()
        """, rules=["RC007"])
        assert fs == []

    def test_inconsistent_discipline_flagged(self, tmp_path):
        """One side locks, a cross-context WRITE doesn't: half-locked
        state is the PR-7/PR-8 bug family."""
        fs = _scan(tmp_path, self.SCOPED, """
            import threading

            class Store:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._t = threading.Thread(
                        target=self._drain, daemon=True)

                async def put(self, x):
                    self.items = x

                def _drain(self):
                    with self._lock:
                        return self.items
        """, rules=["RC007"])
        assert ("RC007", "race:items") in _details(fs)

    def test_same_context_not_flagged(self, tmp_path):
        # two io-loop coroutines interleave only at awaits: dict/list
        # ops between them are loop-serialized
        fs = _scan(tmp_path, self.SCOPED, """
            class Store:
                async def put(self, x):
                    self.items.append(x)

                async def take(self):
                    return self.items.pop()
        """, rules=["RC007"])
        assert fs == []

    def test_init_writes_are_construction(self, tmp_path):
        fs = _scan(tmp_path, self.SCOPED, """
            import threading

            class Store:
                def __init__(self):
                    self.items = []
                    self._t = threading.Thread(
                        target=self._drain, daemon=True)

                def _drain(self):
                    self.items.pop()
        """, rules=["RC007"])
        assert fs == []

    def test_synced_types_are_exempt(self, tmp_path):
        # Queue/deque/Lock-valued attrs synchronize themselves
        fs = _scan(tmp_path, self.SCOPED, """
            import collections
            import threading

            class Store:
                def __init__(self):
                    self.q = collections.deque()
                    self._t = threading.Thread(
                        target=self._drain, daemon=True)

                async def put(self, x):
                    self.q.append(x)

                def _drain(self):
                    self.q.popleft()
        """, rules=["RC007"])
        assert fs == []

    def test_out_of_scope_module_not_scanned(self, tmp_path):
        fs = _scan(tmp_path, "ray_tpu/util/thing.py", """
            import threading

            class Store:
                def __init__(self):
                    self._t = threading.Thread(
                        target=self._drain, daemon=True)

                async def put(self, x):
                    self.items.append(x)

                def _drain(self):
                    self.items.pop()
        """, rules=["RC007"])
        assert fs == []

    def test_suppression(self, tmp_path):
        fs = _scan(tmp_path, self.SCOPED, """
            import threading

            class Store:
                def __init__(self):
                    self._t = threading.Thread(
                        target=self._drain, daemon=True)

                async def put(self, x):
                    # single-writer by design — raycheck: disable=RC007
                    self.items.append(x)

                def _drain(self):
                    self.items.pop()
        """, rules=["RC007"])
        assert _details(fs) == [("RC007", "race:items")]  # _drain side
        assert fs[0].scope == "Store._drain"


# =====================================================================
# RC008 — protocol conformance (checked-in transition tables)
# =====================================================================

class TestRC008:
    GCS = "ray_tpu/_private/gcs/server.py"

    def test_unknown_state_typo(self, tmp_path):
        fs = _scan(tmp_path, self.GCS, """
            def check(actor):
                if actor.state == "ALVIE":
                    return 1
        """, rules=["RC008"])
        assert _details(fs) == [("RC008", "unknown-state:ALVIE")]

    def test_illegal_transition_dead_to_alive_actor(self, tmp_path):
        # DEAD is terminal for actors: a killed actor must never be
        # resurrected by a late registration
        fs = _scan(tmp_path, self.GCS, """
            def revive(actor):
                if actor.state == "DEAD":
                    actor.state = "ALIVE"
        """, rules=["RC008"])
        assert _details(fs) == [("RC008", "illegal:DEAD->ALIVE")]

    def test_legal_transition_clean(self, tmp_path):
        fs = _scan(tmp_path, self.GCS, """
            def promote(actor):
                if actor.state == "PENDING":
                    actor.state = "ALIVE"

            def fail(actor):
                if actor.state == "ALIVE":
                    actor.state = "RESTARTING"
        """, rules=["RC008"])
        assert fs == []

    def test_unknown_pre_state_not_flagged(self, tmp_path):
        # no dominating guard: the pre-state is the callers' contract
        fs = _scan(tmp_path, self.GCS, """
            def kill(actor):
                actor.state = "DEAD"
        """, rules=["RC008"])
        assert fs == []

    def test_early_terminal_guard_establishes_fact(self, tmp_path):
        # `if actor.state != "PENDING": return` pins PENDING afterwards
        fs = _scan(tmp_path, self.GCS, """
            def promote(actor):
                if actor.state != "PENDING":
                    return
                actor.state = "ALIVE"

            def bad(actor):
                if actor.state != "DEAD":
                    return
                actor.state = "ALIVE"
        """, rules=["RC008"])
        assert _details(fs) == [("RC008", "illegal:DEAD->ALIVE")]

    def test_heartbeat_resurrection_shape(self, tmp_path):
        """The PR-8 bug, reduced: reviving a dead node without testing
        the heartbeat's draining flag is the resurrection bug; with the
        guard it is a legal health-check recovery."""
        fs = _scan(tmp_path, self.GCS, """
            async def heartbeat_bad(self, node, draining=False):
                if not node.alive:
                    node.alive = True
                    node.draining = False

            async def heartbeat_good(self, node, draining=False):
                if not node.alive:
                    if draining:
                        return {"ok": True, "shutdown": True}
                    node.alive = True
                    node.draining = False
        """, rules=["RC008"])
        assert _details(fs) == [("RC008", "unguarded:DEAD->ALIVE")]
        assert fs[0].scope == "heartbeat_bad"

    def test_assignment_invalidates_stale_facts(self, tmp_path):
        """After `actor.state = "DEAD"` the earlier `== "PENDING"` fact
        is stale: the second assignment is DEAD->ALIVE (illegal), not
        PENDING->ALIVE (review finding: facts used to survive the
        assignment, hiding the violation)."""
        fs = _scan(tmp_path, self.GCS, """
            def flow(actor):
                if actor.state == "PENDING":
                    actor.state = "DEAD"
                    notify(actor)
                    actor.state = "ALIVE"
        """, rules=["RC008"])
        assert _details(fs) == [("RC008", "illegal:DEAD->ALIVE")]

    def test_raylet_never_undrains(self, tmp_path):
        fs = _scan(tmp_path, "ray_tpu/_private/raylet/raylet.py", """
            class Raylet:
                def __init__(self):
                    self.draining = False

                def oops(self):
                    if self.draining:
                        self.draining = False
        """, rules=["RC008"])
        assert _details(fs) == [("RC008", "illegal:DRAINING->RUNNING")]

    def test_lease_warmth_never_revoked(self, tmp_path):
        fs = _scan(tmp_path, "ray_tpu/_private/core_worker.py", """
            def chill(entry):
                if entry.warm:
                    if entry.busy:
                        entry.warm = False
        """, rules=["RC008"])
        assert _details(fs) == [("RC008", "illegal:BUSY_WARM->BUSY_COLD")]

    def test_suppression(self, tmp_path):
        fs = _scan(tmp_path, self.GCS, """
            def revive(actor):
                if actor.state == "DEAD":
                    actor.state = "ALIVE"  # raycheck: disable=RC008
        """, rules=["RC008"])
        assert fs == []


class TestRC008Membership:
    """The elastic-collective membership machine: the resize cycle
    ACTIVE -> DRAINING_RANK -> RESIZED -> ACTIVE only moves forward.
    State constants are module-level names, exercising the constant
    resolution RC008 grew alongside this machine."""

    MEM = "ray_tpu/util/collective/v2/membership.py"
    # indented to match the test bodies so the concatenation dedents
    # as one block
    CONSTS = """
            ACTIVE = "ACTIVE"
            DRAINING_RANK = "DRAINING_RANK"
            RESIZED = "RESIZED"
    """

    def test_legal_cycle_is_clean(self, tmp_path):
        fs = _scan(tmp_path, self.MEM, self.CONSTS + """
            class GroupMembership:
                def __init__(self):
                    self.state = ACTIVE

                def flag(self):
                    if self.state == ACTIVE:
                        self.state = DRAINING_RANK

                def commit(self):
                    if self.state != DRAINING_RANK:
                        return
                    self.state = RESIZED

                def reactivate(self):
                    if self.state == RESIZED:
                        self.state = ACTIVE
        """, rules=["RC008"])
        assert fs == []

    def test_resize_shortcut_is_illegal(self, tmp_path):
        """Skipping the flag pass (ACTIVE -> RESIZED) would bump the
        epoch without ever recording who left — a silent resize."""
        fs = _scan(tmp_path, self.MEM, self.CONSTS + """
            def shortcut(mem):
                if mem.state == ACTIVE:
                    mem.state = RESIZED
        """, rules=["RC008"])
        assert _details(fs) == [("RC008", "illegal:ACTIVE->RESIZED")]

    def test_backwards_edge_is_illegal(self, tmp_path):
        """RESIZED -> DRAINING_RANK re-opens a committed resize: the
        epoch an in-flight op pinned would no longer be immutable."""
        fs = _scan(tmp_path, self.MEM, self.CONSTS + """
            def reopen(mem):
                if mem.state == RESIZED:
                    mem.state = DRAINING_RANK
        """, rules=["RC008"])
        assert _details(fs) == [
            ("RC008", "illegal:RESIZED->DRAINING_RANK")]

    def test_unknown_state_literal(self, tmp_path):
        fs = _scan(tmp_path, self.MEM, self.CONSTS + """
            def typo(mem):
                if mem.state == "ACTVE":
                    mem.state = RESIZED
        """, rules=["RC008"])
        assert ("RC008", "unknown-state:ACTVE") in _details(fs)

    def test_live_membership_module_is_clean(self):
        """The checked-in GroupMembership conforms to its own table."""
        import tools.raycheck.protocol as proto
        from tools.raycheck.rules import SourceModule

        path = os.path.join(REPO, self.MEM)
        with open(path) as f:
            mod = SourceModule(path, self.MEM, f.read())
        fs = proto.check_rc008([mod])
        assert fs == []


# =====================================================================
# RC009 — observability name conformance
# =====================================================================

class TestRC009:
    SCHEMA = 'EVENT_TYPES = {"span": "s", "task_state": "t"}\n'

    def _write_schema(self, tmp_path):
        p = tmp_path / "ray_tpu" / "observability" / "schema.py"
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(self.SCHEMA)

    def test_flags_undeclared_event_literal(self, tmp_path):
        self._write_schema(tmp_path)
        fs = _scan(tmp_path, "mod.py", """
            from ray_tpu.observability import events as obs_events

            def f():
                obs_events.record_event("task_stat", x=1)
        """, rules=["RC009"])
        assert _details(fs) == [("RC009", "undeclared-event:task_stat")]

    def test_declared_literal_and_variable_are_clean(self, tmp_path):
        self._write_schema(tmp_path)
        fs = _scan(tmp_path, "mod.py", """
            from ray_tpu.observability import events as obs_events

            def f(etype):
                obs_events.record_event("task_state", x=1)
                obs_events.record_event(etype, x=1)
        """, rules=["RC009"])
        assert fs == []

    def test_flags_fstring_span_name(self, tmp_path):
        fs = _scan(tmp_path, "mod.py", """
            from ray_tpu.observability import tracing as obs_tracing

            def f(op):
                with obs_tracing.span(f"collective.{op}"):
                    pass
        """, rules=["RC009"])
        assert _details(fs) == [("RC009", "dynamic-name:span")]

    def test_flags_concat_metric_name(self, tmp_path):
        fs = _scan(tmp_path, "mod.py", """
            from ray_tpu.util.metrics import get_histogram

            def f(kind):
                get_histogram("lat_" + kind, description="d",
                              boundaries=(1,), tag_keys=())
        """, rules=["RC009"])
        assert _details(fs) == [("RC009", "dynamic-name:get_histogram")]

    def test_interned_lookup_is_clean(self, tmp_path):
        """The sanctioned pattern: names come out of a table somebody
        owns (observability/collective.py::_span_name)."""
        fs = _scan(tmp_path, "mod.py", """
            from ray_tpu.observability import tracing as obs_tracing

            def _span_name(op):
                return "collective." + op

            def f(op):
                with obs_tracing.span(_span_name(op)):
                    pass
        """, rules=["RC009"])
        assert fs == []

    @pytest.mark.parametrize("call", ["setup_phase", "record_setup_phase"])
    def test_flags_an_undeclared_setup_phase_literal(self, tmp_path, call):
        """A set-up phase's name is held to SETUP_PHASES as an event's
        type is to EVENT_TYPES; a declared literal and a variable pass."""
        p = tmp_path / "ray_tpu" / "observability" / "schema.py"
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(self.SCHEMA
                     + 'SETUP_PHASES = {"ray_tpu.setup.init": "driver"}\n')
        fs = _scan(tmp_path, "mod.py", f"""
            from ray_tpu.observability import timeline as obs_timeline
            from ray_tpu.observability.timeline import {call}

            def f(name, op):
                obs_timeline.{call}("ray_tpu.setup.init")
                {call}(name)
                {call}("ray_tpu.setup.innit")
                obs_timeline.{call}(f"ray_tpu.setup.{{op}}")
        """, rules=["RC009"])
        assert _details(fs) == [
            ("RC009", "undeclared-phase:ray_tpu.setup.innit"),
            ("RC009", f"dynamic-name:{call}")]

    def test_missing_schema_skips_membership_only(self, tmp_path):
        """No schema in the analyzed tree: membership checks are
        skipped (partial trees must stay lintable), dynamic-name checks
        still fire."""
        fs = _scan(tmp_path, "mod.py", """
            from ray_tpu.observability import events as obs_events

            def f(op):
                obs_events.record_event("never_declared", x=1)
                obs_events.record_event(f"ev.{op}", x=1)
        """, rules=["RC009"])
        assert _details(fs) == [("RC009", "dynamic-name:record_event")]

    def test_suppression(self, tmp_path):
        self._write_schema(tmp_path)
        fs = _scan(tmp_path, "mod.py", """
            from ray_tpu.observability import events as obs_events

            def f():
                obs_events.record_event("oddball")  # raycheck: disable=RC009
        """, rules=["RC009"])
        assert fs == []


# =====================================================================
# interprocedural RC001 — whole-program reachability (v2 tentpole)
# =====================================================================

class TestRC001Interprocedural:
    def test_cross_module_reachability(self, tmp_path):
        """v1's same-module depth-3 walk could not see this: the inline
        handler's blocking sleep lives two modules away."""
        (tmp_path / "helpers.py").write_text(textwrap.dedent("""
            import time

            def deep_wait():
                time.sleep(0.2)
        """))
        (tmp_path / "middle.py").write_text(textwrap.dedent("""
            from helpers import deep_wait

            def relay():
                deep_wait()
        """))
        (tmp_path / "server.py").write_text(textwrap.dedent("""
            from middle import relay

            class S:
                def __init__(self, srv):
                    srv.register("Q", self._q, inline=True)

                def _q(self):
                    relay()
        """))
        from tools.raycheck.rules import analyze as _an, \
            load_modules as _lm
        mods = _lm([str(tmp_path)], root=str(tmp_path))
        fs = _an(mods, rules=["RC001"])
        assert ("RC001", "inline:time.sleep") in _details(fs)
        [f] = [f for f in fs if f.detail == "inline:time.sleep"]
        assert f.path == "helpers.py"
        # the finding carries the whole call chain for --json/CI
        assert list(f.chain) == ["S._q", "relay", "deep_wait"]

    def test_depth_beyond_three_still_caught(self, tmp_path):
        """v1 cut reachability at depth 3; v2 is unbounded — the old
        finding set is a strict subset of the new one."""
        src = textwrap.dedent("""
            import time

            class S:
                def __init__(self, srv):
                    srv.register("Q", self._q, inline=True)

                def _q(self):
                    hop0()
        """)
        src += "\n".join(
            f"\ndef hop{i}():\n    hop{i + 1}()\n" for i in range(6))
        src += "\ndef hop6():\n    time.sleep(1)\n"
        p = tmp_path / "mod.py"
        p.write_text(src)
        mods = load_modules([str(tmp_path)], root=str(tmp_path))
        fs = analyze(mods, rules=["RC001"])
        assert ("RC001", "inline:time.sleep") in _details(fs)
        [f] = [f for f in fs if f.detail == "inline:time.sleep"]
        assert list(f.chain) == \
            ["S._q"] + [f"hop{i}" for i in range(7)]


# =====================================================================
# regression guards — the two shipped bugs must stay lintable
# =====================================================================

class TestRegressionGuards:
    def test_deleting_pr8_heartbeat_guard_fails_lint(self, tmp_path):
        """Acceptance criterion: textually delete the PR-8
        drain-completion guard from the REAL gcs/server.py and RC008
        must fail the lint."""
        real = os.path.join(REPO, "ray_tpu", "_private", "gcs",
                            "server.py")
        src = open(real).read()
        import re as _re
        cut = _re.sub(
            r"\n +if draining:\n( +#[^\n]*\n)* +return "
            r"\{\"ok\": True, \"shutdown\": True\}\n",
            "\n", src, count=1)
        assert cut != src, \
            "heartbeat guard not found — did Heartbeat get refactored?"
        p = tmp_path / "ray_tpu" / "_private" / "gcs" / "server.py"
        p.parent.mkdir(parents=True)
        p.write_text(cut)
        r = subprocess.run(
            [sys.executable, "-m", "tools.raycheck", str(p),
             "--no-baseline", "--no-cache", "--rules", "RC008"],
            capture_output=True, text=True, cwd=REPO, timeout=120)
        assert r.returncode == 1 and "RC008" in r.stdout and \
            "resurrection" in r.stdout, r.stdout + r.stderr
        # and the UNMODIFIED file stays clean
        r2 = subprocess.run(
            [sys.executable, "-m", "tools.raycheck", real,
             "--no-baseline", "--no-cache", "--rules", "RC008"],
            capture_output=True, text=True, cwd=REPO, timeout=120)
        assert r2.returncode == 0, r2.stdout + r2.stderr

    def test_reintroducing_pr7_lock_held_teardown_fails_lint(
            self, tmp_path):
        """Acceptance criterion: the PR-7 livelock shape (closing
        clients while holding the module lock the io loop needs) must
        exit non-zero."""
        p = tmp_path / "_private" / "mod.py"
        p.parent.mkdir(parents=True)
        p.write_text(textwrap.dedent("""
            import threading

            _client_lock = threading.Lock()
            _clients = {}

            def clear_client_cache():
                with _client_lock:
                    for c in _clients.values():
                        c.close()
                    _clients.clear()
        """))
        r = subprocess.run(
            [sys.executable, "-m", "tools.raycheck", str(p),
             "--no-baseline", "--no-cache"],
            capture_output=True, text=True, cwd=REPO, timeout=120)
        assert r.returncode == 1 and "RC002" in r.stdout, \
            r.stdout + r.stderr


# =====================================================================
# cache + CLI --json + wall clock
# =====================================================================

class TestCache:
    def test_cache_hit_identical_findings(self, tmp_path):
        """Satellite acceptance: a cache hit must produce findings
        byte-identical to a cold run."""
        src_dir = tmp_path / "src"
        src_dir.mkdir()
        (src_dir / "mod.py").write_text(textwrap.dedent("""
            import time

            async def handler():
                time.sleep(1)

            def leak(cond):
                a_lock.acquire()
                if cond:
                    return
                a_lock.release()
        """))
        from tools.raycheck import analyze_paths
        n_cold, cold = analyze_paths([str(src_dir)],
                                     root=str(tmp_path), use_cache=False)
        n_w1, warm1 = analyze_paths([str(src_dir)],
                                    root=str(tmp_path), use_cache=True)
        n_w2, warm2 = analyze_paths([str(src_dir)],
                                    root=str(tmp_path), use_cache=True)
        assert (tmp_path / ".raycheck_cache").is_dir()
        for warm in (warm1, warm2):
            assert [f.as_json() for f in warm] == \
                [f.as_json() for f in cold]
        assert n_cold == n_w1 == n_w2

    def test_file_count_stable_with_unparseable_file(self, tmp_path):
        # a syntax-error file is skipped by the analysis; the reported
        # file count must be identical on cold, cache-miss and
        # cache-hit runs (review finding: the hit path used to count
        # raw inputs, not parsed ones)
        src_dir = tmp_path / "src"
        src_dir.mkdir()
        (src_dir / "ok.py").write_text("def f():\n    return 1\n")
        (src_dir / "broken.py").write_text("def f(:\n")
        from tools.raycheck import analyze_paths
        n_cold, _ = analyze_paths([str(src_dir)], root=str(tmp_path),
                                  use_cache=False)
        n_miss, _ = analyze_paths([str(src_dir)], root=str(tmp_path),
                                  use_cache=True)
        n_hit, _ = analyze_paths([str(src_dir)], root=str(tmp_path),
                                 use_cache=True)
        assert n_cold == n_miss == n_hit == 1

    def test_edit_invalidates(self, tmp_path):
        src_dir = tmp_path / "src"
        src_dir.mkdir()
        p = src_dir / "mod.py"
        p.write_text("async def h():\n    return 1\n")
        from tools.raycheck import analyze_paths
        _, fs = analyze_paths([str(src_dir)], root=str(tmp_path),
                              use_cache=True)
        assert fs == []
        p.write_text("import time\n\nasync def h():\n    time.sleep(1)\n")
        _, fs2 = analyze_paths([str(src_dir)], root=str(tmp_path),
                               use_cache=True)
        assert [f.detail for f in fs2] == ["async:time.sleep"]

    def test_warm_lint_wall_clock_budget(self):
        """Acceptance: warm-cache `make lint` ≤ 30 s on this box (it
        runs in well under 10; the margin absorbs CI noise)."""
        import time as _time
        cmd = [sys.executable, "-m", "tools.raycheck",
               "ray_tpu/", "tests/", "-q"]
        subprocess.run(cmd, capture_output=True, cwd=REPO, timeout=120)
        t0 = _time.monotonic()
        r = subprocess.run(cmd, capture_output=True, text=True,
                           cwd=REPO, timeout=120)
        dt = _time.monotonic() - t0
        assert r.returncode == 0, r.stdout + r.stderr
        assert dt <= 30.0, f"warm `make lint` took {dt:.1f}s (> 30s)"


class TestJsonOutput:
    def test_json_findings_schema(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(textwrap.dedent("""
            import time

            class S:
                def __init__(self, srv):
                    srv.register("Q", self._q, inline=True)

                def _q(self):
                    self._helper()

                def _helper(self):
                    time.sleep(1)
        """))
        r = subprocess.run(
            [sys.executable, "-m", "tools.raycheck", str(bad),
             "--no-baseline", "--no-cache", "--json",
             "--rules", "RC001"],
            capture_output=True, text=True, cwd=REPO, timeout=120)
        assert r.returncode == 1, r.stdout + r.stderr
        doc = json.loads(r.stdout)
        assert doc["files"] == 1 and doc["stale_baseline"] == []
        [f] = doc["findings"]
        assert f["rule"] == "RC001"
        assert f["fingerprint"].startswith("RC001|")
        assert f["line"] > 0 and f["path"].endswith("bad.py")
        # the interprocedural context chain rides along for CI diffing
        assert f["chain"] == ["S._q", "S._helper"]

    def test_json_clean_exit_zero(self, tmp_path):
        ok = tmp_path / "ok.py"
        ok.write_text("def f():\n    return 1\n")
        r = subprocess.run(
            [sys.executable, "-m", "tools.raycheck", str(ok),
             "--no-baseline", "--no-cache", "--json"],
            capture_output=True, text=True, cwd=REPO, timeout=120)
        assert r.returncode == 0
        doc = json.loads(r.stdout)
        assert doc["findings"] == []


# =====================================================================
# live tree + CLI — the tier-1 enforcement point
# =====================================================================

class TestLiveTree:
    def test_live_tree_is_clean(self):
        """Zero non-baselined findings across ALL rules — including the
        v2 interprocedural ones (RC006/RC007/RC008), which run by
        default and whose genuine pre-PR findings were FIXED, not
        baselined."""
        from tools.raycheck.rules import RULE_DOCS, builtin_rules
        assert set(builtin_rules()) == set(RULE_DOCS) and \
            {"RC006", "RC007", "RC008"} <= set(RULE_DOCS), \
            "the interprocedural rules must be registered by default"
        new, _old, stale = run(
            [os.path.join(REPO, "ray_tpu"), os.path.join(REPO, "tests")],
            baseline_path=os.path.join(REPO, "tools", "raycheck",
                                       "baseline.json"),
            root=REPO)
        assert new == [], "raycheck findings on the live tree:\n" + \
            "\n".join(f.render() for f in new)
        assert stale == [], \
            f"stale baseline entries (regenerate the baseline): {stale}"

    def test_cli_exit_codes(self, tmp_path):
        # clean file -> 0; regression (inline sleep = the PR-7 latency
        # contract) -> 1
        clean = tmp_path / "clean.py"
        clean.write_text("def ok():\n    return 1\n")
        r = subprocess.run(
            [sys.executable, "-m", "tools.raycheck", str(clean),
             "--no-baseline"],
            capture_output=True, text=True, cwd=REPO, timeout=120)
        assert r.returncode == 0, r.stdout + r.stderr
        bad = tmp_path / "bad.py"
        bad.write_text(textwrap.dedent("""
            import time

            class S:
                def __init__(self, srv):
                    srv.register("Q", self._q, inline=True)

                def _q(self):
                    time.sleep(1)
        """))
        r = subprocess.run(
            [sys.executable, "-m", "tools.raycheck", str(bad),
             "--no-baseline"],
            capture_output=True, text=True, cwd=REPO, timeout=120)
        assert r.returncode == 1 and "RC001" in r.stdout, \
            r.stdout + r.stderr


# =====================================================================
# RAY_TPU_DEBUG_LOCKS dynamic proxy — validates RC002's model
# =====================================================================

class TestDebugLocks:
    def test_cycle_forming_acquisition_raises(self):
        from ray_tpu._private import debug_locks

        debug_locks.order_graph().reset()
        A = debug_locks.DebugLock(threading.Lock(), "A")
        B = debug_locks.DebugLock(threading.Lock(), "B")
        with A:
            with B:
                pass
        with pytest.raises(debug_locks.LockOrderError):
            with B:
                with A:
                    pass
        debug_locks.order_graph().reset()

    def test_cycle_detected_across_threads(self):
        from ray_tpu._private import debug_locks

        debug_locks.order_graph().reset()
        A = debug_locks.DebugLock(threading.Lock(), "tA")
        B = debug_locks.DebugLock(threading.Lock(), "tB")

        def t1():
            with A:
                with B:
                    pass

        th = threading.Thread(target=t1, daemon=True)
        th.start()
        th.join(timeout=5)
        errs = []

        def t2():
            try:
                with B:
                    with A:
                        pass
            except debug_locks.LockOrderError as e:
                errs.append(e)

        th = threading.Thread(target=t2, daemon=True)
        th.start()
        th.join(timeout=5)
        assert len(errs) == 1, "opposite-order acquisition on another " \
                               "thread must raise LockOrderError"
        debug_locks.order_graph().reset()

    def test_reentrant_rlock_is_not_a_cycle(self):
        from ray_tpu._private import debug_locks

        debug_locks.order_graph().reset()
        R = debug_locks.DebugLock(threading.RLock(), "R")
        with R:
            with R:  # re-entrant: legal, no self-edge
                pass
        debug_locks.order_graph().reset()

    def test_maybe_wrap_is_env_gated(self, monkeypatch):
        from ray_tpu._private import debug_locks

        raw = threading.Lock()
        monkeypatch.delenv("RAY_TPU_DEBUG_LOCKS", raising=False)
        assert debug_locks.maybe_wrap(raw, "x") is raw
        monkeypatch.setenv("RAY_TPU_DEBUG_LOCKS", "1")
        wrapped = debug_locks.maybe_wrap(raw, "x")
        assert isinstance(wrapped, debug_locks.DebugLock)
        # the proxy keeps the full Lock surface the codebase uses
        assert wrapped.acquire(timeout=1)
        assert wrapped.locked()
        wrapped.release()
        debug_locks.order_graph().reset()

    def test_cluster_boots_with_debug_locks(self):
        """End-to-end: the wired _private locks run wrapped without a
        false-positive LockOrderError on the normal task path."""
        code = textwrap.dedent("""
            import ray_tpu

            ray_tpu.init(num_cpus=2, ignore_reinit_error=True)

            @ray_tpu.remote
            def f(x):
                return x + 1

            assert ray_tpu.get([f.remote(i) for i in range(8)]) == \\
                list(range(1, 9))
            ray_tpu.shutdown()
            print("DEBUG_LOCKS_OK")
        """)
        env = dict(os.environ)
        env.update({"RAY_TPU_DEBUG_LOCKS": "1", "JAX_PLATFORMS": "cpu"})
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, timeout=180,
                           env=env, cwd=REPO)
        assert r.returncode == 0 and "DEBUG_LOCKS_OK" in r.stdout, \
            r.stdout[-2000:] + r.stderr[-2000:]
