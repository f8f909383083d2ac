"""Profiling: task timeline export + TPU (jax.profiler) hooks.

Reference: python/ray/_private/profiling.py (`ray.timeline` → Chrome
trace of task lifetimes from GcsTaskManager events) and the runtime-env
GPU profiler plugins (_private/runtime_env/nsight.py) — the TPU
equivalent wraps jax.profiler/xprof traces.
"""

from __future__ import annotations

import glob
import json
import os
import threading
from typing import Any, Dict, List, Optional


def timeline(filename: Optional[str] = None) -> Optional[List[Dict[str, Any]]]:
    """Chrome-trace events of task execution (open in chrome://tracing
    or Perfetto). Spans: queued (SUBMITTED→RUNNING) and execution
    (RUNNING→FINISHED/FAILED); tasks missing a RUNNING event fall back
    to one SUBMITTED→end span.

    Reference surface: ray.timeline(_private/profiling.py).
    """
    from ray_tpu.util.state import list_tasks

    by_task: Dict[str, Dict[str, dict]] = {}
    for ev in list_tasks(limit=20000):
        by_task.setdefault(ev["task_id"], {})[ev["state"]] = ev
    events: List[Dict[str, Any]] = []
    for tid, states in by_task.items():
        sub = states.get("SUBMITTED")
        run = states.get("RUNNING")
        end = states.get("FINISHED") or states.get("FAILED")
        name = (end or run or sub or {}).get("name", "?")
        failed = "FAILED" in states
        if sub and run:
            events.append({
                "name": f"queued:{name}", "cat": "queue", "ph": "X",
                "ts": sub["ts"] * 1e6,
                "dur": max(0.0, (run["ts"] - sub["ts"]) * 1e6),
                "pid": sub.get("job_id", "job"),
                "tid": run.get("worker", "worker"),
                "args": {"task_id": tid},
            })
        start = run or sub
        if start and end:
            events.append({
                "name": name, "cat": "task", "ph": "X",
                "ts": start["ts"] * 1e6,
                "dur": max(0.0, (end["ts"] - start["ts"]) * 1e6),
                "pid": start.get("job_id", "job"),
                "tid": (run or end).get("worker", "worker"),
                "args": {"task_id": tid, "state": end["state"],
                         "failed": failed},
            })
    if filename:
        with open(filename, "w") as f:
            json.dump(events, f)
        return None
    return events


# ---------------------------------------------------------------------------
# TPU device profiling (jax.profiler / xprof)
# ---------------------------------------------------------------------------
class ProfileError(RuntimeError):
    """A profile ended and wrote no trace."""


class _Profile:
    """The one profiler session a process may have open (JAX allows one).
    Only the process that holds the chip can trace it: this is the hook it
    exposes (``ray_tpu.tpu_profile`` in a train loop, ``profile_start`` /
    ``profile_stop`` on a Serve replica)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._logdir: Optional[str] = None
        self._before: set = set()

    @staticmethod
    def _xplanes(logdir: str) -> set:
        return set(glob.glob(os.path.join(
            logdir, "plugins", "profile", "*", "*.xplane.pb")))

    def start(self, logdir: str) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        # the Python tracer hooks every call of every thread: it slows the
        # host loop a trace is taken to observe, and grows without bound
        options.python_tracer_level = 0
        options.host_tracer_level = 2  # device_span()s and XLA's host events
        options.raise_error_on_start_failure = True
        with self._lock:
            if self._logdir is not None:
                raise RuntimeError(
                    f"a profile into {self._logdir} is already running")
            os.makedirs(logdir, exist_ok=True)
            before = self._xplanes(logdir)
            # a start that raises has set nothing here, and JAX keeps no
            # session it failed to create: the next start finds none
            jax.profiler.start_trace(logdir, profiler_options=options)
            self._logdir, self._before = logdir, before

    def stop(self) -> str:
        """End the session and return the ``.xplane.pb`` it wrote. State is
        cleared whatever happens: a stop that raised is not stopped again,
        and the next start succeeds."""
        import jax

        with self._lock:
            logdir, self._logdir = self._logdir, None
            if logdir is None:
                return ""
            try:
                jax.profiler.stop_trace()
            except BaseException:
                # JAX forgets its session only after a successful export
                self._drop_jax_session()
                raise
            written = self._xplanes(logdir) - self._before
        if not written:
            # seen on the chip (PERF.md, PR 22): a session that wrote
            # nothing and raised nothing. The window is the caller's to
            # take again; a silent empty directory is not an answer.
            raise ProfileError(f"the profile wrote no .xplane.pb under "
                               f"{logdir}")
        return max(written, key=os.path.getmtime)

    @staticmethod
    def _drop_jax_session() -> None:
        from jax._src import profiler as jax_profiler

        state = getattr(jax_profiler, "_profile_state", None)
        if state is not None:
            with state.lock:
                state.reset()


_profile = _Profile()


def start_tpu_profile(logdir: str) -> None:
    """Start a jax.profiler trace of this process (view in XProf /
    TensorBoard, or read with ``jax.profiler.ProfileData``): device
    operations, XLA's host events and the program's ``device_span``s, no
    Python tracer. Raises if the profiler cannot start."""
    _profile.start(logdir)


def stop_tpu_profile() -> str:
    """Stop the trace; returns the path of the ``.xplane.pb`` written
    ("" if none was running). Raises ProfileError if nothing was written."""
    return _profile.stop()


class tpu_profile:
    """Context manager: ``with ray_tpu.tpu_profile("/tmp/trace"): step()``"""

    def __init__(self, logdir: str):
        self.logdir = logdir

    def __enter__(self):
        start_tpu_profile(self.logdir)
        return self

    def __exit__(self, *exc):
        stop_tpu_profile()
        return False
