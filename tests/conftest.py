"""Test config: force JAX onto a virtual 8-device CPU mesh.

No accelerator is attached where the tests run; all sharding/collective
tests run against ``--xla_force_host_platform_device_count=8``. What only
the chip's compiler can say (the Pallas kernels, the sharded step on a
described v5e:2x2) is in tests/test_chip_compile.py; what only a chip run
can say is chip_smoke.py's.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# The environment covers every process the tests start; the config covers
# a jax that something imported before this file ran.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# Let local-mode tests pretend the host has 4 TPU chips for resource math.
os.environ.setdefault("RAY_TPU_FAKE_CHIPS", "4")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import threading  # noqa: E402
import time  # noqa: E402

import pytest  # noqa: E402

# Thread-leak guard allowlist (the dynamic face of raycheck RC005):
# long-lived runtime pools that legitimately outlive a single test. All
# are process-lifetime ThreadPoolExecutors (non-daemon by design, reaped
# by their atexit join) or pytest internals.
_THREAD_ALLOW_PREFIXES = (
    "rpc-exec",        # EventLoopThread default executor (global loop)
    "rpc-io",          # event-loop threads (daemon, listed for clarity)
    "task",            # local-mode task pool
    "actor-",          # local-mode / worker actor pools
    "serve-local",     # serve local-mode pool
    "borrow-release",  # core worker borrow-release pool
    "exec",            # worker task pool
    "ThreadPoolExecutor",  # unnamed stdlib pools (grpc proxy, asyncio)
    "asyncio_",        # asyncio.to_thread default executor
    "pytest",          # pytest-timeout et al.
)


def _leaked_threads(before):
    # compare Thread OBJECTS, not idents — CPython recycles idents after
    # a thread exits, which would let a leak hide behind a dead thread
    return [
        t for t in threading.enumerate()
        if t.is_alive() and not t.daemon
        and t not in before
        and t is not threading.main_thread()
        and not t.name.startswith(_THREAD_ALLOW_PREFIXES)
    ]


@pytest.fixture(autouse=True)
def _thread_leak_guard(request):
    """After each test, no NEW non-daemon thread may survive — the
    dynamic complement of raycheck's RC005 (a stop() path that skips
    join, or a Thread whose author never decided its daemon-ness, shows
    up here as a leak). Allowlisted prefixes cover the known
    process-lifetime runtime pools; mark a test ``no_thread_guard`` to
    opt out."""
    if request.node.get_closest_marker("no_thread_guard"):
        yield
        return
    before = set(threading.enumerate())
    yield
    deadline = time.monotonic() + 2.0
    leaked = _leaked_threads(before)
    while leaked and time.monotonic() < deadline:
        time.sleep(0.05)  # teardown stragglers get a short grace window
        leaked = _leaked_threads(before)
    assert not leaked, (
        f"test leaked non-daemon thread(s): {[t.name for t in leaked]} — "
        f"join them in teardown, make them daemon, or (for a known "
        f"runtime pool) extend _THREAD_ALLOW_PREFIXES in conftest.py")


def pytest_addoption(parser):
    parser.addoption(
        "--stress-repeat", type=int, default=1, metavar="N",
        help="run every @pytest.mark.stress test N times (race "
             "discipline: the seqlock channels, the paged batcher pump, "
             "collective rendezvous, and event-bus flush suites are "
             "timing-sensitive; one green run proves little)")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "stress: race-prone suite, repeated --stress-repeat "
                   "times by the repeat-runner")
    config.addinivalue_line("markers", "slow: excluded from tier-1 runs")
    config.addinivalue_line(
        "markers", "no_thread_guard: opt out of the per-test non-daemon "
                   "thread-leak assertion")


def pytest_generate_tests(metafunc):
    """Repeat-runner: parametrize stress-marked tests N times so
    ``pytest -m stress --stress-repeat=20`` hammers the racy paths."""
    n = metafunc.config.getoption("--stress-repeat")
    if n > 1 and metafunc.definition.get_closest_marker("stress"):
        metafunc.fixturenames.append("_stress_rep")
        metafunc.parametrize("_stress_rep", range(n))


@pytest.fixture
def ray_start_local():
    import ray_tpu

    ray_tpu.init(local_mode=True, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_regular():
    """Single-node multi-process cluster (the real runtime)."""
    import ray_tpu

    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()
