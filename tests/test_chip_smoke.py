"""chip_smoke.py off the chip, and the rules it stands on: a run without
``--toy`` and without a chip fails; the parent stays off JAX; the compile
cache lands where JAX_COMPILATION_CACHE_DIR says or in the checkout; only a
lease that holds chips lets a worker's JAX off the CPU."""

import json
import os
import subprocess
import sys

import pytest

import ray_tpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run_smoke(*args):
    proc = subprocess.run(
        [sys.executable, SMOKE, *args], cwd=REPO, capture_output=True,
        text=True, timeout=420, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    lines = proc.stdout.strip().splitlines()
    return proc, lines, json.loads(lines[-1])


@pytest.fixture(scope="module")
def toy_run(tmp_path_factory):
    """One ``--toy`` run (kernels, train, serve at debug/tiny widths) shared
    by the cases that read it — also across xdist workers, which would
    otherwise each start their own two clusters: the first worker to take
    the lock runs it, the others read what it wrote."""
    if "PYTEST_XDIST_WORKER" not in os.environ:
        return _run_smoke("--toy")
    import fcntl

    shared = tmp_path_factory.getbasetemp().parent / "chip_smoke_toy.json"
    with open(str(shared) + ".lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if not shared.exists():
            proc, _, _ = _run_smoke("--toy")
            shared.write_text(json.dumps(
                [proc.returncode, proc.stdout, proc.stderr]))
        returncode, stdout, stderr = json.loads(shared.read_text())
    proc = subprocess.CompletedProcess([SMOKE, "--toy"], returncode,
                                       stdout, stderr)
    lines = stdout.strip().splitlines()
    return proc, lines, json.loads(lines[-1])


class TestToySmoke:
    def test_passes_and_names_the_cpu(self, toy_run):
        proc, lines, last = toy_run
        assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
        assert set(last) == {"ok", "device"}
        assert last["ok"] is True
        assert last["device"]["platform"] == "cpu"
        assert last["device"]["count"] >= 1

    @pytest.mark.parametrize("phase", ["kernels", "train", "serve"])
    def test_every_phase_ran_and_passed(self, toy_run, phase):
        _, lines, _ = toy_run
        assert any(line.startswith(f"[{phase}] ok=true") for line in lines)

    def test_parent_never_imports_jax(self, toy_run):
        _, lines, _ = toy_run
        assert any("parent_imported_jax=false" in line for line in lines)
        # importing the script (what the parent is before it parses its
        # arguments) leaves JAX alone too
        subprocess.run(
            [sys.executable, "-c", "import sys, chip_smoke; "
             "assert 'jax' not in sys.modules"],
            cwd=REPO, check=True, timeout=60)

    def test_replica_is_the_only_serve_process_on_jax(self, toy_run):
        _, lines, _ = toy_run
        assert any("driver_initialised_a_backend=false" in line
                   for line in lines)
        stats = next(line for line in lines
                     if line.startswith("[serve] engine_stats="))
        assert '"failed": 0' in stats and '"admitted": 8' in stats

    def test_reports_the_cache_and_the_repeated_compile(self, toy_run):
        _, lines, _ = toy_run
        line = next(line for line in lines if "compile_s_repeat=" in line)
        assert "repeat_compile_cache_hit=" in line
        assert any("cache_dir=" in line for line in lines)


def test_without_toy_and_without_a_chip_it_fails():
    proc, lines, last = _run_smoke()
    assert proc.returncode != 0
    assert last["ok"] is False
    assert any("no TPU" in line for line in lines)
    assert not any("ok=true" in line for line in lines)


class TestCompilationCacheDir:
    @pytest.fixture
    def config_updates(self, monkeypatch):
        import jax

        calls = []
        monkeypatch.setattr(jax.config, "update",
                            lambda name, value: calls.append((name, value)))
        return calls

    def test_variable_set_is_used_and_code_sets_nothing(
            self, monkeypatch, config_updates, tmp_path):
        from ray_tpu.parallel.bootstrap import configure_compilation_cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert configure_compilation_cache() == str(tmp_path)
        assert config_updates == []

    def test_variable_unset_lands_in_the_checkout(
            self, monkeypatch, config_updates):
        from ray_tpu.parallel.bootstrap import configure_compilation_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(REPO, ".jax_cache")
        assert configure_compilation_cache() == want
        assert config_updates == [("jax_compilation_cache_dir", want)]
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()


def _jax_platforms():
    """What this worker's JAX is held to, without initialising a backend."""
    import os
    import sys

    jax = sys.modules.get("jax")
    return (os.environ.get("JAX_PLATFORMS"),
            jax.config.jax_platforms if jax is not None else None,
            os.environ.get("TPU_VISIBLE_CHIPS"))


def test_only_a_lease_with_chips_lets_a_worker_off_the_cpu(monkeypatch):
    """One process for each chip: the node here says "tpu,cpu" (as the chip
    machine does) and holds fake chips. A worker whose lease has none is
    pinned to the CPU, in the environment and in an imported JAX's config;
    a lease with a chip returns it to the node's setting."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    ray_tpu.init(num_cpus=2, num_tpus=2)
    try:
        plain = ray_tpu.remote(_jax_platforms)
        leased = ray_tpu.remote(num_tpus=1)(_jax_platforms)
        env, cfg, chips = ray_tpu.get(plain.remote(), timeout=60)
        assert env == "cpu" and cfg in (None, "cpu")
        env, cfg, chips = ray_tpu.get(leased.remote(), timeout=60)
        assert env == "tpu,cpu" and cfg in (None, "tpu,cpu")
        assert chips in ("0", "1")
        # and back: whichever worker takes the next chipless lease
        for _ in range(3):
            env, cfg, _ = ray_tpu.get(plain.remote(), timeout=60)
            assert env == "cpu" and cfg in (None, "cpu")
    finally:
        ray_tpu.shutdown()


def _bring_up_cpu_backend():
    import os

    import jax

    return os.getpid(), jax.devices()[0].platform


def _pid_and_platforms():
    import os

    return os.getpid(), os.environ.get("JAX_PLATFORMS")


def test_a_worker_whose_backend_is_up_cannot_take_chips(monkeypatch):
    """A backend cannot be moved: a worker that initialised JAX on the CPU
    would run a later chip lease on the CPU without a word. It refuses the
    lease instead, the raylet retires it, and another worker gets the
    chips."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    ray_tpu.init(num_cpus=1, num_tpus=1)
    try:
        on_cpu = {ray_tpu.get(ray_tpu.remote(_bring_up_cpu_backend).remote(),
                              timeout=120) for _ in range(3)}
        assert {platform for _, platform in on_cpu} == {"cpu"}
        leased = ray_tpu.remote(num_tpus=1)(_pid_and_platforms)
        pid, platforms = ray_tpu.get(leased.remote(), timeout=120)
        assert platforms == "tpu,cpu"
        assert pid not in {p for p, _ in on_cpu}
    finally:
        ray_tpu.shutdown()
