"""What ``runners/serve.py`` sets for the cluster it starts is what an
actor's own process reads: the Serve controller is such a process, and it is
the controller that waits for a cold replica (``r.health_check.remote()``
under ``actor_wait_alive_timeout_s``). A toy cluster in a process of its own,
so that neither the environment nor the cluster outlives the test."""

import os
import subprocess
import sys

from benchmarks import harness

PROBE = """
import os
import ray_tpu
from benchmarks.runners import serve

os.environ.update(serve.CLUSTER_ENV)
ray_tpu.init(num_cpus=2, log_to_driver=False)


@ray_tpu.remote
class Replica:
    def limit(self):
        from ray_tpu._private.config import config
        return config.actor_wait_alive_timeout_s


@ray_tpu.remote
class Controller:
    def limits(self):
        from ray_tpu._private.config import config
        return [config.actor_wait_alive_timeout_s,
                ray_tpu.get(Replica.remote().limit.remote())]


try:
    print("LIMITS", *ray_tpu.get(Controller.remote().limits.remote(),
                                 timeout=120))
finally:
    ray_tpu.shutdown()
"""


def test_an_actor_reads_the_start_up_limit_the_runner_sets():
    from benchmarks.runners import serve

    want = float(serve.CLUSTER_ENV["RAY_TPU_ACTOR_WAIT_ALIVE_TIMEOUT_S"])
    # not under the controller's own wait for its replicas (300 s,
    # ServeController.deploy), nor over the program's limit on a creation
    assert 300 <= want <= 600
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=harness.ROOT)
    env.pop("RAY_TPU_ACTOR_WAIT_ALIVE_TIMEOUT_S", None)
    proc = subprocess.run([sys.executable, "-c", PROBE], env=env,
                          cwd=harness.ROOT, capture_output=True, text=True,
                          timeout=240)
    assert proc.returncode == 0, proc.stderr[-2000:]
    said = [ln for ln in proc.stdout.splitlines() if ln.startswith("LIMITS")]
    assert said == [f"LIMITS {want} {want}"], proc.stdout[-500:]
