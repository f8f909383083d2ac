#!/usr/bin/env python3
"""``rehearse.py``'s serve rows for the MiMo-V2-Flash configuration: the engine's own
prefill and decode programs (``ContinuousBatcher._jit_programs()``: the decode
step donates its cache, slots and ring) compiled at full size for a described
``v5e:2x2`` with no chip attached.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse_mimo.py [--buckets 8192]

As ``rehearse_laguna.py`` (``rehearse.py`` builds its model through
``harness.model_config``, which reads another family's keys), with the kernels
on (``ops.attention._on_tpu`` sees the CPU during such a compile and is
steered here): prefill[8192] has to take the two-width flash forward for its
full layers (the dense spelling's ``[64, 8192, 8192]`` float32 logits are 17
GB), run its MLPs 1,024 rows at a time, and stay with the engine's cache
(``resident_gb``: the weights, the slots and the rings) under the chip's 15.75
GB; each row says what its fresh rows were attended with.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import harness, rehearse  # noqa: E402

CONFIG = "mimo-v2-flash-serve-ep16-d11"


def serve_programs(config: dict, topo, buckets) -> list:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models import transformer as T
    from ray_tpu.models.continuous_batching import ContinuousBatcher
    from ray_tpu.models.decoding import init_cache

    runner = harness.load_module("runners", "serve_mimo")
    sv, cfg = config["serve"], runner.mimo_model_config(config)
    one = SingleDeviceSharding(topo.devices[0])
    params = rehearse._on(one, jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.key(0))))
    batcher = ContinuousBatcher.__new__(ContinuousBatcher)  # programs only
    batcher.cfg, batcher.max_len, batcher.slots = \
        cfg, sv["max_len"], sv["cache_slots"]
    batcher._jit_programs()
    slots = sv["cache_slots"]

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    out = []
    for bucket in buckets:
        t0 = time.perf_counter()
        prefill = jax.jit(batcher._prefill_impl).lower(
            params, arr((1, bucket), jnp.int32),
            arr((1,), jnp.int32)).compile()
        out.append({"program": f"prefill[{bucket}]",
                    "params_b": round(cfg.num_params() / 1e9, 3),
                    "attended": batcher.prefill_attention_path.get(
                        f"prefill_{bucket}"),
                    "compile_s": round(time.perf_counter() - t0, 1),
                    "per_device": rehearse._mem(prefill)})
    cache = rehearse._on(one, jax.eval_shape(
        lambda: init_cache(cfg, slots, sv["max_len"])))
    t0 = time.perf_counter()
    decode = batcher._decode_jit.lower(
        params, arr((slots,), jnp.int32), cache,
        rehearse._on(one, jax.eval_shape(lambda: jax.random.key(0))),
        arr((slots,), jnp.float32), arr((slots,), jnp.int32),
        arr((slots,), jnp.bool_)).compile()
    kept_gb = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache)) \
        / 1e9
    out.append({"program": f"decode[{slots}x{sv['max_len']}]",
                "compile_s": round(time.perf_counter() - t0, 1),
                "slots_gb": round((cache.k.size + cache.v.size) * 2 / 1e9, 3),
                "ring_gb": round(
                    (cache.ring_k.size + cache.ring_v.size) * 2 / 1e9, 3),
                "resident_gb": round(
                    cfg.num_params() * 2 / 1e9 + kept_gb, 3),
                "per_device": rehearse._mem(decode)})
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--buckets", type=int, nargs="*", default=[8192])
    args = ap.parse_args()
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    from ray_tpu.ops import attention

    attention._on_tpu = lambda: True  # the compile is for a TPU
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    config = harness.load_json(os.path.join(
        harness.HERE, "configs", CONFIG + ".json"))
    for row in serve_programs(config, topo, args.buckets):
        print(json.dumps({"config": CONFIG, "chips": 1,
                          "layers": config["num_hidden_layers"], **row}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
