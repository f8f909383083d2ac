#!/usr/bin/env python3
"""``rehearse.py``'s serve rows for the Laguna configuration: the engine's own
prefill and decode programs (``ContinuousBatcher._jit_programs()``: the decode
step donates its cache, slots and ring) compiled at full size for a described
``v5e:2x2`` with no chip attached.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse_laguna.py [--buckets 2048]

``rehearse.py`` builds its model through ``harness.model_config``, which
refuses a sliding window, and jits ``_decode_impl`` bare; nothing else differs.
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

CONFIG = "laguna-s-2.1-serve-ep2-d5"


def serve_programs(config: dict, topo, buckets) -> list:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models import transformer as T
    from ray_tpu.models.continuous_batching import ContinuousBatcher
    from ray_tpu.models.decoding import init_cache

    runner = harness.load_module("runners", "serve_laguna")
    sv, cfg = config["serve"], runner.laguna_model_config(config)
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
    out.append({"program": f"decode[{slots}x{sv['max_len']}]",
                "compile_s": round(time.perf_counter() - t0, 1),
                "slots_gb": round(2 * cache.k.size * 2 / 1e9, 3),
                "ring_gb": round(2 * cache.ring_k.size * 2 / 1e9, 3),
                "per_device": rehearse._mem(decode)})
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--buckets", type=int, nargs="*", default=[2048])
    args = ap.parse_args()
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

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
