#!/usr/bin/env python3
"""``rehearse.py``'s serve rows for the Solar-Open2 configuration: the
engine's own prefill and decode programs (``ContinuousBatcher.
_jit_programs()``: the decode step donates its cache: K/V rows, matrix states
of 64 heads and convolution windows) compiled at the published widths and the
cell's sizes for a described ``v5e:2x2`` with no chip attached: whether the
chip's compiler takes ``ops.delta_rule.state_update`` at 64 heads in one grid
step, and what the 8,192 bucket's prefill holds.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse_solar_open2.py [--buckets 8192] [--text DIR]

``--text DIR`` also writes the compiled programs' text there (what holds a
temporary, which operation lies under which scope). ``rehearse.py`` builds its
model through ``harness.model_config``, which reads one kind of layer;
nothing else differs (``rehearse_kimi_linear.py`` is the same for its
runner).
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

CONFIG = "solar-open2-250b-serve-ep16-d8"


def serve_programs(config: dict, topo, buckets, text_dir=None) -> list:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models import transformer as T
    from ray_tpu.models.continuous_batching import ContinuousBatcher
    from ray_tpu.models.decoding import init_cache

    runner = harness.load_module("runners", "serve_solar_open2")
    sv, cfg = config["serve"], runner.solar_model_config(config)
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

    def keep(name, program):
        if text_dir:
            os.makedirs(text_dir, exist_ok=True)
            with open(os.path.join(text_dir, name + ".txt"), "w") as f:
                f.write(program.as_text())

    out = []
    for bucket in buckets:
        t0 = time.perf_counter()
        prefill = jax.jit(batcher._prefill_impl).lower(
            params, arr((1, bucket), jnp.int32),
            arr((1,), jnp.int32)).compile()
        keep(f"prefill_{bucket}", prefill)
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
    keep("decode", decode)
    out.append({"program": f"decode[{slots}x{sv['max_len']}]",
                "compile_s": round(time.perf_counter() - t0, 1),
                "state_gb": round(cache.mat.size * 4 / 1e9, 3),
                "windows_gb": round(cache.conv.size * 2 / 1e9, 3),
                "rows_gb": round((cache.k.size + cache.v.size) * 2 / 1e9, 3),
                "per_device": rehearse._mem(decode)})
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--buckets", type=int, nargs="*", default=[8192])
    ap.add_argument("--text", default=None)
    args = ap.parse_args()
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    from ray_tpu.ops import attention

    attention._on_tpu = lambda: True  # the compile is for the chip's path

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    config = harness.load_json(os.path.join(
        harness.HERE, "configs", CONFIG + ".json"))
    for row in serve_programs(config, topo, args.buckets, args.text):
        print(json.dumps({"config": CONFIG, "chips": 1,
                          "layers": config["num_hidden_layers"], **row}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
