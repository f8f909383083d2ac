#!/usr/bin/env python3
"""Rehearsal 3 of the on-chip-measurement guide: compile each cell's device
programs at full size for a described ``v5e:2x2`` with no chip attached, and
print ``memory_analysis()`` per device.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse.py [config ...]

Nothing runs, so this says nothing of results or times; what the chip's
compiler refuses here (a program that does not fit 16 GB, a kernel that
cannot be partitioned) costs no chip time. ``--layers N`` overrides the
depth of the train configurations, which is how the depth cut was chosen.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmarks import harness  # noqa: E402


def _on(sharding, tree):
    import jax

    if not isinstance(sharding, (dict, list, tuple)):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sharding), tree)
    return jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sh), tree, sharding)


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    out = {"arguments_gb": m.argument_size_in_bytes / 1e9,
           "outputs_gb": m.output_size_in_bytes / 1e9,
           "aliased_gb": m.alias_size_in_bytes / 1e9,
           "temporaries_gb": m.temp_size_in_bytes / 1e9}
    out["total_gb"] = (out["arguments_gb"] + out["outputs_gb"]
                       - out["aliased_gb"] + out["temporaries_gb"])
    return {k: round(v, 3) for k, v in out.items()}


def train_step(config: dict, topo, batch: int, seq: int) -> dict:
    import jax
    import jax.numpy as jnp

    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    from ray_tpu.train import step as S

    tr = config["train"]
    cfg = harness.model_config(config, lora_rank=tr["lora_rank"],
                               lora_alpha=tr["lora_alpha"], remat=tr["remat"])
    mesh = build_mesh(MeshSpec(**tr["mesh"]), list(topo.devices)[:tr["chips"]])
    opt = S.default_optimizer(cfg)
    step = S.make_train_step(cfg, opt, mesh)
    state = _on(step._shardings, jax.eval_shape(
        lambda: S.fresh_state(cfg, opt, jax.random.key(0))))
    tokens = {"tokens": jax.ShapeDtypeStruct(
        (batch, seq), jnp.int32, sharding=step._batch_sharding)}
    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        compiled = step._jitted.lower(state, tokens).compile()
    text = compiled.as_text()
    # the reference check's program: the same loss and kernels on the sample
    runner = harness.load_module("runners", "train")
    with jax.set_mesh(mesh):
        probe = runner.probe_program(cfg, mesh).lower(
            state["params"], jax.ShapeDtypeStruct(runner.SAMPLE, jnp.int32)
        ).compile()
    return {"program": f"train_step[{batch}x{seq}]", "chips": tr["chips"],
            "probe_per_device": _mem(probe),
            "params_b": round(cfg.num_params() / 1e9, 3),
            "compile_s": round(time.perf_counter() - t0, 1),
            "tpu_custom_calls": text.count("tpu_custom_call"),
            "collectives": {n: text.count(f" {n}(") + text.count(f" {n}-start(")
                            for n in ("all-reduce", "all-gather",
                                      "reduce-scatter", "all-to-all",
                                      "collective-permute")},
            "per_device": _mem(compiled)}


def serve_programs(config: dict, topo, buckets) -> list:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models import transformer as T
    from ray_tpu.models.continuous_batching import ContinuousBatcher
    from ray_tpu.models.decoding import init_cache

    sv = config["serve"]
    cfg = harness.model_config(config)
    one = SingleDeviceSharding(topo.devices[0])
    params = _on(one, jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.key(0))))
    batcher = ContinuousBatcher.__new__(ContinuousBatcher)  # programs only
    batcher.cfg, batcher.max_len, batcher.slots = \
        cfg, sv["max_len"], sv["cache_slots"]
    slots = sv["cache_slots"]

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    out = []
    for bucket in buckets:
        t0 = time.perf_counter()
        prefill = jax.jit(batcher._prefill_impl).lower(
            params, arr((1, bucket), jnp.int32),
            arr((1,), jnp.int32)).compile()
        out.append({"program": f"prefill[{bucket}]", "chips": 1,
                    "params_b": round(cfg.num_params() / 1e9, 3),
                    "compile_s": round(time.perf_counter() - t0, 1),
                    "per_device": _mem(prefill)})
    cache = _on(one, jax.eval_shape(
        lambda: init_cache(cfg, slots, sv["max_len"])))
    t0 = time.perf_counter()
    decode = jax.jit(batcher._decode_impl).lower(
        params, arr((slots,), jnp.int32), cache,
        _on(one, jax.eval_shape(lambda: jax.random.key(0))),
        arr((slots,), jnp.float32), arr((slots,), jnp.int32),
        arr((slots,), jnp.bool_)).compile()
    out.append({"program": f"decode[{slots}x{sv['max_len']}]", "chips": 1,
                "compile_s": round(time.perf_counter() - t0, 1),
                "cache_gb": round(sum(
                    s.size * s.dtype.itemsize
                    for s in jax.tree.leaves(cache)) / 1e9, 3),
                "per_device": _mem(decode)})
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("configs", nargs="*")
    ap.add_argument("--layers", type=int)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--buckets", type=int, nargs="*", default=[2048])
    args = ap.parse_args()
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    from ray_tpu.ops import attention as A

    A._on_tpu = lambda: True  # the compile is for a TPU; the backend is not
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    names = args.configs or sorted(
        os.path.basename(p)[:-5]
        for p in glob.glob(os.path.join(HERE, "configs", "*.json")))
    for name in names:
        config = harness.load_json(os.path.join(HERE, "configs", name + ".json"))
        if args.layers and "train" in config:
            config["num_hidden_layers"] = args.layers
        try:
            if "train" in config:
                rows = [train_step(config, topo, args.batch, args.seq)]
            else:
                rows = serve_programs(config, topo, args.buckets)
        except Exception as e:  # noqa: BLE001 — the refusal is the finding
            rows = [{"refused": f"{type(e).__name__}: {str(e)[:1500]}"}]
        for row in rows:
            print(json.dumps({"config": name,
                              "layers": config["num_hidden_layers"], **row}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
