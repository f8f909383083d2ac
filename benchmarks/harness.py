"""What every runner shares: finding a cell's files by name, building the
program's model configuration from a published ``config.json``, describing
the device, and the shape of the result line.

Nothing here imports JAX at module level: ``run.py`` (the parent) and the
Serve driver import this file and must never touch a backend.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
DATA_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")

# --toy: the same code at debug widths on whatever device JAX finds. A toy
# run prints the device it ran on (cpu) and is never a result.
TOY_MODEL = dict(hidden_size=128, intermediate_size=352, num_hidden_layers=2,
                 num_attention_heads=4, num_key_value_heads=2, head_dim=32,
                 vocab_size=512)

T0 = time.perf_counter()  # process start, as near as Python lets us see it


def say(tag: str, **fields) -> None:
    """One progress line on stderr; stdout carries the result line alone
    (plus whatever the program itself prints)."""
    sys.stderr.write(f"[{tag} +{time.perf_counter() - T0:6.1f}s] " + " ".join(
        f"{k}={json.dumps(v, default=str)}" for k, v in fields.items()) + "\n")
    sys.stderr.flush()


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def find_data_file(directory: str, name: str) -> str:
    """``<directory>/<name>.<data suffix>`` under this benchmark."""
    for suffix in DATA_SUFFIXES:
        path = os.path.join(HERE, directory, name + suffix)
        if os.path.exists(path):
            return path
    raise FileNotFoundError(
        f"no {directory}/{name}{{{','.join(DATA_SUFFIXES)}}} under {HERE}")


def load_module(directory: str, name: str):
    """Import ``<directory>/<name>.py`` by path: a later PR adds a runner or
    a metric reader as a new file and edits nothing."""
    path = os.path.join(HERE, directory, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{directory}_{name}".replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(metric: str):
    """The reader of a per-layer metric: ``layer_metrics/<metric>.py``; a
    name with no file of its own is read by the file of the name without its
    first words (``tput_slot_occupancy`` by ``slot_occupancy.py``).

    BENCHMARK.json holds ONE entry for each pair (reader file, ``moves``),
    and the entry's ``workloads`` lists every cell the reader is read in
    (PR 42; ``tests/test_contract.py`` here refuses a second). A new cell
    JOINS the lists of the readers it shares, by appending its name, and adds
    entries only for readers of its own. A prefix is for the one case in
    which two entries must share a file: a reader that moves another
    end-to-end metric in other cells. The entry whose cells report the
    chat or train metric keeps the reader's name (``slot_occupancy`` moves
    ``itl_p95_ms``), the one that moves ``out_tokens_per_s`` is
    ``tput_<reader>``. No reader sees its metric's name."""
    words = metric.split("_")
    for i in range(len(words)):
        name = "_".join(words[i:])
        if os.path.exists(os.path.join(HERE, "layer_metrics", name + ".py")):
            return load_module("layer_metrics", name)
    raise FileNotFoundError(f"no reader under layer_metrics/ for {metric!r}")


def load_cell(workload: str, toy: bool = False, root: str = ROOT) -> dict:
    """The cell named ``workload`` with its configuration, its traffic mix
    and its metrics, all found by the names in BENCHMARK.json."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"it has {sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(root, entry["file"]))
    traffic = load_json(find_data_file("traffic", cell["traffic"]))
    if toy:
        config = dict(config, **TOY_MODEL)
        traffic = dict(traffic, **traffic.get("toy", {}))

    def in_cell(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "name": workload, "chips": cell["chips"], "toy": toy,
        "config": config, "traffic": traffic,
        "end_to_end": [m for m in bench["end_to_end"] if in_cell(m)],
        "per_layer": [m for m in bench["per_layer"] if in_cell(m)],
    }


def model_config(config: dict, **extra):
    """The program's TransformerConfig for a published ``config.json``.
    Every width comes from the file; bf16 parameters as published."""
    import jax.numpy as jnp

    from ray_tpu.models import transformer as T

    if config.get("sliding_window") or config.get("tie_word_embeddings") \
            or config.get("hidden_act", "silu") != "silu":
        raise ValueError("the program's block has no sliding window, tied "
                         "embeddings or other activation than SiLU")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config.get("torch_dtype", "bfloat16")]
    return T.config(
        "llama2_7b", vocab_size=config["vocab_size"],
        hidden=config["hidden_size"], mlp_hidden=config["intermediate_size"],
        layers=config["num_hidden_layers"],
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        max_seq=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]), tie_embeddings=False,
        dtype=jnp.bfloat16, param_dtype=dtype, **extra)


def describe_devices(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def require_chips(device: dict, want: int, toy: bool) -> None:
    """No TPU, or another number of chips than the cell asks for, is a
    failure: there is no fallback. ``--toy`` alone runs elsewhere."""
    if toy:
        return
    if device["platform"] != "tpu":
        raise RuntimeError(f"no TPU: JAX found {device}; only --toy runs "
                           f"on another device, and a toy run is no result")
    if device["count"] != want:
        raise RuntimeError(f"the cell asks for {want} chip(s), JAX found "
                           f"{device}")


def memory_peak_bytes(devices) -> int:
    """Peak on the fullest chip. ``peak_bytes_in_use`` leaves out program
    temporaries on this runtime (PERF.md section 7), so where the device
    reports ``peak_bytes_reserved`` the larger of the two stands."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use") or 0),
                   int(stats.get("peak_bytes_reserved") or 0))
    return peak


def setup_compile_cache() -> str:
    """JAX's persistent compilation cache for the process that holds the
    chip: where JAX_COMPILATION_CACHE_DIR says, else the program's own fixed
    ``<checkout>/.jax_cache``. Every program is kept, however quick its
    compile, so that a warm run compiles nothing."""
    import jax

    from ray_tpu.parallel.bootstrap import configure_compilation_cache

    path = configure_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def percentile(values, q: float) -> float:
    """Nearest-rank-with-interpolation percentile of a non-empty list."""
    import numpy as np

    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


def metric(value: float, unit: str) -> dict:
    return {"value": float(value), "unit": unit}
