#!/usr/bin/env python3
"""The quickest proof that ray_tpu still starts on the chip.

Drives the two paths the system exists for, once each, through the entry
points a user calls, at the full width of the 953M Llama-shaped model
(hidden 2048, mlp 5632, 16 layers, 16 x 128 heads, vocab 32000, bf16
parameters, random weights from ``--seed``):

- **kernels**: the device as JAX reports it, and the three Pallas
  attention kernels against ``mha_reference`` and its ``jax.grad`` on a
  small input.
- **train**: ``ray_tpu.init()`` -> ``JaxTrainer(loop, ScalingConfig(
  num_workers=1, use_tpu=True, chips_per_worker=1)).fit()``; the loop
  builds the mesh, ``init_state`` and ``make_train_step`` (LoRA), checks
  that the lowered step holds the kernels, compiles it twice (the second
  time from the persistent cache), and takes 2 + 3 steps on one batch of
  8 x 2048 tokens. Loss finite and lower at the last step than the first.
- **serve**: ``ray_tpu.init()`` -> ``serve.run(build_llm_deployment(
  LLMConfig(..., resources={"TPU": 1})))`` + ``start_http_proxy``; eight
  HTTP requests (two warm-ups, then six at once, two of them streamed),
  prompts of 64-1024 tokens in two prefill buckets, 32 new tokens each.
  Every answer 200 with 32 tokens; equal prompts give equal answers.

``--chips 4`` runs nothing but the sharded train step on a
``MeshSpec(fsdp=2, tensor=2)`` mesh and the one-device step it is
compared with. ``--toy`` runs the same code at debug/tiny widths on
whatever device JAX finds (the CPU rehearsal and the tests); without it,
finding no TPU is a failure.

One process for each chip: this parent never imports JAX. It runs every
phase as a child process tree, one after another, waits until the tree is
gone before the next starts, and takes the device description from the
process that held the chip. The last line of standard output is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and the exit code is 0 only if every phase passed.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))

WIDTHS = dict(hidden=2048, mlp_hidden=5632, layers=16, heads=16, kv_heads=16,
              max_seq=2048)
# per mode: model preset + overrides, then the traffic that drives it
SIZES = {
    "real": dict(
        train_model=("llama2_7b_lora", WIDTHS), batch=8, seq=2048,
        serve_model=("llama2_7b", WIDTHS), max_len=2048, slots=8,
        short=64, long=(1024, 640, 800), new_tokens=32),
    "toy": dict(
        train_model=("tiny", dict(lora_rank=8)), batch=8, seq=128,
        serve_model=("debug", {}), max_len=128, slots=4,
        short=12, long=(64, 40, 50), new_tokens=8),
}
PHASE_LIMIT_S = {"kernels": 240, "train": 480, "serve": 600, "train4": 900}
TOTAL_LIMIT_S = 1150  # the contract allows 1200, compilation included
LOSS_REL_TOL = 2e-2  # sharded vs one-device first-step loss, bf16


def say(phase: str, **fields) -> None:
    # one write per line: request threads report at the same time
    sys.stdout.write(f"[{phase}] " + " ".join(
        f"{k}={json.dumps(v, default=str)}" for k, v in fields.items()) + "\n")
    sys.stdout.flush()


# ---------------------------------------------------------------------------
# Phase bodies: each runs in its own child process (tree).
# ---------------------------------------------------------------------------

def _describe(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def _require_tpu(devices, toy: bool, want: int) -> dict:
    dev = _describe(devices)
    if toy:
        return dev
    if dev["platform"] != "tpu":
        raise RuntimeError(f"no TPU: JAX found {dev}; --toy is the only "
                           f"way to run this on another device")
    if dev["count"] != want:
        raise RuntimeError(f"{want} chip(s) wanted, JAX found {dev}")
    return dev


def _model(spec, **extra):
    import jax.numpy as jnp

    from ray_tpu.models import transformer as T

    name, overrides = spec
    return T.config(name, param_dtype=jnp.bfloat16, **overrides, **extra)


def phase_kernels(args, size) -> dict:
    """The device, and the Pallas kernels against the plain reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import attention as A

    dev = _require_tpu(jax.devices(), args.toy, 1)
    say("kernels", device=dev, jax=jax.__version__,
        jax_platforms=os.environ.get("JAX_PLATFORMS"),
        memory_stats=jax.devices()[0].memory_stats())
    b, s, h, d = 2, 512, 4, 128
    ks = jax.random.split(jax.random.key(args.seed), 4)
    q, k, v, g = (jax.random.normal(kk, (b, s, h, d), jnp.float32)
                  .astype(jnp.bfloat16) for kk in ks)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v).astype(jnp.float32)
                                * g.astype(jnp.float32)).sum()

    flash = jax.jit(jax.value_and_grad(
        loss(lambda q, k, v: A.flash_attention(q, k, v, True, None, 128, 256)),
        argnums=(0, 1, 2)))
    ref = jax.jit(jax.value_and_grad(loss(A.mha_reference), argnums=(0, 1, 2)))
    n_kernels = flash.lower(q, k, v).as_text().count("tpu_custom_call")
    out = A.flash_attention(q, k, v, True, None, 128, 256)
    want = A.mha_reference(q, k, v)
    errs = {"out": float(jnp.max(jnp.abs(
        out.astype(jnp.float32) - want.astype(jnp.float32))))}
    (_, grads), (_, want_grads) = flash(q, k, v), ref(q, k, v)
    for name, a, w in zip(("dq", "dk", "dv"), grads, want_grads):
        a, w = np.asarray(a, np.float32), np.asarray(w, np.float32)
        errs[name] = float(np.max(np.abs(a - w)) / max(np.max(np.abs(w)), 1e-6))
    say("kernels", shape=[b, s, h, d], tpu_custom_calls=n_kernels,
        max_abs_err_out_and_rel_err_grads=errs)
    if dev["platform"] == "tpu" and n_kernels < 3:
        raise RuntimeError(f"on a TPU the lowered attention holds "
                           f"{n_kernels} tpu_custom_call, 3 expected")
    bad = {k_: e for k_, e in errs.items() if not e < 3e-2}
    if bad:
        raise RuntimeError(f"kernels disagree with mha_reference: {bad}")
    return {"device": dev}


def _train_loop(config: dict) -> None:
    """The user's train loop: runs inside JaxTrainer, on the process that
    holds the chip(s), and reports through ray_tpu.train.report."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu import train
    from ray_tpu.parallel.bootstrap import (
        configure_compilation_cache, watch_compiles,
    )
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    from ray_tpu.train import step as S

    compiles = watch_compiles()
    cache_dir = configure_compilation_cache()
    toy, size = config["toy"], SIZES["toy" if config["toy"] else "real"]
    devices = jax.devices()
    dev = _require_tpu(devices, toy, config["chips"])
    on_tpu = dev["platform"] == "tpu"
    cfg = _model(size["train_model"])
    opt = S.default_optimizer(cfg)
    tokens = np.random.RandomState(config["seed"]).randint(
        0, cfg.vocab_size, (size["batch"], size["seq"])).astype(np.int32)
    batch = {"tokens": jnp.asarray(tokens)}
    say(config["phase"], device=dev, params_m=round(cfg.num_params() / 1e6),
        batch=size["batch"], seq=size["seq"], lora_rank=cfg.lora_rank,
        remat=cfg.remat, cache_dir=cache_dir,
        cache_dir_from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")))

    def run(mesh_spec, mesh_devices, n_steps, tag):
        mesh = build_mesh(mesh_spec, mesh_devices)
        before, t0 = compiles["compile_s"], time.perf_counter()
        state = jax.block_until_ready(
            S.init_state(cfg, opt, mesh, seed=config["seed"]))
        init_s = time.perf_counter() - t0
        init_compile_s = compiles["compile_s"] - before
        # Twice from this one line: a Pallas kernel is lowered with the
        # Python call stack that traced it inside, so the cache key of a
        # step that holds kernels differs between two call sites.
        compile_s, hits = [], []
        for _ in range(2):
            step = S.make_train_step(cfg, opt, mesh)
            lowered = step.lower(state, batch)
            before, t0 = compiles["cache_hits"], time.perf_counter()
            compiled = lowered.compile()
            compile_s.append(round(time.perf_counter() - t0, 2))
            hits.append(compiles["cache_hits"] > before)
        n_kernels = lowered.as_text().count("tpu_custom_call")
        if on_tpu and n_kernels < 3:
            raise RuntimeError(
                f"{tag}: the lowered step holds {n_kernels} tpu_custom_call "
                f"on a TPU — attention is not on the Pallas kernels")
        text = compiled.as_text()
        collectives = {name: text.count(f" {name}(") + text.count(f" {name}-start(")
                       for name in ("all-reduce", "all-gather", "reduce-scatter",
                                    "all-to-all", "collective-permute")}
        losses, step_s = [], []
        for _ in range(n_steps):
            t0 = time.perf_counter()
            state, metrics = step(state, batch)
            losses.append(float(jax.block_until_ready(metrics)["loss"]))
            step_s.append(round(time.perf_counter() - t0, 3))
        # program temporaries show under "reserved", not "in use"
        peaks = [{k: (d.memory_stats() or {}).get(k) for k in
                  ("peak_bytes_in_use", "peak_bytes_reserved")}
                 for d in mesh.devices.flat]
        mem = compiled.memory_analysis()
        say(config["phase"], run=tag, mesh={a: n for a, n in mesh.shape.items() if n > 1},
            init_s=round(init_s, 2), init_compile_s=round(init_compile_s, 2),
            tpu_custom_calls=n_kernels,
            compile_s_first=compile_s[0], compile_s_repeat=compile_s[1],
            first_compile_cache_hit=hits[0], repeat_compile_cache_hit=hits[1],
            collectives=collectives, step_s=step_s, losses=losses,
            peak_bytes_per_device=peaks,
            compiler_bytes_per_device={
                "arguments": mem.argument_size_in_bytes,
                "temporaries": mem.temp_size_in_bytes})
        if not all(np.isfinite(losses)):
            raise RuntimeError(f"{tag}: loss not finite: {losses}")
        if on_tpu and not hits[1]:
            raise RuntimeError(
                f"{tag}: the repeated compile missed the persistent cache "
                f"at {cache_dir}")
        return losses, {"compile_s": compile_s, "step_s": step_s,
                        "peak_bytes_per_device": peaks,
                        "tpu_custom_calls": n_kernels,
                        "collectives": collectives}

    if config["chips"] == 1:
        losses, detail = run(MeshSpec(), devices[:1], 5, "one-device")
    else:
        losses, detail = run(MeshSpec(fsdp=2, tensor=2), devices, 5, "sharded")
        if not any(collectives for collectives in detail["collectives"].values()):
            raise RuntimeError("sharded step holds no collective")
        # the comparison comes second, so that device 0's peak above is
        # the sharded run's own
        one, _ = run(MeshSpec(), devices[:1], 1, "one-device")
        rel = abs(losses[0] - one[0]) / abs(one[0])
        say(config["phase"], first_loss_sharded=losses[0], first_loss_one_device=one[0],
            rel_diff=rel, tol=LOSS_REL_TOL)
        if not rel <= LOSS_REL_TOL:
            raise RuntimeError(
                f"sharded first-step loss {losses[0]} vs one-device {one[0]}: "
                f"rel diff {rel} > {LOSS_REL_TOL}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"loss did not fall on the repeated batch: {losses}")
    train.report({"device": dev, "first_loss": losses[0],
                  "last_loss": losses[-1],
                  "total_compile_s": round(compiles["compile_s"], 2), **detail})


def _start_cluster(phase: str, chips: int, toy: bool) -> dict:
    """ray_tpu.init(), then the node's resources once it has registered
    (init returns before the raylet has told the GCS what it holds)."""
    import ray_tpu

    t0 = time.perf_counter()
    # toy runs declare the chips they pretend to have; a real run finds them
    ray_tpu.init(num_tpus=chips if toy else None)
    deadline = time.monotonic() + 20
    while not (resources := ray_tpu.cluster_resources()) and \
            time.monotonic() < deadline:
        time.sleep(0.1)
    say(phase, init_s=round(time.perf_counter() - t0, 2),
        cluster_resources=resources)
    return resources


def phase_train(args, size) -> dict:
    import ray_tpu
    from ray_tpu.train import JaxTrainer, ScalingConfig

    t0 = time.perf_counter()
    try:
        _start_cluster(args.phase, args.chips, args.toy)
        # With one worker JaxTrainer runs the loop in this process (no
        # actor hop), so THIS child is the chip's owner and the raylet's
        # chip accounting does not know.
        result = JaxTrainer(
            _train_loop,
            train_loop_config={"toy": args.toy, "seed": args.seed,
                               "chips": args.chips, "phase": args.phase},
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                         chips_per_worker=args.chips),
        ).fit()
    finally:
        ray_tpu.shutdown()
    if result.error is not None:
        raise RuntimeError(f"JaxTrainer.fit failed:\n{result.error}")
    say(args.phase, fit_s=round(time.perf_counter() - t0, 2),
        first_loss=result.metrics["first_loss"],
        last_loss=result.metrics["last_loss"],
        total_compile_s=result.metrics["total_compile_s"])
    return {"device": result.metrics["device"]}


class IdTokenizer:
    """Token ids as decimal text, so that a client of the HTTP front door
    can send exact ids and count the ones that come back."""

    def encode(self, text: str):
        return [int(t) for t in text.split()]

    def decode(self, ids) -> str:
        return " ".join(str(int(i)) for i in ids)


def _post(port: int, path: str, prompt: str, timeout_s=None):
    """One HTTP request; returns (status, text, seconds). A streamed
    answer is one JSON line per delta."""
    import http.client

    headers = {"Content-Type": "application/json"}
    if timeout_s is not None:
        headers["x-request-timeout-s"] = str(timeout_s)
    conn = http.client.HTTPConnection("127.0.0.1", port,
                                      timeout=(timeout_s or 60) + 30)
    t0 = time.perf_counter()
    try:
        conn.request("POST", path, body=json.dumps(prompt), headers=headers)
        resp = conn.getresponse()
        body = resp.read().decode()
    finally:
        conn.close()
    seconds = time.perf_counter() - t0
    if resp.status != 200:
        return resp.status, body, seconds
    if path.endswith("/generate_stream"):
        frames = [json.loads(line) for line in body.splitlines() if line.strip()]
        errors = [f for f in frames if isinstance(f, dict)]
        if errors:  # the documented terminal error frame of a stream
            return 500, json.dumps(errors), seconds
        return 200, "".join(frames), seconds
    return 200, json.loads(body)["result"], seconds


def phase_serve(args, size) -> dict:
    import numpy as np

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm import LLMConfig, SamplingParams, build_llm_deployment

    cfg = _model(size["serve_model"])
    n_new = size["new_tokens"]
    rng = np.random.RandomState(args.seed)
    tok = IdTokenizer()

    def prompt(n):
        return tok.decode(rng.randint(0, cfg.vocab_size, n))

    same = prompt(size["short"])
    long0, long1, long2 = (prompt(n) for n in size["long"])
    # (name, path, prompt): two buckets only — short and long
    warmups = [("warm-short", "/llm", same), ("warm-long", "/llm", long0)]
    burst = [("same-a", "/llm", same), ("same-b", "/llm", same),
             ("stream-long", "/llm/generate_stream", long1),
             ("long", "/llm", long0), ("long-2", "/llm", long2),
             ("stream-short", "/llm/generate_stream", prompt(size["short"]))]

    try:
        resources = _start_cluster("serve", 1, args.toy)
        if resources.get("TPU", 0) < 1:
            raise RuntimeError(
                f"the node advertises no TPU ({resources}): a replica asking "
                f"for {{'TPU': 1}} would wait forever. Chips are counted from "
                f"/dev/accel* and /dev/vfio/* (accelerators/tpu.py)")
        t0 = time.perf_counter()
        try:
            handle = serve.run(build_llm_deployment(LLMConfig(
                model=cfg, max_len=size["max_len"], cache_slots=size["slots"],
                resources={"TPU": 1}, tokenizer=tok, seed=args.seed,
                sampling=SamplingParams(max_tokens=n_new))))
        except BaseException:
            say("serve", deploy_failed_after_s=round(time.perf_counter() - t0, 1),
                cluster_resources=ray_tpu.cluster_resources(),
                available_resources=ray_tpu.available_resources())
            raise
        port = serve.start_http_proxy(port=0)
        say("serve", replica_start_s=round(time.perf_counter() - t0, 2),
            params_m=round(cfg.num_params() / 1e6), max_len=size["max_len"],
            slots=size["slots"], port=port)

        answers, failures = {}, []

        def request(name, path, text, timeout_s=None):
            try:
                status, out, seconds = _post(port, path, text, timeout_s)
            except Exception as e:  # noqa: BLE001 — a thread must report
                failures.append(f"{name}: {type(e).__name__}: {e}")
                return
            say("serve", request=name, status=status, seconds=round(seconds, 2),
                prompt_tokens=len(text.split()),
                new_tokens=len(out.split()) if status == 200 else None)
            if status != 200:
                failures.append(f"{name}: HTTP {status}: {out[:500]}")
                return
            ids = tok.encode(out)
            if len(ids) != n_new or not all(0 <= i < cfg.vocab_size for i in ids):
                failures.append(f"{name}: {len(ids)} tokens, {n_new} wanted: {out[:200]}")
            answers[name] = out

        # the first request of a bucket compiles its prefill, and the very
        # first also the decode step, inside its own deadline: the warm-ups
        # say so with an explicit budget, no default is raised
        for name, path, text in warmups:
            request(name, path, text, timeout_s=540)
        stats = handle.engine_stats.remote().result()
        say("serve", compile_s_in_warmups=round(stats["compile_s"], 2),
            cache_hits=stats["cache_hits"], cache_misses=stats["cache_misses"])
        threads = [threading.Thread(target=request, args=r, daemon=True)
                   for r in burst]
        t0 = time.perf_counter()
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=180)
        say("serve", burst_s=round(time.perf_counter() - t0, 2),
            still_running=[r[0] for r, th in zip(burst, threads) if th.is_alive()])
        stats = handle.engine_stats.remote().result()
        say("serve", engine_stats=stats, proxy_stats=serve.http_proxy_stats())

        n_requests = len(warmups) + len(burst)
        if len(answers) != n_requests:
            failures.append(f"{len(answers)} of {n_requests} requests answered")
        if len({answers.get(n) for n in ("warm-short", "same-a", "same-b")}) != 1:
            failures.append("equal prompts, greedy, gave different answers: "
                            + str([answers.get(n) for n in
                                   ("warm-short", "same-a", "same-b")]))
        if stats["failed"] or stats["admitted"] != n_requests or \
                stats["tokens_out"] != n_requests * n_new:
            failures.append(f"engine counters are off: {stats}")
        dev = {"platform": stats["platform"], "kind": stats["device_kind"],
               "count": stats["device_count"]}
        if not args.toy and dev["platform"] != "tpu":
            failures.append(f"the replica ran on {dev}, not on a TPU")
        # the replica is the one process that may hold the chip
        from ray_tpu.accelerators.tpu import jax_backend_is_up

        say("serve", driver_initialised_a_backend=jax_backend_is_up())
        if jax_backend_is_up():
            failures.append("the driver (and its proxy) initialised a JAX backend")
        if failures:
            raise RuntimeError("serve phase failed:\n  " + "\n  ".join(failures))
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    return {"device": dev}


PHASES = {"kernels": phase_kernels, "train": phase_train,
          "train4": phase_train, "serve": phase_serve}


def run_phase_child(args) -> int:
    result = {"ok": False, "device": None}
    try:
        result.update(PHASES[args.phase](args, SIZES["toy" if args.toy else "real"]))
        result["ok"] = True
    except BaseException as e:  # noqa: BLE001 — reported to the parent
        import traceback

        traceback.print_exc()
        say(args.phase, failed=f"{type(e).__name__}: {e}"[:2000])
    with open(args.result_file, "w") as f:
        json.dump(result, f)
    return 0 if result["ok"] else 1


# ---------------------------------------------------------------------------
# Parent: never imports JAX.
# ---------------------------------------------------------------------------

def _session_members(sid: int) -> list:
    """Pids of live processes in session ``sid`` (nothing in ray_tpu calls
    setsid, so a phase's whole tree stays in the session its child opened)."""
    out = []
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        try:
            with open(f"/proc/{name}/stat") as f:
                fields = f.read().rsplit(")", 1)[1].split()
        except (OSError, IndexError):
            continue
        if int(fields[3]) == sid and fields[0] != "Z":
            out.append(int(name))
    return out


def _end_tree(sid: int, grace_s: float) -> list:
    """Wait for session ``sid`` to empty; kill what is left. Returns the
    pids that had to be killed."""
    deadline = time.monotonic() + grace_s
    while _session_members(sid) and time.monotonic() < deadline:
        time.sleep(0.2)
    left = _session_members(sid)
    if left:
        try:
            os.killpg(sid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        time.sleep(0.5)
    return left


def _keep_logs(tmp: str, phase: str) -> None:
    """Copy a failed phase's daemon and worker logs where chiprun brings
    them back from."""
    dest = os.path.join(HERE, "chiprun_out", "chip_smoke", phase)
    os.makedirs(dest, exist_ok=True)
    for root, _dirs, files in os.walk(tmp):
        for name in files:
            if name.endswith(".log"):
                shutil.copy(os.path.join(root, name), os.path.join(
                    dest, os.path.basename(root) + "." + name))
    say(phase, logs_kept_in=dest)


def run_phase(phase: str, args, limit_s: float) -> dict:
    tmp = tempfile.mkdtemp(prefix=f"chip_smoke_{phase}_")
    result_file = os.path.join(tmp, "result.json")
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--result-file", result_file, "--seed", str(args.seed),
           "--chips", str(args.chips)] + (["--toy"] if args.toy else [])
    env = dict(os.environ, TMPDIR=tmp, PYTHONPATH=os.pathsep.join(
        p for p in (HERE, os.environ.get("PYTHONPATH")) if p))
    if args.toy and args.chips > 1 and "xla_force_host_platform_device_count" \
            not in env.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                            f"platform_device_count={args.chips}").strip()
    t0 = time.monotonic()
    say(phase, starting=True, limit_s=round(limit_s))
    proc = subprocess.Popen(cmd, env=env, cwd=HERE, start_new_session=True)
    timed_out = False
    try:
        proc.wait(timeout=limit_s)
    except subprocess.TimeoutExpired:
        timed_out = True
        os.killpg(proc.pid, signal.SIGTERM)
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            pass
    leftover = _end_tree(proc.pid, grace_s=0 if timed_out else 30)
    proc.wait()
    result = {"ok": False, "device": None}
    if os.path.exists(result_file):
        with open(result_file) as f:
            result = json.load(f)
    if timed_out:
        result["ok"] = False
        say(phase, failed=f"timed out after {round(limit_s)} s; tree killed")
    elif leftover:
        result["ok"] = False
        say(phase, failed=f"{len(leftover)} process(es) outlived shutdown "
                          f"and were killed: {leftover}")
    elif proc.returncode != 0:
        result["ok"] = False
    say(phase, ok=result["ok"], seconds=round(time.monotonic() - t0, 1),
        exit_code=proc.returncode, device=result["device"])
    if not result["ok"]:
        _keep_logs(tmp, phase)
    shutil.rmtree(tmp, ignore_errors=True)
    return result


def preflight() -> None:
    """What git commits holds no binaries: build the native pieces now,
    and say so plainly if the tools are missing."""
    try:
        from ray_tpu._private import fastpath
        from ray_tpu._private.object_store.client import ensure_store_built
    except ImportError as e:
        raise RuntimeError(f"the ray_tpu package is not beside {__file__}: {e}")
    tools = {t: shutil.which(t) for t in ("make", "g++", "gcc")}
    t0 = time.monotonic()
    store = ensure_store_built()
    say("preflight", python=sys.version.split()[0], tools=tools, store=store,
        build_s=round(time.monotonic() - t0, 2),
        RAY_TPU_FASTPATH=os.environ.get("RAY_TPU_FASTPATH", "unset"),
        fastpath_backend=fastpath.BACKEND)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--toy", action="store_true",
                    help="debug/tiny widths on whatever device JAX finds")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded train step and its comparison")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--phase", choices=sorted(PHASES), help=argparse.SUPPRESS)
    ap.add_argument("--result-file", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.phase:
        return run_phase_child(args)

    start = time.monotonic()
    say("smoke", toy=args.toy, chips=args.chips, seed=args.seed, cwd=HERE)
    results = []
    try:
        preflight()
        for phase in (["train4"] if args.chips == 4 else
                      ["kernels", "train", "serve"]):
            remaining = TOTAL_LIMIT_S - (time.monotonic() - start)
            results.append(run_phase(phase, args,
                                     min(PHASE_LIMIT_S[phase], remaining)))
            if results[-1]["device"] is None:
                break  # it never reached a device: the next would not either
    except Exception as e:  # noqa: BLE001 — the last line must still come
        say("smoke", failed=f"{type(e).__name__}: {e}")
        results.append({"ok": False, "device": None})
    devices = [r["device"] for r in results if r["device"]]
    ok = all(r["ok"] for r in results) and all(d == devices[0] for d in devices)
    if "jax" in sys.modules:
        ok = False
    say("smoke", parent_imported_jax="jax" in sys.modules,
        devices_seen=devices, seconds=round(time.monotonic() - start, 1))
    device = devices[0] if devices else {"platform": None, "kind": None, "count": 0}
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
