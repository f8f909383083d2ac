"""Runner of the train cells: ``JaxTrainer.fit`` with the benchmark's own
loop, one worker that holds all the cell's chips.

With one worker ``JaxTrainer`` runs the loop in the calling process, so this
process owns the chips and is the one that can trace them. The loop builds
the mesh and ``make_train_step`` as a user would, takes the warm-up steps,
checks the system's loss, logits and adapter gradients against
``reference.py`` on a seeded sample, then measures whole steps on fresh
batches from a seeded host generator (one batch prefetched) until
``--seconds`` have passed.

Steps are kept one ahead of the device (``_loop.burst``): step k+1 is
dispatched before the host waits for step k's metrics, as a training loop
that logs the last step's loss does. What the host does between two steps
then costs the device nothing, so a host whose cores are shared does not
slow the steps. The rate is tokens per step over the MEDIAN time between
the ends of consecutive steps of the window: a stall of the host or the
machine that stretches a few steps does not move it (PERF.md section 6,
third round: under the driver a window's mean rate of the older loop, which
waited for every step before it dispatched the next, spread by 6%).

The state is NOT built by the program's ``init_state``: that bakes its seed
into the program, so every new ``--seed`` would recompile it (51-58 s on one
chip, 151 s on four; ``_init_state`` below). ``setup_s`` and ``compile_s``
therefore leave out the ``init_state`` compile a user pays, and a repair of
it shows in no cell until the runner can call it again (PERF.md section 7).

The window starts with the device drained and ends with the step that was
in flight when ``--seconds`` had passed.
"""

from __future__ import annotations

import contextlib
import os
import queue
import threading
import time

from benchmarks import harness, reference, trace_reduce

SAMPLE = (2, 512)  # the seeded sample the loss is compared on
PROBE_LAST = 64  # and the last positions whose logits are compared


def probe_program(cfg, mesh):
    """(params, tokens) -> the system's loss on ``tokens``, its gradient
    with respect to the adapters and its logits at the last positions: the
    model's own loss, differentiated as the step differentiates it, with the
    attention (and its backward kernels) and the recomputation the step
    uses."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import transformer as T
    from ray_tpu.train import step as S

    attn = S.make_attn_fn(cfg, mesh)

    @jax.jit
    def probe(params, tokens):
        def loss_of(lora):
            return T.loss_fn(cfg, dict(params, lora=lora), {"tokens": tokens},
                             attn_fn=attn)[0]

        loss, grads = jax.value_and_grad(loss_of)(params["lora"])
        logits = T.forward(cfg, params, tokens, attn_fn=attn)
        return loss, grads, logits[:, -PROBE_LAST:].astype(jnp.float32)

    return probe


def _batches(seed: int, vocab: int, batch: int, seq: int, prefetch: int,
             stop: threading.Event) -> "queue.Queue":
    """Fresh token batches from a seeded host generator, ``prefetch`` ahead."""
    import numpy as np

    out: "queue.Queue" = queue.Queue(maxsize=prefetch)
    rng = np.random.default_rng(seed)

    def produce():
        while not stop.is_set():
            item = {"tokens": rng.integers(0, vocab, (batch, seq),
                                           dtype=np.int32)}
            while not stop.is_set():
                try:
                    out.put(item, timeout=0.1)
                    break
                except queue.Full:
                    continue

    threading.Thread(target=produce, daemon=True, name="bench-data").start()
    return out


def _init_state(cfg, opt, mesh, seed: int):
    """``train/step.init_state`` with the seed as an argument of the program.

    ``init_state(seed=...)`` also writes the seed into the state's ``rng``
    field as a constant, so every new seed is a new program and a cold
    compile (51-55 s for this state, my chip run, PR 22). The weights come
    from ``--seed`` through the key, which is an argument; the ``rng`` field,
    which the step never reads, stays at seed 0. PERF.md lists the program's
    own fix for a later PR."""
    import functools

    import jax

    from ray_tpu.train import step as S

    init = functools.partial(S.fresh_state, cfg, opt, seed=0)
    with jax.set_mesh(mesh):
        return jax.jit(init, out_shardings=S.state_shardings(cfg, opt, mesh))(
            jax.random.key(seed))


def _span(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX + name)


def _loop(config: dict) -> None:
    """The train loop: runs inside JaxTrainer on the process that holds the
    chips, and reports through ``ray_tpu.train.report``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu import train
    from ray_tpu.parallel.bootstrap import watch_compiles
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    from ray_tpu.train import step as S

    cell, args = config["cell"], config["args"]
    conf, traffic, toy = cell["config"], cell["traffic"], cell["toy"]
    tr = conf["train"]
    compiles = watch_compiles()
    cache_dir = harness.setup_compile_cache()
    devices = jax.devices()[:cell["chips"]] if toy else jax.devices()
    device = harness.describe_devices(devices)
    harness.require_chips(device, cell["chips"], toy)
    cfg = harness.model_config(conf, lora_rank=tr["lora_rank"],
                               lora_alpha=tr["lora_alpha"], remat=tr["remat"])
    batch, seq = traffic["batch"], traffic["seq"]
    harness.say("train", device=device, cache_dir=cache_dir,
                params_b=round(cfg.num_params() / 1e9, 3), batch=batch,
                seq=seq, mesh=tr["mesh"])

    mesh = build_mesh(MeshSpec(**tr["mesh"]), devices)
    opt = S.default_optimizer(cfg)
    t0 = time.perf_counter()
    state = jax.block_until_ready(_init_state(cfg, opt, mesh, args["seed"]))
    init_s = time.perf_counter() - t0
    step = S.make_train_step(cfg, opt, mesh)
    stop = threading.Event()
    feed = _batches(args["seed"], cfg.vocab_size, batch, seq,
                    traffic.get("prefetch", 1), stop)
    losses, step_s, dispatch_s, data_wait_s = [], [], [], []

    def dispatch(state, tracing):
        # every step, warm-up and traced ones too, goes through this one
        # line: a step that holds Pallas kernels is keyed in the persistent
        # cache by the Python call stack that traced it
        t0 = time.perf_counter()
        with _span("data", tracing):
            item = feed.get()
        t1 = time.perf_counter()
        with _span("dispatch", tracing):
            state, metrics = step(state, item)
        data_wait_s.append(t1 - t0)
        dispatch_s.append(time.perf_counter() - t1)
        return state, metrics

    def burst(state, go_on, tracing=False):
        """Steps while ``go_on()``, kept one ahead of the device; begins and
        ends with the device drained. ``step_s`` gets, for each step, the
        time from the end of the step before it (or the burst's start) to
        its own end, which ``block_until_ready`` on its metrics marks."""
        last = time.perf_counter()
        state, pending = dispatch(state, tracing)
        while pending is not None:
            nxt = None
            if go_on():
                state, nxt = dispatch(state, tracing)
            with _span("wait", tracing):
                losses.append(float(jax.block_until_ready(pending)["loss"]))
            now = time.perf_counter()
            step_s.append(now - last)
            last, pending = now, nxt
        return state

    def steps(count):
        upto = len(dispatch_s) + count
        return lambda: len(dispatch_s) < upto

    try:
        state = burst(state, steps(traffic["warmup_steps"]))
        first_step_done = time.time()
        harness.say("train", init_s=round(init_s, 2),
                    warmup_step_s=[round(s, 3) for s in step_s],
                    compile_s=round(compiles["compile_s"], 2),
                    cache_hits=compiles["cache_hits"],
                    cache_misses=compiles["cache_misses"])

        # the system against the plain reference, outside the window
        sample = np.random.default_rng(args["seed"] + 1).integers(
            0, cfg.vocab_size, SAMPLE, dtype=np.int32)
        t0 = time.perf_counter()
        with jax.set_mesh(mesh):
            sys_loss, sys_grads, sys_logits = jax.device_get(
                probe_program(cfg, mesh)(state["params"], jnp.asarray(sample)))
        ref_loss, ref_grads, ref_logits = reference.loss_and_lora_grads(
            state["params"], sample, conf, lora_alpha=tr["lora_alpha"],
            last=PROBE_LAST, device=devices[0])
        check = reference.compare_loss(float(sys_loss), ref_loss)
        check["logits"] = reference.compare_logits(sys_logits, ref_logits)
        check["grads"] = reference.compare_grads(sys_grads, ref_grads)
        # the real step applies its update: the adapters' B leaves start at
        # zero, and AdamW moves every entry whose gradient is not zero
        moved = min(float(np.mean(jax.device_get(leaf) != 0))
                    for name, leaf in state["params"]["lora"].items()
                    if name.endswith("_b"))
        check["update"] = {"adapters_moved_share": moved,
                           "steps": int(state["step"]),
                           "ok": moved > 0.99 and int(state["step"]) == len(step_s)}
        check["ok"] = (check["ok"] and check["logits"]["ok"]
                       and check["grads"]["ok"] and check["update"]["ok"])
        harness.say("train", reference_check=check,
                    seconds=round(time.perf_counter() - t0, 2))

        n_warm = len(step_s)
        compiles_before = dict(compiles)
        trace_dir = os.path.join(args["out_dir"], "trace")
        trace_steps = traffic.get("trace_steps", 4)
        window_t0_wall = time.time()
        window_t0 = time.perf_counter()
        for attempt in range(2 if args["trace"] else 0):
            state = burst(state, steps(traffic.get("trace_after_steps", 3)))
            jax.profiler.start_trace(trace_dir, profiler_options=_quiet())
            with _span("between-steps", True):
                state = burst(state, steps(trace_steps), tracing=True)
            jax.profiler.stop_trace()
            if trace_reduce.has_xplane(trace_dir):
                break
            # seen once on the chip (PR 22): a session that wrote nothing,
            # and no error. One more try, a few steps on.
            harness.say("train", trace_wrote_nothing_in_attempt=attempt)
        state = burst(
            state, lambda: time.perf_counter() - window_t0 < args["seconds"])
        window_s = time.perf_counter() - window_t0
    finally:
        stop.set()
    in_window = compiles["compile_s"] - compiles_before["compile_s"]
    summary = {}
    if args["trace"]:
        t0 = time.perf_counter()
        summary = trace_reduce.reduce_dir(
            trace_dir, sample_to=args.get("sample_to", ""))
        harness.say("train", trace_reduced_s=round(time.perf_counter() - t0, 2),
                    busy_s=summary.get("busy_s"),
                    window_s=summary.get("window_s"),
                    lines_seen=summary.get("lines_seen"),
                    device_op_kinds=summary.get("device_op_kinds"),
                    programs=summary.get("programs"))
    train.report({
        "device": device,
        "memory_peak_bytes": harness.memory_peak_bytes(devices),
        "check": check, "losses": losses, "n_warm": n_warm,
        "step_s": step_s, "dispatch_s": dispatch_s,
        "data_wait_s": data_wait_s, "window_s": window_s,
        "window_t0_wall": window_t0_wall,
        "first_step_done_wall": first_step_done, "init_s": init_s,
        "compile_s": compiles_before["compile_s"],
        "cache_hits": compiles_before["cache_hits"],
        "cache_misses": compiles_before["cache_misses"],
        "compile_s_in_window": in_window,
        "compiles_in_window": (compiles["cache_hits"] + compiles["cache_misses"]
                               - compiles_before["cache_hits"]
                               - compiles_before["cache_misses"]),
        "trace": summary, "trace_steps": trace_steps,
        "tokens_per_step": batch * seq,
    })


def _quiet():
    """Profiler options for a traced window: no Python tracer (it slows the
    host loop it is meant to observe); host spans and the device stay on."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    options.raise_error_on_start_failure = True
    return options


def run(cell: dict, args: dict) -> dict:
    """One run of a train cell. Returns the harness's result record."""
    import ray_tpu
    from ray_tpu.train import JaxTrainer, ScalingConfig

    try:
        ray_tpu.init(num_tpus=cell["chips"] if cell["toy"] else None,
                     log_to_driver=False)
        deadline = time.monotonic() + 20
        while not ray_tpu.cluster_resources() and time.monotonic() < deadline:
            time.sleep(0.05)  # init returns before the raylet has registered
        fit_t0 = time.time()
        result = JaxTrainer(
            _loop, train_loop_config={"cell": cell, "args": args},
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                         chips_per_worker=cell["chips"]),
        ).fit()
    finally:
        ray_tpu.shutdown()
    if result.error is not None:
        raise RuntimeError(f"JaxTrainer.fit failed:\n{result.error}")
    m = result.metrics
    import numpy as np

    measured = m["step_s"][m["n_warm"]:]
    losses = m["losses"]
    # the median time between the ends of consecutive steps, not the
    # window's mean: a stall that stretches a few steps does not move it
    tokens_per_s_per_chip = (m["tokens_per_step"] / float(np.median(measured))
                             / cell["chips"])
    window_mean_rate = (len(measured) * m["tokens_per_step"] / m["window_s"]
                        / cell["chips"])
    harness.say("train", steps=len(measured),
                tokens_per_s_per_chip=round(tokens_per_s_per_chip, 2),
                window_mean_rate=round(window_mean_rate, 2),
                step_ms_min_p25_p50_p75_max=[
                    round(1e3 * float(q), 3)
                    for q in np.percentile(measured, [0, 25, 50, 75, 100])])
    problems = []
    if not m["check"]["ok"]:
        problems.append(f"the step differs from the reference: {m['check']}")
    if not all(np.isfinite(losses)):
        problems.append("a loss is not finite")
    if m["compiles_in_window"]:
        problems.append(f"{m['compiles_in_window']} compilation(s) inside "
                        f"the window ({m['compile_s_in_window']:.2f} s)")
    counters = {
        "train_tokens_per_s_per_chip": tokens_per_s_per_chip,
        "setup_s": m["window_t0_wall"] - args["t0_wall"],
        "fit_to_first_step_s": m["first_step_done_wall"] - fit_t0,
        "step_s": measured, "data_wait_s": m["data_wait_s"][m["n_warm"]:],
        "dispatch_s": m["dispatch_s"][m["n_warm"]:],
        "compile_s": m["compile_s"], "cache_hits": m["cache_hits"],
        "cache_misses": m["cache_misses"], "init_s": m["init_s"],
        "tokens_per_step": m["tokens_per_step"],
        "trace_steps": m["trace_steps"], "window_s": m["window_s"],
        "window_mean_tokens_per_s_per_chip": window_mean_rate,
        "lora_rank": cell["config"]["train"]["lora_rank"],
        "seq": cell["traffic"]["seq"], "batch": cell["traffic"]["batch"],
        "first_loss": losses[0], "last_loss": losses[-1],
        "reference_check": m["check"],
    }
    return {
        "correct": not problems, "problems": problems,
        "attempted": len(measured), "failed": 0,
        "device": dict(m["device"], memory_peak_bytes=m["memory_peak_bytes"]),
        "counters": counters, "trace": m["trace"],
    }
