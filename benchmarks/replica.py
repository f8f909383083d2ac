"""The Serve application the serve cells deploy: the program's own
``LLMServer`` with three methods added and the request path left as it is.

Only the process that holds the chip can trace it, and in a serve cell that
is the replica, which has no hook of its own (PERF.md lists one for the
``tracing`` issue). So the benchmark deploys a subclass of the class that
``build_llm_deployment`` returns:

- ``bench_profile_start`` / ``bench_profile_stop``: the JAX profiler around a
  steady window. The replica starts and stops it and does nothing else with
  it: the stop alone takes 10-100 s here (the profiler's own collection of
  the device's events), and the runner reduces the file in its own process
  once the replica is gone (a reduction here would add seconds of Python to
  the GIL that the engine's pump and the stream threads share);
- ``bench_reference_check``: a seeded prompt through the batcher's own
  prefill and batched decode programs, beside other busy slots, against
  ``reference.py``'s full forward, at the published widths, on the chip;
- ``bench_device``: the device and its memory as JAX reports them here.

This module is imported by the Serve driver and must not import JAX at the
top level.
"""

from __future__ import annotations

import os
import time


class IdTokenizer:
    """Token ids as decimal text, no EOS: a client of the HTTP front door
    sends exact ids and counts the ones that come back."""

    def encode(self, text: str):
        return [int(t) for t in text.split()]

    def decode(self, ids) -> str:
        return " ".join(str(int(i)) for i in ids)


def build_application(llm_config, config: dict):
    """``build_llm_deployment(llm_config)`` with the bench methods added.
    ``config`` is the configuration file, for the reference."""
    from ray_tpu.llm import build_llm_deployment
    from ray_tpu.serve.deployment import Deployment

    app = build_llm_deployment(llm_config)
    base = app.deployment._target

    class BenchLLMServer(base):
        def __init__(self):
            from benchmarks import harness

            harness.setup_compile_cache()
            super().__init__()

        def bench_device(self) -> dict:
            import jax

            from benchmarks import harness

            devices = jax.devices()
            stats = devices[0].memory_stats() or {}
            return dict(harness.describe_devices(devices),
                        memory_peak_bytes=harness.memory_peak_bytes(devices),
                        peak_bytes_in_use=stats.get("peak_bytes_in_use"),
                        peak_bytes_reserved=stats.get("peak_bytes_reserved"))

        def bench_profile_start(self, trace_dir: str) -> bool:
            profile_start(trace_dir)
            return True

        def bench_profile_stop(self, trace_dir: str) -> dict:
            return profile_stop(trace_dir)

        def bench_reference_check(self, seed: int, prompt_len: int,
                                  new_tokens: int) -> dict:
            return reference_check(self.engine, config, seed, prompt_len,
                                   new_tokens)

    return Deployment(BenchLLMServer, app.deployment._config).bind()


def profile_start(trace_dir: str) -> None:
    """The profiler without the Python tracer (it slows the host loop it is
    meant to observe); a refused start raises."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    options.raise_error_on_start_failure = True
    os.makedirs(trace_dir, exist_ok=True)
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def profile_stop(trace_dir: str) -> dict:
    """Stop the profiler, which writes its file, and say where and how long
    that took. Reading the file is the runner's, after this process."""
    import jax

    t0 = time.perf_counter()
    jax.profiler.stop_trace()
    return {"trace_dir": trace_dir, "stop_s": time.perf_counter() - t0}


def reference_check(engine, config: dict, seed: int, prompt_len: int,
                    new_tokens: int, neighbours: int = 3) -> dict:
    """A seeded prompt through the programs the cell times, against ONE full
    forward of the reference.

    Logits: the batcher's own prefill program at the prompt's last position
    (the decode program returns sampled tokens, not logits). Tokens: the
    prompt is submitted to the batcher behind ``neighbours`` other prompts
    that decode for twice as long, so it is prefilled, installed in a slot
    and advanced by the batched decode step over the whole cache with other
    slots busy, greedy as every request of the cell is. The reference then
    reads the prompt plus the tokens the system chose and must rank each of
    them first, or within the tolerance of its first."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import reference
    from ray_tpu.models.decoding import SamplingParams

    batcher, cfg = engine.batcher, engine.model_config
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
    bucket = min(batcher._bucket(prompt_len), batcher.max_len)
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :prompt_len] = prompt
    # the bucket was warmed: the program the requests run, not a new one
    last, _, _ = batcher._prefill_jits[bucket](
        batcher.params, jnp.asarray(toks), jnp.asarray([prompt_len], np.int32))
    # neighbours from the same (warmed) bucket, with room to outlast the prompt
    lengths = np.minimum(rng.integers(bucket // 2 + 1, bucket + 1, neighbours),
                         batcher.max_len - 2 * new_tokens - 1)
    others = [batcher.submit(rng.integers(0, cfg.vocab_size, int(n)).tolist(),
                             SamplingParams(max_tokens=2 * new_tokens))
              for n in lengths]
    chosen = batcher.submit(prompt.tolist(),
                            SamplingParams(max_tokens=new_tokens)).result(600)
    for other in others:
        other.result(600)
    seq = np.concatenate([prompt, np.asarray(chosen[:-1], np.int32)])
    ref = np.asarray(reference.logits(batcher.params, seq[None], config,
                                      last=new_tokens)[0])
    out = reference.compare_logits(np.asarray(last, np.float32)[None], ref[:1])
    out["tokens"] = reference.compare_tokens(chosen, ref)
    out["ok"] = out["ok"] and out["tokens"]["ok"]
    out.update(prompt_len=prompt_len, bucket=bucket,
               neighbour_lens=[int(n) for n in lengths])
    return out
