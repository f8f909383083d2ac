"""LLM serving on ray_tpu.serve (reference: python/ray/llm/_internal/
serve/ — LLM deployments over vLLM with batched + streamed responses).

``build_llm_deployment(config)`` returns a Serve Application whose
replica holds one compiled engine:

- ``__call__(prompt)`` — completion text; concurrent requests are
  merged into one device batch by @serve.batch (MXU utilization),
- ``generate_stream(prompt)`` — generator of text deltas, served over
  the handle's streaming path / HTTP chunked responses.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional

from ray_tpu import serve
from ray_tpu._private import streaming
from ray_tpu.llm.config import LLMConfig
from ray_tpu.models.decoding import SamplingParams
from ray_tpu.observability import schema
from ray_tpu.observability.timeline import setup_phase
from ray_tpu.observability.tracing import device_span
from ray_tpu.ops import traced


def build_llm_deployment(config: LLMConfig):
    """Build (not deploy) the Serve application for ``config``."""

    @serve.deployment(
        name=config.name,
        num_replicas=config.num_replicas,
        # concurrent handlers feed the continuous batcher's one device
        # loop — the replica must accept overlapping requests
        max_ongoing_requests=max(8, config.cache_slots * 2),
        ray_actor_options=(
            {"resources": config.resources} if config.resources else None),
    )
    class LLMServer:
        def __init__(self):
            from ray_tpu.parallel.bootstrap import watch_compiles

            self._compiles = watch_compiles()
            with setup_phase("ray_tpu.setup.engine.build"):
                if config.continuous_batching:
                    from ray_tpu.llm.engine import ContinuousLLMEngine

                    self.engine = ContinuousLLMEngine(config)
                else:
                    from ray_tpu.llm.engine import LLMEngine

                    self.engine = LLMEngine(config)
            self.tokenizer = self.engine.tokenizer

        @serve.batch(max_batch_size=config.batch_max_size,
                     batch_wait_timeout_s=config.batch_wait_timeout_s)
        def _generate_batch(self, prompts):
            return self.engine.generate(prompts)

        def __call__(self, prompt: str) -> str:
            if config.continuous_batching:
                from ray_tpu.serve import slo

                # iteration-level scheduling: this request joins the
                # running decode batch the moment a KV slot frees; the
                # wait is bounded by the request's deadline (expiry →
                # DeadlineExceededError → 504 at the front door)
                return slo.result_within_deadline(
                    self.engine.submit(prompt))
            return self._generate_batch(prompt)

        def engine_stats(self) -> dict:
            """The batcher's counters, this process's CPU seconds, what it
            has sent as a producer of streams, and the device this
            replica's engine runs on, as JAX reports it in THIS process."""
            import jax

            batcher = getattr(self.engine, "batcher", None)
            st = getattr(batcher, "stats", None)
            out = dict(st) if st is not None else {}
            # per jitted program, the implementation each choice made at
            # its trace fell on (`traced.TOLD` says which and their values)
            for booked in traced.TOLD.values():
                if getattr(batcher, booked, None):
                    out[booked] = getattr(batcher, booked)
            cfg = getattr(batcher, "cfg", None)
            if cfg is not None:
                # the passes a program runs its layers in, and the cache
                # layers of K/V rows a sequence keeps for them
                out.update(loop_steps=cfg.loop_steps,
                           kv_layers_kept=cfg.full_layers)
                if cfg.kinds:  # a pattern's layers, counted by kind
                    out["layers_by_kind"] = {
                        kind: cfg.layers_of(kind)
                        for kind in dict.fromkeys(cfg.kinds)}
            devices = jax.devices()
            mem = devices[0].memory_stats() or {}
            out.update(self._compiles)
            # CPU seconds of this process, all threads: over an interval, a
            # whole core of a Python process is a saturated GIL
            out["process_cpu_s"] = time.process_time()
            # `stream_calls`, `stream_items_sent`
            out.update(streaming.send_stats())
            out.update(platform=devices[0].platform,
                       device_kind=devices[0].device_kind,
                       device_count=len(devices),
                       peak_bytes_in_use=mem.get("peak_bytes_in_use"))
            return out

        def generate_stream(self, prompt: str,
                            max_tokens: Optional[int] = None):
            """Yields text deltas for one prompt (token-level streaming)."""
            sampling = self.engine.config.sampling
            if max_tokens is not None:
                sampling = dataclasses.replace(sampling,
                                               max_tokens=max_tokens)
            eos = getattr(self.tokenizer, "eos_token_id", None)
            if sampling.stop_token_id is None and eos is not None:
                sampling = dataclasses.replace(sampling, stop_token_id=eos)
            ids = self.tokenizer.encode(prompt)
            if config.continuous_batching:
                stream = self.engine.submit_stream(ids, sampling)
            else:
                stream = self.engine.generator.generate_stream(
                    ids, sampling, seed=self.engine.next_seed())
            yield from text_deltas(self.tokenizer, stream)

    return LLMServer.bind()


def text_deltas(tokenizer, stream):
    """Text deltas of a stream of token ids, decoded incrementally: beside
    the ids two offsets into them, `prefix` <= `read`. Every id taken
    decodes `ids[prefix:]` and `ids[prefix:read]`, a window of a handful of
    ids whatever the answer's length, and yields what the first text has
    beyond the second. The ids before `read` are decoded again only to be
    subtracted: they keep a `decode` that looks at the left neighbour (a
    leading-space rule, byte-fallback pieces of one character) right
    without the whole list. Where the new text is longer and does not end
    in an incomplete character (U+FFFD at its end) the window moves on,
    `prefix <- read`, `read <- len(ids)`; else the ids are held and nothing
    is yielded until the character completes (the window grows only
    then), and the stream's end flushes what is still held. So the deltas'
    concatenation is `decode(all ids)` and no delta is ever taken back. Each
    id taken is a `ray_tpu.replica.detokenize` span [ids: the ids of the
    answer so far; decoded: the ids this turn hands to `decode`; backlog:
    the ids the engine has emitted for this stream and this thread has not
    taken yet, 0 while the handler keeps up with the pump]."""
    backlog = getattr(stream, "backlog", int)  # a stream without a queue: 0
    decode = tokenizer.decode
    ids = []
    prefix = read = 0

    def unread():
        return decode(ids[prefix:])[len(decode(ids[prefix:read])):]

    for t in stream:
        ids.append(t)
        with device_span(schema.REPLICA_DETOKENIZE, ids=len(ids),
                         decoded=(len(ids) - prefix) + (read - prefix),
                         backlog=backlog()):
            delta = unread()
            held = not delta or delta.endswith("\ufffd")
            if not held:
                prefix, read = read, len(ids)
        if not held:
            yield delta
    if read < len(ids):  # held to the end: an incomplete character, or none
        delta = unread()
        if delta:
            yield delta


def serve_llm(config: LLMConfig):
    """Deploy and return the live handle (reference: ray.llm serve
    entrypoints)."""
    return serve.run(build_llm_deployment(config), name=config.name)
