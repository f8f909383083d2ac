"""In-process LLM engine: tokenizer + compiled generator.

Reference: the vLLM engine wrapper (python/ray/llm/_internal/serve/
engines/vllm/vllm_engine.py) — ours drives ray_tpu.models.decoding.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Union

from ray_tpu.llm.config import LLMConfig
from ray_tpu.models.decoding import Generator, SamplingParams
from ray_tpu.observability.timeline import setup_phase


class LLMEngine:
    def __init__(self, config: LLMConfig):
        import jax

        from ray_tpu.models import transformer as T
        from ray_tpu.parallel.bootstrap import (
            configure_compilation_cache,
            process_compiles,
        )

        configure_compilation_cache()
        with setup_phase("ray_tpu.setup.engine.backend"):
            jax.devices()
        self.config = config
        self.tokenizer = config.get_tokenizer()
        cfg = T.config(config.model)
        vocab = getattr(self.tokenizer, "vocab_size", None)
        if vocab and vocab > cfg.vocab_size:
            # model must cover the tokenizer's id space
            cfg = T.config(cfg, vocab_size=int(vocab))
        self.model_config = cfg
        compiles = process_compiles()
        programs = compiles["programs"]
        with setup_phase("ray_tpu.setup.engine.params") as attrs:
            if config.params_path:
                from ray_tpu.train.checkpoint import restore_state

                params_shape = jax.eval_shape(
                    lambda: T.init_params(cfg, jax.random.key(0)))
                params = restore_state(config.params_path,
                                       target=params_shape)
            else:
                params = T.init_params(cfg, jax.random.key(config.seed))
            params = jax.block_until_ready(params)
            attrs.update(
                bytes=sum(p.nbytes for p in jax.tree.leaves(params)),
                programs=compiles["programs"] - programs)
        self.generator = Generator(cfg, params, max_len=config.max_len)
        self._call_count = 0

    def next_seed(self) -> int:
        """Fresh seed per call: temperature sampling must differ across
        requests for the same prompt (deterministic given config.seed
        and call order, so tests stay reproducible)."""
        self._call_count += 1
        return self.config.seed + self._call_count

    def generate_tokens(self, prompts: Sequence[Sequence[int]],
                        sampling: Optional[SamplingParams] = None
                        ) -> List[List[int]]:
        sampling = sampling or self.config.sampling
        return self.generator.generate(
            [list(p) for p in prompts], sampling, seed=self.next_seed())

    def _with_eos(self, sampling: SamplingParams) -> SamplingParams:
        tok = self.tokenizer
        if sampling.stop_token_id is None and \
                getattr(tok, "eos_token_id", None) is not None:
            import dataclasses

            sampling = dataclasses.replace(
                sampling, stop_token_id=tok.eos_token_id)
        return sampling

    def generate(self, prompts: Sequence[Union[str, Sequence[int]]],
                 sampling: Optional[SamplingParams] = None) -> List[str]:
        """Text in → text out (token-id prompts pass through encode)."""
        tok = self.tokenizer
        sampling = self._with_eos(sampling or self.config.sampling)
        ids = [tok.encode(p) if isinstance(p, str) else list(p)
               for p in prompts]
        # empty prompts would index position -1 at prefill; give them BOS=0
        ids = [p if p else [0] for p in ids]
        outs = self.generate_tokens(ids, sampling)
        return [tok.decode(o) for o in outs]


class ContinuousLLMEngine(LLMEngine):
    """Engine whose device loop is a ContinuousBatcher: concurrent
    callers share decode steps, new requests join the running batch the
    moment a slot frees (reference: vLLM iteration-level scheduling —
    models/continuous_batching.py is the TPU-native core)."""

    def __init__(self, config: LLMConfig):
        super().__init__(config)
        from ray_tpu.models.continuous_batching import ContinuousBatcher

        self.batcher = ContinuousBatcher(
            self.model_config, self.generator.params,
            max_len=config.max_len, slots=config.cache_slots,
            seed=config.seed)
        # ONE tree a process: the batcher put the leaves its decode step
        # reads in another layout there and deleted the ones it was given
        self.generator.params = self.batcher.params

    def submit(self, prompt: Union[str, Sequence[int]],
               sampling: Optional[SamplingParams] = None):
        """Thread-safe; returns a Future resolving to the completion
        TEXT."""
        from concurrent.futures import Future

        tok = self.tokenizer
        sampling = self._with_eos(sampling or self.config.sampling)
        ids = tok.encode(prompt) if isinstance(prompt, str) else list(prompt)
        inner = self.batcher.submit(ids or [0], sampling)
        out: Future = Future()

        def _chain(f):
            # concurrent.futures swallows callback exceptions: a decode
            # failure must still resolve `out` or the caller hangs
            try:
                exc = f.exception()
                if exc is not None:
                    out.set_exception(exc)
                else:
                    # raycheck: disable=RC001 — done-callback: f resolved
                    out.set_result(tok.decode(f.result()))
            except BaseException as e:  # noqa: BLE001
                if not out.done():
                    out.set_exception(e)

        inner.add_done_callback(_chain)
        return out

    def submit_stream(self, prompt: Union[str, Sequence[int]],
                      sampling: Optional[SamplingParams] = None):
        """Yields token ids as the batcher emits them."""
        tok = self.tokenizer
        sampling = self._with_eos(sampling or self.config.sampling)
        ids = tok.encode(prompt) if isinstance(prompt, str) else list(prompt)
        return self.batcher.submit_stream(ids or [0], sampling)

    def shutdown(self) -> None:
        self.batcher.shutdown()
