"""Runner of the looped serve cell: ``runners/serve.py``'s deployment, traffic
and accounting, with what this configuration needs replaced (the way
``serve_olmoe.py`` replaces it: the model is the ONE block, built by
``harness.model_config`` with this configuration's own keys through its
``**extra``). From ``serve_mimo.py``, loaded as a private copy whose scope
names are pointed here, come the compiled programs' operations by scope and
the capture of one warmed prefill; from ``serve_kimi_linear.py`` the warmed
prefill's arguments; from ``serve_jamba.py`` where the window's end fell
among the waves (``phases``).

- The model: ``total_ut_steps`` passes over ``num_hidden_layers`` layers with
  a norm behind each sublayer and the exit gate (``loop_steps``, ``sandwich``,
  ``exit_threshold``); what the one block does not run is refused by name.
- The reference check: through ``reference_ouro.py``, through the same timed
  programs (the batcher's own warmed prefill of the 256 bucket, then the
  batched decode beside busy slots through the scheduler), and which path
  each program's attention compiled (``paths_traced``: another than
  ``KERNEL_PATHS`` names is a problem of the run, not a slower result).
- A traced run also times ONE warmed prefill of the cell's bucket under a
  profiler capture of its own, before the window: ``loop_prefill`` (the
  window's trace lies inside a decode phase and holds no prefill).
"""

from __future__ import annotations

import types

from benchmarks import harness, readers, replica

serve = harness.load_module("runners", "serve")  # a copy of our own to rebind
mimo = harness.load_module("runners", "serve_mimo")
jamba = harness.load_module("runners", "serve_jamba")
kimi = mimo.kimi
_account = serve.account
# outermost first, as `scope_ops.SCOPES`
SCOPES = ("attend_cached", "mlp", "loop.pass_end", "lm_head", "sample")
mimo.SCOPES = SCOPES  # what its op-scope maps and its capture sort by
# What each program must have compiled on the chip (`engine_stats()` carries
# the same): the decode kernel over the held rows of cache layer t * layers
# + i. The 256 bucket's prefill attends dense and must: its float32 scores
# are 4 MiB a call, far under `ops.attention.DENSE_SCORES_BYTES` (112 MiB),
# the ONE rule every configuration's prefill reads (PR 53's measurement: below
# it the dense spelling is the faster; ISSUE 64 reckoned with the flash
# forward here, PERF.md section 6, PR 64 has both timed).
KERNEL_PATHS = {"prefill_attention": {"prefill_256": "dense"},
                "decode_attention": {"decode": "kernel"}}
# Limits of the comparison that decides `correct`, each between its two
# readings at the published widths on the chip (PERF.md section 6, PR 64, my
# chip runs: 192 tokens through the 256 bucket, then 8 greedy steps beside
# three busy slots; sixteen checks of sixteen weight seeds): what the system
# gives over seeds, and what `reference_ouro` gives with one thing wrong.
# Prefill logits at the prompt's last position, RMS over the reference's
# standard deviation: the system 0.197-0.310. That is the stated precision's
# own and no fault's: `reference_ouro`'s `precision="stated"` (bfloat16
# operands and stored values, float32 sums) stands 0.266-0.333 from the
# float32 reference by itself, and the system 0.356-0.408 from IT (two
# independent roundings of one size): 384 sublayer outputs, each re-normed
# to unit size, are summed into ONE stream that is re-normed four times, so
# a stored value's 2^-9 does not average out as it does over 16 to 56
# sublayers (the other runners' 0.02-0.12 would refuse every seed). A
# bfloat16 accumulator reads 0.976-1.162, three passes for four 1.148-1.215,
# no norm behind a sublayer 1.290-1.332, no final norm between passes
# 1.002-1.429. One cache for four changes no prefill logit (0.0 from the
# reference): the tokens' limit refuses it.
LOGITS_RMS_MAX = 0.55
# The 8 greedy tokens: how far below the reference's first choice the
# system's token lies at most, in the logits' standard deviations
# (`reference.compare_tokens`, tie-aware): the system 0.094-0.702 in 128
# tokens of sixteen checks (logits 0.2-0.3 off flip the near ties of 49,152);
# one cache for four (a decode step's passes all reading pass 0's rows)
# 3.720-5.119, the bfloat16 accumulator 2.610-4.327, the other three faults
# 3.661-6.063.
TOKENS_SHORTFALL_MAX = 1.3
TOY = dict(num_key_value_heads=4, total_ut_steps=2, torch_dtype="float32")


def toy_config(conf: dict) -> dict:
    """``--toy``: the configuration file at debug widths (``harness.
    TOY_MODEL`` names the dense keys; this family's own follow here): 2
    layers run twice, 4 heads = 4 KV heads, in float32."""
    return dict(conf, **TOY)


def ouro_model_config(conf: dict):
    """The program's TransformerConfig for the published ``config.json`` of
    an ``ouro`` model: the one block with its passes, its sandwich norms and
    its threshold. Every width comes from the file; bf16 parameters."""
    import dataclasses

    import jax.numpy as jnp

    if conf["model_type"] != "ouro" or conf.get("rope_scaling") \
            or conf.get("use_sliding_window") \
            or set(conf["layer_types"]) != {"full_attention"} \
            or len(conf["layer_types"]) < conf["num_hidden_layers"]:
        raise ValueError(
            "the one block as a looped model runs total_ut_steps passes of "
            "full-attention layers, rotated without scaling, no window")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        conf["torch_dtype"]]
    cfg = harness.model_config(
        conf, loop_steps=int(conf["total_ut_steps"]), sandwich=True,
        exit_threshold=float(conf["early_exit_threshold"]), remat=False)
    return dataclasses.replace(cfg, dtype=dtype)  # a toy's float32 stream


def paths_traced(batcher) -> dict:
    """Which path each attention kernel's program compiled, as
    `engine_stats()` has it."""
    return {"prefill_attention": dict(batcher.prefill_attention_path),
            "decode_attention": dict(batcher.decode_attention_path)}


def reference_check(engine, config: dict, seed: int, prompt_len: int,
                    new_tokens: int, neighbours: int = 3,
                    second_readings=()) -> dict:
    """``replica.reference_check`` for this model: a seeded prompt through
    the batcher's own warmed prefill program (logits at its TRUE last
    position, the prompt being shorter than its bucket) and, behind
    ``neighbours`` busy slots, through the scheduler's batched decode step
    (greedy tokens: all 192 cache layers' rows installed, then appended to
    and read by the steps' four passes), against ONE full forward of
    ``reference_ouro`` over the prompt and the chosen tokens.
    ``second_readings`` are (name, keyword arguments of
    ``reference_ouro.logits``) pairs: how the limits were set (the builder's
    calibration alone asks)."""
    import jax
    import numpy as np

    from benchmarks import reference_ouro as reference
    from ray_tpu.models.decoding import SamplingParams

    batcher, cfg = engine.batcher, engine.model_config
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
    program, arguments, bucket = kimi._warmed_prefill(batcher, prompt)
    last = program(*arguments)[0]
    lengths = np.minimum(rng.integers(bucket // 2 + 1, bucket + 1, neighbours),
                         batcher.max_len - 2 * new_tokens - 1)
    others = [batcher.submit(
        rng.integers(0, cfg.vocab_size, int(n)).tolist(),
        SamplingParams(max_tokens=2 * new_tokens)) for n in lengths]
    chosen = batcher.submit(
        prompt.tolist(), SamplingParams(max_tokens=new_tokens)).result(600)
    for other in others:
        other.result(600)
    seq = np.concatenate([prompt, np.asarray(chosen[:-1], np.int32)])
    ref = np.asarray(reference.logits(batcher.params, seq[None], config,
                                      last=new_tokens)[0][0])
    out = reference.compare_logits(
        np.asarray(last, np.float32)[None], ref[:1])
    out.update(tol=LOGITS_RMS_MAX,
               ok=bool(out["rms_err_over_std"] <= LOGITS_RMS_MAX))
    out["tokens"] = tokens = reference.compare_tokens(chosen, ref)
    tokens.update(tol=TOKENS_SHORTFALL_MAX, ok=bool(tokens.get(
        "max_shortfall_over_std", float("inf")) <= TOKENS_SHORTFALL_MAX))
    out["paths_traced"] = paths = paths_traced(batcher)
    # on the chip a program that kept a plain spelling fails the check (off
    # the chip every program does, and says so)
    on_chip = jax.devices()[0].platform == "tpu"
    out["fell_back"] = {
        what: paths[what] for what, want in KERNEL_PATHS.items()
        if on_chip and any(paths[what].get(told) != path
                           for told, path in want.items())}
    out["ok"] = bool(out["ok"] and tokens["ok"] and not out["fell_back"])
    for name, kwargs in second_readings:
        other = np.asarray(reference.logits(
            batcher.params, seq[None], config, last=new_tokens,
            **kwargs)[0][0])
        out.setdefault("second_readings", {})[name] = dict(
            rms_err_over_std=reference.compare_logits(
                np.asarray(last, np.float32)[None], other[:1]
            )["rms_err_over_std"],
            # how far this reading's own logits stand from the reference's
            from_reference_over_std=reference.compare_logits(
                other[:1], ref[:1])["rms_err_over_std"],
            max_shortfall_over_std=reference.compare_tokens(
                chosen, other)["max_shortfall_over_std"])
    out.update(prompt_len=prompt_len, bucket=bucket,
               neighbour_lens=[int(n) for n in lengths],
               passes=cfg.loop_steps, cache_layers=cfg.full_layers,
               op_scopes={readers.DECODE_PROGRAM:
                          mimo.decode_op_scopes(batcher)})
    return out


def build_application(llm_config, config: dict):
    """``replica.build_application`` with the reference check replaced and
    the prefill's capture added."""
    from ray_tpu.serve.deployment import Deployment

    app = replica.build_application(llm_config, config)

    class LoopedBenchLLMServer(app.deployment._target):
        def bench_reference_check(self, seed: int, prompt_len: int,
                                  new_tokens: int) -> dict:
            self._prefill_scopes = mimo.prefill_op_scopes(
                self.engine.batcher, prompt_len)
            return reference_check(self.engine, config, seed, prompt_len,
                                   new_tokens)

        def bench_loop_prefill(self, seed: int, prompt_len: int) -> dict:
            return mimo.mimo_prefill(self.engine, self._prefill_scopes, seed,
                                     prompt_len)

    return Deployment(LoopedBenchLLMServer, app.deployment._config).bind()


class LoopedDeployed(serve.Deployed):
    def __init__(self, cell: dict, args: dict):
        """``serve.Deployed.__init__`` but for the model and the toy's own
        keys."""
        if cell["toy"]:
            cell = dict(cell, config=toy_config(cell["config"]))
        super().__init__(cell, args)
        self.cfg = ouro_model_config(self.conf)

    def measure(self, traffic: dict, seed: int, seconds: float,
                trace: bool = False) -> dict:
        """``serve.Deployed.measure``; a traced run first captures one warmed
        prefill (before the window opens: the capture is set-up)."""
        captured = {}
        if trace:
            captured = self.handle.bench_loop_prefill.remote(
                seed + 2, traffic["warmup_prompt_tokens"][0]).result()
            harness.say("serve", loop_prefill=captured)
        win = super().measure(traffic, seed, seconds, trace)
        win["loop_prefill"] = captured
        return win


def account(dep, traffic, schedule, played, marks) -> dict:
    """``serve.account`` plus where the window's end fell among the waves
    (``serve_jamba.phases``) and what the engine says of the loop."""
    win = _account(dep, traffic, schedule, played, marks)
    win["phases"] = jamba.phases(played, int(traffic["clients"]))
    closed = marks["engine_close"]
    win["loop"] = {k: closed.get(k) for k in ("loop_steps", "kv_layers_kept")}
    if win["loop"] != {"loop_steps": dep.cfg.loop_steps,
                       "kv_layers_kept": dep.cfg.full_layers}:
        win["problems"].append(
            f"the engine says {win['loop']} of its passes and cache layers, "
            f"the configuration {dep.cfg.loop_steps} x {dep.cfg.layers}")
    harness.say("serve", phases=win["phases"], loop=win["loop"])
    return win


serve.Deployed = LoopedDeployed
serve.account = account
serve.replica = types.SimpleNamespace(
    IdTokenizer=replica.IdTokenizer, build_application=build_application)
run = serve.run
