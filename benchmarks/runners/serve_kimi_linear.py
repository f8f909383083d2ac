"""Runner of the Kimi-Linear serve cell: ``runners/serve.py``'s deployment,
traffic and accounting, with what this configuration needs replaced (the way
``serve_laguna.py`` replaces it).

- The model: ``harness.model_config`` reads one kind of layer; this
  configuration's keys (``linear_attn_config``'s two layer lists, head count,
  head size and taps, ``kv_lora_rank``, ``qk_nope_head_dim``,
  ``qk_rope_head_dim``, ``v_head_dim``, ``first_k_dense_replace``,
  ``moe_router_activation_func``, ``moe_renormalize``,
  ``routed_scaling_factor``, ``num_shared_experts``, the held share of
  ``num_experts``) go into a ``TransformerConfig`` built here. ``--toy``
  narrows the pattern too (``toy_config``).
- The reference check: through ``reference_kimi_linear.py``, through the same
  timed programs (the batcher's own warmed prefill of the 2,048 bucket, then
  the batched decode beside busy slots through the scheduler).
- The window also carries the engine's expert counters, the held share among
  them, and the replica maps the decode program's operations to this
  configuration's scopes (``SCOPES``).
- A traced run also times ONE warmed prefill of the cell's bucket under a
  profiler capture of its own, before the window (the window's 2 s trace lies
  inside a decode phase and holds no prefill): ``kda_prefill``.

Everything else (the front door, the replica, the load generator, the window)
is ``serve.py``'s own code, loaded as a private copy of that module whose
``Deployed``, ``account`` and ``replica`` names are pointed here.
"""

from __future__ import annotations

import types

from benchmarks import harness, readers, replica

serve = harness.load_module("runners", "serve")  # a copy of our own to rebind
_account = serve.account
COUNTED = ("moe_assignments", "moe_rows", "moe_assignments_held",
           "moe_experts_reached", "state_installs", "state_resets")
# outermost first, as `scope_ops.SCOPES`
SCOPES = ("kda.project", "kda.conv", "kda.gate", "kda.state",
          "kda.prefill_scan", "kda.out", "mla.project", "mla.attend",
          "mla.out", "moe.shared", "moe_router", "moe_experts", "mlp",
          "lm_head", "sample")
KDA_SCOPES = tuple(s for s in SCOPES if s.startswith("kda."))
# Limits of the comparison that decides `correct`, each between its two
# readings at the published widths and depth on the chip (my chip runs, PR 38;
# PERF.md section 6: 1,200 tokens through the 2,048 bucket, then 8 greedy
# steps beside busy slots; the reference following the system's sets of
# experts where they are ties): what the system gives over fifteen weight seeds,
# and what `reference_kimi_linear` gives with a bfloat16 accumulator (all
# matmuls but the routed experts' and the recurrence's own), with a bfloat16
# STATE, or with one part dropped.
# Prefill logits at the prompt's last position, RMS over the reference's
# standard deviation: the system 0.0450-0.0521 (27 layers); the bfloat16 state
# 0.073 (0.0716-0.0730 in three readings), the selection bias dropped 0.050
# (it moves the sets alone, which the reference then follows: the routes
# refuse it), the shared key part dropped 0.093, the factor 2.446 dropped
# 0.38, the bfloat16 accumulator 0.47, the decay, beta, the output gate, the
# convolution or the shared expert dropped 1.18-1.36.
LOGITS_RMS_MAX = 0.0625
# The 8 greedy tokens keep `reference.compare_tokens`' 0.15 standard
# deviations: the system 0-0.090; the factor 0.65, the accumulator 0.78, the
# five large parts 3.9-5.8 (the bfloat16 state, the bias and the shared key
# part read 0 here: the two limits beside this one refuse them).
# Sets of experts the reference cannot follow as a tie
# (`reference_kimi_linear.ROUTE_TIE_MARGIN`, which has its readings), of 31,382
# (layer, token) pairs: the system 0-3; the bfloat16 state 56, the bias
# dropped 935, the shared key part 2,380, the others 18,000 to all.
ROUTES_REFUSED_MAX = 12
TOY = dict(
    num_hidden_layers=11, num_key_value_heads=4,
    linear_attn_config=dict(
        kda_layers=[1, 2, 3, 5, 6, 7, 9, 10], full_attn_layers=[4, 8, 11],
        head_dim=16, num_heads=4, short_conv_kernel_size=4),
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    num_experts=8, num_experts_per_token=4, moe_intermediate_size=64,
    torch_dtype="float32")


def toy_config(conf: dict) -> dict:
    """``--toy``: the configuration file at debug widths. ``harness.TOY_MODEL``
    has narrowed the dense keys; the pattern's own keys follow here, every
    mechanism kept (a leading linear layer, two periods and the trailing pair,
    a latent of 32 + 8, 16 experts of which 8 are held, top-4). In float32,
    as Laguna's toy and for its reason."""
    return dict(conf, **TOY, published=dict(conf["published"], num_experts=16))


def kimi_model_config(conf: dict):
    """The program's TransformerConfig for the published ``config.json`` of a
    ``kimi_linear`` model at its published depth, cut to the share of the
    experts and of the vocabulary that the file states. Every width comes
    from the file; bf16 parameters."""
    import jax.numpy as jnp

    from benchmarks.reference_kimi_linear import kinds_of
    from ray_tpu.models import transformer as T

    lin = conf["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    if conf["model_type"] != "kimi_linear" or conf["tie_word_embeddings"] \
            or conf["hidden_act"] != "silu" or conf["q_lora_rank"] \
            or not conf["mla_use_nope"] or conf["rope_scaling"] \
            or conf["first_k_dense_replace"] != 1 \
            or conf["moe_layer_freq"] != 1 or conf["num_shared_experts"] != 1 \
            or conf["num_expert_group"] != 1 or conf["topk_group"] != 1 \
            or conf["num_nextn_predict_layers"] \
            or conf["moe_router_activation_func"] != "sigmoid" \
            or conf["num_attention_heads"] != heads \
            or conf["qk_nope_head_dim"] != d or conf["v_head_dim"] != d:
        raise ValueError(
            "models/kimi_linear.py runs one leading layer with a dense MLP, "
            "then sparse layers with one shared expert and an ungrouped "
            "sigmoid router; latent attention without rotation, a query "
            "low-rank or a head size other than the linear layers'")
    kinds = kinds_of(conf)
    body = kinds[1:]
    period, tail = next(  # the shortest period the layers behind the first
        (tuple(body[:size]), tuple(body[len(body) // size * size:]))  # repeat
        for size in range(1, len(body) + 1)
        if body[:len(body) // size * size]
        == body[:size] * (len(body) // size))
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        conf["torch_dtype"]]
    return T.TransformerConfig(
        vocab_size=conf["vocab_size"], hidden=conf["hidden_size"],
        mlp_hidden=conf["moe_intermediate_size"],
        layers=conf["num_hidden_layers"], heads=heads, kv_heads=heads,
        head_dim=d, max_seq=conf["model_max_length"],
        norm_eps=float(conf["rms_norm_eps"]), remat=False,
        num_experts=conf["published"]["num_experts"],
        experts_per_token=conf["num_experts_per_token"],
        norm_topk_prob=bool(conf["moe_renormalize"]),
        routed_scale=float(conf["routed_scaling_factor"]),
        experts_held=(int(conf["experts_held_first"]), conf["num_experts"]),
        shared_expert_hidden=conf["moe_intermediate_size"],
        dense_mlp_hidden=conf["intermediate_size"], lead_kind=kinds[0],
        layer_kinds=period, tail_kinds=tail,
        kda_conv=lin["short_conv_kernel_size"],
        mla_latent=conf["kv_lora_rank"],
        mla_rope_dim=conf["qk_rope_head_dim"], router_score="sigmoid",
        dtype=dtype, param_dtype=dtype)


def _warmed_prefill(batcher, prompt):
    """(the bucket's warmed program, its arguments) for ``prompt``: the
    program the requests run, not a new one."""
    import jax.numpy as jnp
    import numpy as np

    bucket = min(batcher._bucket(len(prompt)), batcher.max_len)
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :len(prompt)] = prompt
    return batcher._prefill_jits[bucket], (
        batcher.params, jnp.asarray(toks),
        jnp.asarray([len(prompt)], np.int32)), bucket


def reference_check(engine, config: dict, seed: int, prompt_len: int,
                    new_tokens: int, neighbours: int = 3,
                    second_readings=()) -> dict:
    """``serve_laguna.reference_check`` for this model: a seeded prompt
    through the batcher's own prefill program (logits at its TRUE last
    position, the prompt being shorter than its bucket and many chunks of the
    scan long) and, behind ``neighbours`` busy slots, through the scheduler's
    batched decode step (greedy tokens: the matrix states, windows and latent
    rows installed, then rewritten and appended to by the steps), against ONE
    full forward of ``reference_kimi_linear`` over the prompt and the chosen
    tokens, the recurrence a position at a time and attention expanded. The
    reference follows the sets of experts the programs took where its own
    scores call them a tie, and refuses them elsewhere. ``second_readings``
    are (name, keyword arguments of ``reference_kimi_linear.logits``) pairs:
    how the limits were set (the builder's calibration alone asks)."""
    import numpy as np

    from benchmarks import reference_kimi_linear as reference
    from ray_tpu.models.decoding import SamplingParams

    batcher, cfg = engine.batcher, engine.model_config
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
    program, arguments, bucket = _warmed_prefill(batcher, prompt)
    last, *_, load, choice, _ = program(*arguments)
    lengths = np.minimum(rng.integers(bucket // 2 + 1, bucket + 1, neighbours),
                         batcher.max_len - 2 * new_tokens - 1)
    batcher.route_log = log = []
    try:
        others = [batcher.submit(
            rng.integers(0, cfg.vocab_size, int(n)).tolist(),
            SamplingParams(max_tokens=2 * new_tokens)) for n in lengths]
        mine = batcher._enqueue(batcher_request(prompt, new_tokens))
        chosen = mine.future.result(600)
        for other in others:
            other.result(600)
    finally:
        batcher.route_log = None
    # the sets of the chosen request: its admit's positions (the first entry
    # that names it), then its slot's row of every step that advanced it
    entries = [(slot, c) for reqs, c in log for slot, r in reqs.items()
               if r is mine]
    routes = [entries[0][1][:, :prompt_len]] + [
        c[:, [slot]] for slot, c in entries[1:]]
    seq = np.concatenate([prompt, np.asarray(chosen[:-1], np.int32)])
    route = np.concatenate(routes, axis=1)  # [sparse layers, tokens, k]
    ref, followed = reference.logits(
        batcher.params, seq[None], config, last=new_tokens,
        follow=route[:, :len(seq)])
    ref = np.asarray(ref[0])
    out = reference.compare_logits(
        np.asarray(last, np.float32)[None], ref[:1])
    out.update(tol=LOGITS_RMS_MAX,
               ok=bool(out["rms_err_over_std"] <= LOGITS_RMS_MAX))
    out["tokens"] = reference.compare_tokens(chosen, ref)
    out["routes"] = dict(
        {k: v for k, v in followed.items() if k != "chosen"},
        logged=int(route.shape[1]), wanted=len(seq),
        admit_is_the_program=bool(np.array_equal(
            routes[0], np.asarray(choice)[:, :prompt_len])))
    out["ok"] = bool(out["ok"] and out["tokens"]["ok"]
                     and route.shape[1] == len(seq)
                     and out["routes"]["admit_is_the_program"]
                     and followed["refused"] <= ROUTES_REFUSED_MAX)
    # dropless, pad rows not counted: the prefill program's own counter, over
    # ALL the published experts, and the share of it that is held here
    load = np.asarray(load)
    first, count = cfg.experts_held
    out["prefill_assignments"] = int(load.sum())
    out["prefill_held_share"] = float(
        load[first:first + count].sum() / max(load.sum(), 1))
    want = prompt_len * cfg.experts_per_token * cfg.sparse_layers
    if out["prefill_assignments"] != want:
        out.update(ok=False, prefill_assignments_expected=want)
    for name, kwargs in second_readings:
        other, told = reference.logits(
            batcher.params, seq[None], config, last=new_tokens,
            follow=route[:, :len(seq)], **kwargs)
        other = np.asarray(other[0])
        out.setdefault("second_readings", {})[name] = dict(
            refused=told["refused"], followed=told["followed"],
            max_followed_gap=told["max_followed_gap"],
            rms_err_over_std=reference.compare_logits(
                np.asarray(last, np.float32)[None], other[:1]
            )["rms_err_over_std"],
            max_shortfall_over_std=reference.compare_tokens(
                chosen, other)["max_shortfall_over_std"])
    out.update(prompt_len=prompt_len, bucket=bucket,
               neighbour_lens=[int(n) for n in lengths],
               op_scopes={readers.DECODE_PROGRAM: decode_op_scopes(batcher)})
    return out


def _like(tree):
    import jax

    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=a.sharding), tree)


def decode_op_scopes(batcher) -> dict:
    """``scope_ops.op_scopes`` of the decode program as the pump runs it, by
    this configuration's ``SCOPES`` (``serve_zaya.decode_op_scopes`` says why
    it is read off the compiled text, here, before the window)."""
    import jax
    import numpy as np

    from benchmarks import scope_ops

    def per_slot(dtype):
        return jax.ShapeDtypeStruct((batcher.slots,), dtype)

    compiled = batcher._decode_jit.lower(
        _like(batcher.params), per_slot(np.int32), _like(batcher.cache),
        _like(batcher._rng), per_slot(np.float32), per_slot(np.int32),
        per_slot(np.bool_)).compile()
    return scope_ops.op_scopes(compiled.as_text(), SCOPES)


def prefill_op_scopes(batcher, prompt_len: int) -> dict:
    """``scope_ops.op_scopes`` of the warmed prefill program of
    ``prompt_len``'s bucket (compiled again from the cache: part of the
    reference check, before the run counts compilations)."""
    import numpy as np

    from benchmarks import scope_ops

    program, arguments, _ = _warmed_prefill(
        batcher, np.zeros(prompt_len, np.int32))
    return scope_ops.op_scopes(
        program.lower(*_like(arguments)).compile().as_text(), SCOPES)


def kda_prefill(engine, mapped: dict, seed: int, prompt_len: int) -> dict:
    """Device time of ONE warmed prefill of ``prompt_len`` seeded tokens, and
    the part of it under the linear-attention layers' scopes (the chunked
    scan, the projections, convolutions and gates; ``mapped`` is
    ``prefill_op_scopes``'), from a profiler capture of its own around that
    one call: {"ms_per_req", "prefill_ms", "by_scope_ms"}, or {} where the
    capture shows no such operation."""
    import shutil
    import tempfile

    import jax
    import numpy as np

    from benchmarks import trace_reduce

    batcher = engine.batcher
    prompt = np.random.default_rng(seed).integers(
        0, engine.model_config.vocab_size, prompt_len).astype(np.int32)
    program, arguments, _ = _warmed_prefill(batcher, prompt)
    scope_at = {op: scope for scope, ops in mapped.items() for op in ops}
    trace_dir = tempfile.mkdtemp(prefix="kda_prefill_")
    try:
        replica.profile_start(trace_dir)
        try:
            jax.block_until_ready(program(*arguments))
        finally:
            replica.profile_stop(trace_dir)
        summary = trace_reduce.reduce_dir(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    by_scope = {}
    for name, seconds in (summary or {}).get("op_self_s", {}).items():
        program_name, _, op = name.rpartition("/")
        if readers.PREFILL_PROGRAM in program_name and op in scope_at:
            by_scope[scope_at[op]] = by_scope.get(scope_at[op], 0.0) \
                + seconds * 1e3
    kda = sum(ms for scope, ms in by_scope.items() if scope in KDA_SCOPES)
    if not kda:
        return {}
    whole = sum(v["total_s"] for n, v in summary["programs"].items()
                if readers.PREFILL_PROGRAM in n)
    return {"ms_per_req": kda, "prefill_ms": whole * 1e3,
            "by_scope_ms": by_scope}


def batcher_request(prompt, new_tokens: int):
    """A request as ``ContinuousBatcher.submit`` builds it, kept so that its
    entries of the route log can be told from its neighbours'."""
    from concurrent.futures import Future

    from ray_tpu.models.continuous_batching import _Request
    from ray_tpu.models.decoding import SamplingParams

    return _Request(list(map(int, prompt)),
                    SamplingParams(max_tokens=new_tokens), Future(), None)


def build_application(llm_config, config: dict):
    """``replica.build_application`` with the reference check replaced and
    the prefill's capture added."""
    from ray_tpu.serve.deployment import Deployment

    app = replica.build_application(llm_config, config)

    class KimiLinearBenchLLMServer(app.deployment._target):
        def bench_reference_check(self, seed: int, prompt_len: int,
                                  new_tokens: int) -> dict:
            self._prefill_scopes = prefill_op_scopes(self.engine.batcher,
                                                     prompt_len)
            return reference_check(self.engine, config, seed, prompt_len,
                                   new_tokens)

        def bench_kda_prefill(self, seed: int, prompt_len: int) -> dict:
            return kda_prefill(self.engine, self._prefill_scopes, seed,
                               prompt_len)

    return Deployment(KimiLinearBenchLLMServer, app.deployment._config).bind()


class KimiLinearDeployed(serve.Deployed):
    def __init__(self, cell: dict, args: dict):
        """``serve.Deployed.__init__`` but for the model: it asks
        ``harness.model_config``, which reads one kind of layer."""
        self.cell, self.args = cell, args
        self.traffic, self.toy = cell["traffic"], cell["toy"]
        self.conf = toy_config(cell["config"]) if self.toy else cell["config"]
        self.sv = dict(self.conf["serve"], **(
            self.traffic.get("toy_serve", {}) if self.toy else {}))
        self.cfg = kimi_model_config(self.conf)
        self.n_new = int(self.traffic["new_tokens"])
        self.tok = replica.IdTokenizer()
        self.problems = []

    def measure(self, traffic: dict, seed: int, seconds: float,
                trace: bool = False) -> dict:
        """``serve.Deployed.measure``; a traced run first captures one warmed
        prefill (before the window opens: the capture is set-up)."""
        captured = {}
        if trace:
            captured = self.handle.bench_kda_prefill.remote(
                seed + 2, traffic["reference_prompt_tokens"]).result()
            harness.say("serve", kda_prefill=captured)
        win = super().measure(traffic, seed, seconds, trace)
        win["kda_prefill"] = captured
        return win


def account(dep, traffic, schedule, played, marks) -> dict:
    """``serve.account`` plus the window's expert counters; ``layers`` are
    the layers that ROUTE, so that ``moe_assignments_per_token`` divides by
    them."""
    win = _account(dep, traffic, schedule, played, marks)
    opened, closed = marks["engine_open"], marks["engine_close"]
    win["moe"] = dict(
        {k: closed[k] - opened[k] for k in COUNTED},
        expert_load=[b - a for a, b in zip(opened["moe_expert_load"],
                                           closed["moe_expert_load"])],
        layers=dep.cfg.sparse_layers)
    return win


serve.Deployed = KimiLinearDeployed
serve.account = account
serve.replica = types.SimpleNamespace(
    IdTokenizer=replica.IdTokenizer, build_application=build_application)
run = serve.run
