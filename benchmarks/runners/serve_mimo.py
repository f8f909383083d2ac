"""Runner of the MiMo-V2-Flash serve cell: ``runners/serve.py``'s deployment,
traffic and accounting, with what this configuration needs replaced (the way
``serve_laguna.py`` and ``serve_longcat.py`` replace it; the pieces that fit as
they stand are imported from ``serve_kimi_linear.py``: the warmed prefill's
arguments, a request that can be told from its neighbours in the route log,
the shapes of live arrays).

- The model: this configuration's own keys (``hybrid_layer_pattern``,
  ``moe_layer_freq``, ``swa_*``, ``v_head_dim``, ``attention_value_scale``,
  ``add_swa_attention_sink_bias``, ``scoring_func``, ``topk_method``, the held
  share of ``n_routed_experts``) go into a ``TransformerConfig`` built here;
  what ``models/laguna.py`` does not run is refused by name. ``--toy`` narrows
  them too (``toy_config``).
- The reference check: through ``reference_mimo.py``, through the same timed
  programs (the batcher's own warmed prefill of the 8,192 bucket, then the
  batched decode beside busy slots through the scheduler).
- The window also carries the engine's expert counters, the held share among
  them, and the replica maps the decode program's operations to this
  configuration's scopes (``SCOPES``).
- A traced run also times ONE warmed prefill of the cell's bucket under a
  profiler capture of its own, before the window: ``mimo_prefill`` (the
  window's trace lies inside a decode phase and holds no prefill).
"""

from __future__ import annotations

import types

from benchmarks import harness, readers, replica

serve = harness.load_module("runners", "serve")  # a copy of our own to rebind
kimi = harness.load_module("runners", "serve_kimi_linear")
_account = serve.account
COUNTED = ("moe_assignments", "moe_rows", "moe_assignments_held",
           "moe_experts_reached")
# outermost first, as `scope_ops.SCOPES`: an operation under
# attn.window/attend_cached is attn.window's
SCOPES = ("attn.window", "attn.full", "moe_router", "moe_experts", "mlp",
          "lm_head", "sample")
# Limits of the comparison that decides `correct`, each between its two
# readings at the published widths on the chip (PERF.md section 6, PR 58, my
# chip runs: 4,800 tokens through the 8,192 bucket, then 8 greedy steps beside
# busy slots; the reference following the system's sets of experts where they
# are ties): what the system gives over seeds, and what `reference_mimo` gives
# with a bfloat16 accumulator (every projection but the routed experts') or
# with one part dropped or swapped.
# Prefill logits at the prompt's last position, RMS over the reference's
# standard deviation: the system 0.0066-0.0071 in ten checks of ten weight
# seeds; no sink 0.0255, the bfloat16 accumulator 0.0713, no value scale
# 0.296, the kinds' theta swapped 0.313, full attention in a window layer
# 1.06 (no selection bias moves the sets alone, which the reference then
# follows: 0.0071, the system's own; the two limits on the routes refuse it).
LOGITS_RMS_MAX = 0.016
# The 8 greedy tokens keep `reference.compare_tokens`' 0.15 standard
# deviations: the system 0-0.023; the accumulator 0.205, theta swapped 0.87,
# the window 3.6 (no sink 0.0 and no value scale 0.048: the logits' limit and
# the routes' refuse those).
# Sets of experts the reference cannot follow as a tie
# (`reference_mimo.ROUTE_TIE_MARGIN`: the system's largest followed gap is
# 0.0039-0.0064 of its 0.025), of 48,070 (layer, token) pairs: the system 0 in
# every check; no sink 358, no bias 538, the accumulator 1,340, the other
# parts 34,000-44,000.
ROUTES_REFUSED_MAX = 40
# Sets that differ from the reference's own and were followed as ties: the
# system 3,153-3,391; no sink 9,527, no bias 29,022, the accumulator 29,064.
ROUTES_FOLLOWED_MAX = 6000
TOY = dict(
    hidden_size=128, intermediate_size=192, moe_intermediate_size=64,
    num_hidden_layers=7, num_attention_heads=8, num_key_value_heads=2,
    swa_num_attention_heads=8, swa_num_key_value_heads=4, head_dim=24,
    swa_head_dim=24, v_head_dim=16, swa_v_head_dim=16, sliding_window=8,
    sliding_window_size=8, n_routed_experts=4, num_experts_per_tok=2,
    vocab_size=512, torch_dtype="float32",
    hybrid_layer_pattern=[0, 1, 1, 0, 1, 1, 1],
    moe_layer_freq=[0, 1, 1, 1, 1, 1, 1])


def toy_config(conf: dict) -> dict:
    """``--toy``: the configuration file at debug widths
    (``harness.TOY_MODEL`` names the dense keys of other families; this
    family's own follow here), every mechanism kept: full, 2 window, full, 3
    window; two KV-head counts, keys of 24 beside values of 16, a window of 8
    with its sink, sigmoid top-2 of 8 experts of which 4 are held. In
    float32, as Laguna's toy and for its reason."""
    return dict(conf, **TOY,
                published=dict(conf["published"], n_routed_experts=8))


def mimo_model_config(conf: dict):
    """The program's TransformerConfig for the published ``config.json`` of a
    ``mimo_v2_flash`` model, cut to ``num_hidden_layers`` and to the share of
    the experts and of the vocabulary that the file states. Every width comes
    from the file; bf16 parameters."""
    import jax.numpy as jnp

    from ray_tpu.models import transformer as T

    n = conf["num_hidden_layers"]
    kinds = tuple("window" if k else "full"
                  for k in conf["hybrid_layer_pattern"][:n])
    if conf["model_type"] != "mimo_v2_flash" or conf["attention_bias"] \
            or conf["tie_word_embeddings"] or conf["hidden_act"] != "silu" \
            or list(conf["moe_layer_freq"][:n]) != [0] + [1] * (n - 1) \
            or kinds[0] != "full" or conf["add_full_attention_sink_bias"] \
            or not conf["add_swa_attention_sink_bias"] \
            or conf["scoring_func"] != "sigmoid" \
            or conf["topk_method"] != "noaux_tc" or conf["n_group"] != 1 \
            or conf["topk_group"] != 1 or conf["n_shared_experts"] \
            or conf["swa_num_attention_heads"] != conf["num_attention_heads"] \
            or conf["swa_head_dim"] != conf["head_dim"] \
            or conf["swa_v_head_dim"] != conf["v_head_dim"] \
            or conf["sliding_window_size"] != conf["sliding_window"]:
        raise ValueError(
            "models/laguna.py's list form runs one leading full layer with a "
            "dense MLP, then sparse layers of two kinds that differ in their "
            "KV heads, their theta and the window layers' sink alone; a "
            "sigmoid router with a stored bias and one group, no shared "
            "expert, no bias; SiLU, untied")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        conf["torch_dtype"]]
    return T.TransformerConfig(
        vocab_size=conf["vocab_size"], hidden=conf["hidden_size"],
        mlp_hidden=conf["moe_intermediate_size"], layers=n,
        heads=conf["num_attention_heads"],
        kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        max_seq=conf["max_position_embeddings"],
        rope_theta=float(conf["rope_theta"]),
        partial_rotary=float(conf["partial_rotary_factor"]),
        norm_eps=float(conf["layernorm_epsilon"]), remat=False,
        num_experts=conf["published"]["n_routed_experts"],
        experts_per_token=conf["num_experts_per_tok"],
        norm_topk_prob=bool(conf["norm_topk_prob"]),
        routed_scale=float(conf["routed_scaling_factor"] or 1.0),
        experts_held=(int(conf["experts_held_first"]),
                      conf["n_routed_experts"]),
        router_score="sigmoid", lead_kind="", layer_kinds=kinds,
        window=conf["sliding_window"],
        window_heads=conf["swa_num_attention_heads"],
        window_kv_heads=conf["swa_num_key_value_heads"],
        window_rope_theta=float(conf["swa_rope_theta"]),
        window_partial_rotary=float(conf["partial_rotary_factor"]),
        value_dim=conf["v_head_dim"], window_sink=True,
        value_scale=float(conf["attention_value_scale"]),
        dense_mlp_hidden=conf["intermediate_size"],
        dtype=dtype, param_dtype=dtype)


def reference_check(engine, config: dict, seed: int, prompt_len: int,
                    new_tokens: int, neighbours: int = 3,
                    second_readings=()) -> dict:
    """``serve_laguna.reference_check`` for this model: a seeded prompt
    through the batcher's own prefill program (logits at its TRUE last
    position, the prompt being shorter than its bucket and 37 windows long)
    and, behind ``neighbours`` busy slots, through the scheduler's batched
    decode step (greedy tokens: the rings installed with the rows, then
    written round by the steps), against ONE full forward of
    ``reference_mimo`` over the prompt and the chosen tokens, a query head at
    a time. The reference follows the sets of experts the programs took where
    its own scores call them a tie, and refuses them elsewhere.
    ``second_readings`` are (name, keyword arguments of
    ``reference_mimo.logits``) pairs: how the limits were set (the builder's
    calibration alone asks)."""
    import numpy as np

    from benchmarks import reference_mimo as reference
    from ray_tpu.models.decoding import SamplingParams

    batcher, cfg = engine.batcher, engine.model_config
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
    program, arguments, bucket = kimi._warmed_prefill(batcher, prompt)
    last, _, _, _, _, load, choice, _ = program(*arguments)
    lengths = np.minimum(rng.integers(bucket // 2 + 1, bucket + 1, neighbours),
                         batcher.max_len - 2 * new_tokens - 1)
    batcher.route_log = log = []
    try:
        others = [batcher.submit(
            rng.integers(0, cfg.vocab_size, int(n)).tolist(),
            SamplingParams(max_tokens=2 * new_tokens)) for n in lengths]
        mine = batcher._enqueue(kimi.batcher_request(prompt, new_tokens))
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
                     and followed["refused"] <= ROUTES_REFUSED_MAX
                     and followed["followed"] <= ROUTES_FOLLOWED_MAX)
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
               prefill_attention_path=dict(batcher.prefill_attention_path),
               op_scopes={readers.DECODE_PROGRAM: decode_op_scopes(batcher)})
    return out


def decode_op_scopes(batcher) -> dict:
    """``scope_ops.op_scopes`` of the decode program as the pump runs it, by
    this configuration's ``SCOPES``."""
    import jax
    import numpy as np

    from benchmarks import scope_ops

    def per_slot(dtype):
        return jax.ShapeDtypeStruct((batcher.slots,), dtype)

    like = kimi._like
    compiled = batcher._decode_jit.lower(
        like(batcher.params), per_slot(np.int32), like(batcher.cache),
        like(batcher._rng), per_slot(np.float32), per_slot(np.int32),
        per_slot(np.bool_)).compile()
    return scope_ops.op_scopes(compiled.as_text(), SCOPES)


def prefill_op_scopes(batcher, prompt_len: int) -> dict:
    """``scope_ops.op_scopes`` of the warmed prefill program of
    ``prompt_len``'s bucket (compiled again from the cache: part of the
    reference check, before the run counts compilations)."""
    import numpy as np

    from benchmarks import scope_ops

    program, arguments, _ = kimi._warmed_prefill(
        batcher, np.zeros(prompt_len, np.int32))
    return scope_ops.op_scopes(
        program.lower(*kimi._like(arguments)).compile().as_text(), SCOPES)


def mimo_prefill(engine, mapped: dict, seed: int, prompt_len: int) -> dict:
    """Device time of ONE warmed prefill of ``prompt_len`` seeded tokens from
    a profiler capture of its own around that one call, whole, by this
    configuration's scopes (``mapped`` is ``prefill_op_scopes``') and of its
    Pallas calls of three inputs (the two-width flash forward of the full
    layers; a long prefill's grouped matmuls are ``ragged_dot``s, no kernel):
    {"ms_per_req", "by_scope_ms", "flash_ms", "bucket"}, or {} where the
    capture shows no such program."""
    import shutil
    import tempfile

    import jax
    import numpy as np

    from benchmarks import trace_reduce

    batcher = engine.batcher
    prompt = np.random.default_rng(seed).integers(
        0, engine.model_config.vocab_size, prompt_len).astype(np.int32)
    program, arguments, bucket = kimi._warmed_prefill(batcher, prompt)
    scope_at = {op: scope for scope, ops in mapped.items() for op in ops}
    trace_dir = tempfile.mkdtemp(prefix="mimo_prefill_")
    try:
        replica.profile_start(trace_dir)
        try:
            jax.block_until_ready(program(*arguments))
        finally:
            replica.profile_stop(trace_dir)
        summary = trace_reduce.reduce_dir(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    whole = sum(v["total_s"] for n, v in
                (summary or {}).get("programs", {}).items()
                if readers.PREFILL_PROGRAM in n)
    if not whole:
        return {}
    by_scope = {}
    for name, seconds in summary.get("op_self_s", {}).items():
        program_name, _, op = name.rpartition("/")
        if readers.PREFILL_PROGRAM in program_name and op in scope_at:
            by_scope[scope_at[op]] = by_scope.get(scope_at[op], 0.0) \
                + seconds * 1e3
    return {"ms_per_req": whole * 1e3, "by_scope_ms": by_scope,
            "flash_ms": trace_reduce.kernel_self_s(
                summary, readers.FLASH_FORWARD) * 1e3,
            "bucket": bucket,
            # where the rest went: the largest operations and kinds, ms
            "top_ops": [[n, round(s * 1e3, 2)]
                        for n, s in summary.get("device_ops", [])],
            "top_kinds": [[n, round(s * 1e3, 2)]
                          for n, s in summary.get("device_op_kinds", [])]}


def build_application(llm_config, config: dict):
    """``replica.build_application`` with the reference check replaced and
    the prefill's capture added."""
    from ray_tpu.serve.deployment import Deployment

    app = replica.build_application(llm_config, config)

    class MiMoBenchLLMServer(app.deployment._target):
        def bench_reference_check(self, seed: int, prompt_len: int,
                                  new_tokens: int) -> dict:
            self._prefill_scopes = prefill_op_scopes(self.engine.batcher,
                                                     prompt_len)
            return reference_check(self.engine, config, seed, prompt_len,
                                   new_tokens)

        def bench_mimo_prefill(self, seed: int, prompt_len: int) -> dict:
            return mimo_prefill(self.engine, self._prefill_scopes, seed,
                                prompt_len)

    return Deployment(MiMoBenchLLMServer, app.deployment._config).bind()


class MiMoDeployed(serve.Deployed):
    def __init__(self, cell: dict, args: dict):
        """``serve.Deployed.__init__`` but for the model: it asks
        ``harness.model_config``, which reads another family's keys."""
        self.cell, self.args = cell, args
        self.traffic, self.toy = cell["traffic"], cell["toy"]
        self.conf = toy_config(cell["config"]) if self.toy else cell["config"]
        self.sv = dict(self.conf["serve"], **(
            self.traffic.get("toy_serve", {}) if self.toy else {}))
        self.cfg = mimo_model_config(self.conf)
        self.n_new = int(self.traffic["new_tokens"])
        self.tok = replica.IdTokenizer()
        self.problems = []

    def measure(self, traffic: dict, seed: int, seconds: float,
                trace: bool = False) -> dict:
        """``serve.Deployed.measure``; a traced run first captures one warmed
        prefill (before the window opens: the capture is set-up)."""
        captured = {}
        if trace:
            captured = self.handle.bench_mimo_prefill.remote(
                seed + 2, traffic["warmup_prompt_tokens"][0]).result()
            harness.say("serve", mimo_prefill=captured)
        win = super().measure(traffic, seed, seconds, trace)
        win["mimo_prefill"] = captured
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


serve.Deployed = MiMoDeployed
serve.account = account
serve.replica = types.SimpleNamespace(
    IdTokenizer=replica.IdTokenizer, build_application=build_application)
run = serve.run
