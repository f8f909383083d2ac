"""Runner of the LongCat-Flash serve cell: ``runners/serve.py``'s deployment,
traffic and accounting, with what this configuration needs replaced (the way
``serve_kimi_linear.py`` replaces it, from which the pieces that fit as they
stand are imported: the warmed prefill's arguments, a request that can be told
from its neighbours in the route log, the compiled decode program).

- The model: this configuration's own keys (``num_layers`` that counts DOUBLE
  layers, ``ffn_hidden_size``, ``expert_ffn_hidden_size``, ``moe_topk``,
  ``zero_expert_num``, ``q_lora_rank``, the two ``mla_scale_*`` flags, the
  held share of ``n_routed_experts``) go into a ``TransformerConfig`` built
  here; what ``models/longcat.py`` does not run is refused by name. ``--toy``
  narrows them too (``toy_config``).
- The reference check: through ``reference_longcat.py``, through the same
  timed programs (the batcher's own warmed prefill of the 4,096 bucket, in
  blocks of queries, then the batched decode beside busy slots through the
  scheduler).
- The window also carries the engine's expert counters, the three classes of
  a choice apart, and the replica maps the decode program's operations to
  this configuration's scopes (``SCOPES``).
- A traced run also times ONE warmed prefill of the cell's bucket under a
  profiler capture of its own, before the window: ``longcat_prefill``.
"""

from __future__ import annotations

import math
import types

from benchmarks import harness, readers, replica

serve = harness.load_module("runners", "serve")  # a copy of our own to rebind
kimi = harness.load_module("runners", "serve_kimi_linear")
_account = serve.account
COUNTED = ("moe_assignments", "moe_rows", "moe_assignments_held",
           "moe_experts_reached", "moe_assignments_zero",
           "moe_assignments_absent", "moe_rows_gathered", "moe_routed_most")
# outermost first, as `scope_ops.SCOPES`
SCOPES = ("mla.project", "mla.rotate", "mla.attend", "mla.out", "scmoe.dense",
          "moe_router", "moe.zero", "moe_experts", "lm_head", "sample")
# Limits of the comparison that decides `correct`, each between its two
# readings at the published widths on the chip (my chip runs, PR 44; PERF.md
# section 6: 2,400 tokens through the 4,096 bucket, then 8 greedy steps beside
# busy slots, the reference following the system's sets where they are ties):
# what the system gives over seventeen weight seeds, and what
# `reference_longcat` gives with a bfloat16 accumulator (every projection but
# the routed experts') or with one part dropped.
# Prefill logits at the prompt's last position, RMS over the reference's
# standard deviation: the system 0.0127-0.0147; the bfloat16 accumulator
# 0.145, the factor 6 dropped 0.42, the zero-compute part 0.51, the shortcut
# joined before the second attention 0.52, no rotation 0.56, the query's
# factor 0.68, the latent's 1.27 (the selection bias moves the sets alone,
# which the reference then follows: 0.0130, the system's own).
LOGITS_RMS_MAX = 0.05
# The 8 greedy tokens keep `reference.compare_tokens`' 0.15 standard
# deviations: the system 0-0.026; the accumulator 0.187, the parts 0.98-4.6.
# Sets of outputs the reference cannot follow as a tie
# (`reference_longcat.ROUTE_TIE_MARGIN`, which has its readings), of 9,628
# (layer, token) pairs: the system 0; the accumulator 1,328 at a margin of
# 0.2, the parts 5,200-9,600.
ROUTES_REFUSED_MAX = 4
# Sets that differ from the reference's own and were followed as ties: the
# system 1,422-1,621; without the selection bias 3,189 (every one a tie: the
# margin cannot refuse it), the accumulator 7,477.
ROUTES_FOLLOWED_MAX = 2300
TOY = dict(
    hidden_size=128, ffn_hidden_size=192, expert_ffn_hidden_size=64,
    moe_intermediate_size=64, num_layers=3, num_attention_heads=4,
    kv_lora_rank=32, q_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, n_routed_experts=4, zero_expert_num=8, moe_topk=4,
    vocab_size=512, torch_dtype="float32")


def toy_config(conf: dict) -> dict:
    """``--toy``: the configuration file at debug widths
    (``harness.TOY_MODEL`` names the dense keys of other families; this
    family's own follow here), every mechanism kept: 3 double layers, a
    low-rank query, the rotation and the factors, 16 routed experts of which
    4 are held beside 8 zero-compute outputs, top-4. In float32, as Laguna's
    toy and for its reason."""
    return dict(conf, **TOY,
                published=dict(conf["published"], n_routed_experts=16))


def longcat_model_config(conf: dict):
    """The program's TransformerConfig for the published ``config.json`` of a
    ``longcat_flash`` model, cut to ``num_layers`` double layers and to the
    share of the routed experts and of the vocabulary that the file states.
    Every width comes from the file; bf16 parameters."""
    import jax.numpy as jnp

    from ray_tpu.models import transformer as T

    if conf.get("attention_method") != "MLA" or conf.get("attention_bias") \
            or conf.get("zero_expert_type") != "identity" \
            or conf.get("router_bias") or conf.get("rope_scaling") \
            or conf.get("tie_word_embeddings") \
            or conf.get("hidden_act", "silu") != "silu" \
            or conf["v_head_dim"] != conf["qk_nope_head_dim"] \
            or not conf["q_lora_rank"]:
        raise ValueError(
            "models/longcat.py runs double layers of latent attention (MLA, "
            "a low-rank query, values as wide as the unrotated keys, no bias, "
            "no rope scaling) with zero-compute experts of type 'identity' "
            "and a router without a bias in its classifier; SiLU, untied")
    h = conf["hidden_size"]
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        conf["torch_dtype"]]
    return T.TransformerConfig(
        vocab_size=conf["vocab_size"], hidden=h,
        mlp_hidden=conf["expert_ffn_hidden_size"], layers=conf["num_layers"],
        heads=conf["num_attention_heads"],
        kv_heads=conf["num_attention_heads"],
        head_dim=conf["qk_nope_head_dim"],
        max_seq=conf["max_position_embeddings"],
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]), remat=False,
        num_experts=conf["published"]["n_routed_experts"],
        experts_per_token=conf["moe_topk"], norm_topk_prob=False,
        routed_scale=float(conf["routed_scaling_factor"]),
        experts_held=(int(conf["experts_held_first"]),
                      conf["n_routed_experts"]),
        zero_experts=conf["zero_expert_num"],
        dense_mlp_hidden=conf["ffn_hidden_size"], layer_kinds=("scmoe",),
        lead_kind="", mla_latent=conf["kv_lora_rank"],
        mla_rope_dim=conf["qk_rope_head_dim"], mla_q_rank=conf["q_lora_rank"],
        mla_rotate=True, mla_scales=(
            math.sqrt(h / conf["q_lora_rank"])
            if conf["mla_scale_q_lora"] else 1.0,
            math.sqrt(h / conf["kv_lora_rank"])
            if conf["mla_scale_kv_lora"] else 1.0),
        dtype=dtype, param_dtype=dtype)


def reference_check(engine, config: dict, seed: int, prompt_len: int,
                    new_tokens: int, neighbours: int = 3,
                    second_readings=()) -> dict:
    """``serve_kimi_linear.reference_check`` for this model: a seeded prompt
    through the batcher's own prefill program (logits at its TRUE last
    position, the prompt being shorter than its bucket and five blocks of
    queries long) and, behind ``neighbours`` busy slots, through the
    scheduler's batched decode step (greedy tokens: the latent rows of every
    sublayer installed, then appended to by the absorbed steps), against ONE
    full forward of ``reference_longcat`` over the prompt and the chosen
    tokens, attention expanded, a head at a time. The reference follows the
    sets of outputs the programs took where its own scores call them a tie,
    and refuses them elsewhere. ``second_readings`` are (name, keyword
    arguments of ``reference_longcat.logits``) pairs: how the limits were set
    (the builder's calibration alone asks)."""
    import numpy as np

    from benchmarks import reference_longcat as reference
    from ray_tpu.models.decoding import SamplingParams

    batcher, cfg = engine.batcher, engine.model_config
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
    program, arguments, bucket = kimi._warmed_prefill(batcher, prompt)
    last, *_, load, choice, _, _, _ = program(*arguments)
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
    route = np.concatenate(routes, axis=1)  # [double layers, tokens, k]
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
    # dropless, pad rows not counted: the prefill program's own counter over
    # ALL the router's outputs, and the shares of it by class
    load = np.asarray(load)
    first, count = cfg.experts_held
    out["prefill_assignments"] = int(load.sum())
    out["prefill_held_share"] = float(
        load[first:first + count].sum() / max(load.sum(), 1))
    out["prefill_zero_share"] = float(
        load[cfg.num_experts:].sum() / max(load.sum(), 1))
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


def longcat_prefill(engine, mapped: dict, seed: int, prompt_len: int) -> dict:
    """Device time of ONE warmed prefill of ``prompt_len`` seeded tokens from
    a profiler capture of its own around that one call, whole and by this
    configuration's scopes (``mapped`` is ``prefill_op_scopes``': the blocked
    attention under ``mla.attend``, the dense MLPs, the grouped matmuls over
    12 rows a token): {"ms_per_req", "by_scope_ms"}, or {} where the capture
    shows no such program."""
    import shutil
    import tempfile

    import jax
    import numpy as np

    from benchmarks import trace_reduce

    batcher = engine.batcher
    prompt = np.random.default_rng(seed).integers(
        0, engine.model_config.vocab_size, prompt_len).astype(np.int32)
    program, arguments, _ = kimi._warmed_prefill(batcher, prompt)
    scope_at = {op: scope for scope, ops in mapped.items() for op in ops}
    trace_dir = tempfile.mkdtemp(prefix="longcat_prefill_")
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
    return {"ms_per_req": whole * 1e3, "by_scope_ms": by_scope}


def build_application(llm_config, config: dict):
    """``replica.build_application`` with the reference check replaced and
    the prefill's capture added."""
    from ray_tpu.serve.deployment import Deployment

    app = replica.build_application(llm_config, config)

    class LongCatBenchLLMServer(app.deployment._target):
        def bench_reference_check(self, seed: int, prompt_len: int,
                                  new_tokens: int) -> dict:
            self._prefill_scopes = prefill_op_scopes(self.engine.batcher,
                                                     prompt_len)
            return reference_check(self.engine, config, seed, prompt_len,
                                   new_tokens)

        def bench_longcat_prefill(self, seed: int, prompt_len: int) -> dict:
            return longcat_prefill(self.engine, self._prefill_scopes, seed,
                                   prompt_len)

    return Deployment(LongCatBenchLLMServer, app.deployment._config).bind()


class LongCatDeployed(serve.Deployed):
    def __init__(self, cell: dict, args: dict):
        """``serve.Deployed.__init__`` but for the model: it asks
        ``harness.model_config``, which reads another family's keys."""
        self.cell, self.args = cell, args
        self.traffic, self.toy = cell["traffic"], cell["toy"]
        self.conf = toy_config(cell["config"]) if self.toy else cell["config"]
        self.sv = dict(self.conf["serve"], **(
            self.traffic.get("toy_serve", {}) if self.toy else {}))
        self.cfg = longcat_model_config(self.conf)
        self.n_new = int(self.traffic["new_tokens"])
        self.tok = replica.IdTokenizer()
        self.problems = []

    def measure(self, traffic: dict, seed: int, seconds: float,
                trace: bool = False) -> dict:
        """``serve.Deployed.measure``; a traced run first captures one warmed
        prefill (before the window opens: the capture is set-up)."""
        captured = {}
        if trace:
            captured = self.handle.bench_longcat_prefill.remote(
                seed + 2, traffic["warmup_prompt_tokens"][0]).result()
            harness.say("serve", longcat_prefill=captured)
        win = super().measure(traffic, seed, seconds, trace)
        win["longcat_prefill"] = captured
        return win


def account(dep, traffic, schedule, played, marks) -> dict:
    """``serve.account`` plus the window's expert counters; ``layers`` are
    the layers that ROUTE (one a double layer), so that
    ``moe_assignments_per_token`` divides by them."""
    win = _account(dep, traffic, schedule, played, marks)
    opened, closed = marks["engine_open"], marks["engine_close"]
    win["moe"] = dict(
        {k: closed[k] - opened[k] for k in COUNTED},
        expert_load=[b - a for a, b in zip(opened["moe_expert_load"],
                                           closed["moe_expert_load"])],
        layers=dep.cfg.sparse_layers)
    return win


serve.Deployed = LongCatDeployed
serve.account = account
serve.replica = types.SimpleNamespace(
    IdTokenizer=replica.IdTokenizer, build_application=build_application)
run = serve.run
