"""Runner of the Laguna serve cell: ``runners/serve.py``'s deployment, traffic
and accounting, with the three things this configuration needs replaced (the
way ``serve_zaya.py`` replaces them).

- The model: ``harness.model_config`` refuses ``sliding_window`` by name and
  reads one head count; this configuration's keys (``layer_types``,
  ``num_attention_heads_per_layer``, ``sliding_window``, ``rope_parameters`` by
  layer type, ``gating``, ``mlp_only_layers``, ``moe_intermediate_size``,
  ``shared_expert_intermediate_size``, ``moe_routed_scaling_factor``, the held
  share of ``num_experts``) go into a ``TransformerConfig`` built here.
  ``--toy`` narrows the pattern too (``toy_config``): the program's and the
  reference's configuration are then the same toy dictionary.
- The reference check: through ``reference_laguna.py``, through the same timed
  programs (the batcher's own warmed prefill of the 2,048 bucket, then the
  batched decode beside busy slots through the scheduler).
- The window also carries the engine's expert counters, the held share among
  them, and the replica maps the decode program's operations to this
  configuration's scopes (``SCOPES``).

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
           "moe_experts_reached")
# outermost first, as `scope_ops.SCOPES`: an operation under
# attn.window/attend_cached is attn.window's
SCOPES = ("attn.window", "attn.full", "moe.shared", "moe_router",
          "moe_experts", "mlp", "lm_head", "sample")
KINDS = {"full_attention": "full", "sliding_attention": "window"}
# Limits of the comparison that decides `correct`, each between its two
# readings at the published widths on the chip (PERF.md section 6, PR 34; 1,200
# tokens through the 2,048 bucket, then 8 greedy steps beside busy slots; the
# reference following the system's sets of experts where they are ties): what
# the system gives over seeds, and what `reference_laguna` gives with a
# bfloat16 accumulator (all matmuls but the routed experts') or with one part
# dropped or swapped.
# Prefill logits at the prompt's last position, RMS over the reference's
# standard deviation: the system 0.020-0.030 in six checks and 0.019-0.035 over
# 192 single positions of four weight seeds (5 layers, but ten renormalised
# weights times 2.5); full attention in a window layer 0.31, the bfloat16
# accumulator 0.35, no factor 2.5 0.56, no gate 1.07, no shared expert 1.13,
# the kinds' RoPE swapped 1.39. `reference.py`'s 5% stands too near the system.
LOGITS_RMS_MAX = 0.08
# The 8 greedy tokens keep `reference.compare_tokens`' 0.15 standard
# deviations: the system 0-0.048; the window 0.85, the accumulator 1.13, the
# others 1.4-6.0.
# Sets of experts the reference cannot follow as a tie
# (`reference_laguna.ROUTE_TIE_MARGIN`, which has its readings): the system 0
# of 4,828 (layer, token) pairs; a reference without a part hundreds to all.
ROUTES_REFUSED_MAX = 4
TOY = dict(num_hidden_layers=5, sliding_window=8, num_experts=8,
           num_experts_per_tok=4, moe_intermediate_size=64,
           shared_expert_intermediate_size=64, torch_dtype="float32")


def toy_config(conf: dict) -> dict:
    """``--toy``: the configuration file at debug widths. ``harness.TOY_MODEL``
    has narrowed the dense keys; the pattern's own keys follow here, every
    mechanism kept (two head counts, a ring of 8, 16 experts of which 8 are
    held, top-4, YaRN over 16 original positions). In float32: at a hidden
    size of 128 a bfloat16 stream flips the 4th and 5th of 16 experts often
    enough to move single logits by a third of their spread, which says
    nothing of the program (in float32 it is the reference's to 2e-6)."""
    out = dict(conf, **TOY, published=dict(conf["published"], num_experts=16))
    heads, kv = conf["num_attention_heads"], conf["num_key_value_heads"]
    out["num_attention_heads_per_layer"] = [
        heads if kind == "full_attention" else heads + kv
        for kind in conf["layer_types"]]
    rope = conf["rope_parameters"]
    out["rope_parameters"] = dict(rope, full_attention=dict(
        rope["full_attention"], factor=4, original_max_position_embeddings=16,
        attention_factor=1.1386294361119891))
    return out


def laguna_model_config(conf: dict):
    """The program's TransformerConfig for the published ``config.json`` of a
    ``laguna`` model, cut to ``num_hidden_layers`` and to the share of the
    experts and of the vocabulary that the file states. Every width comes from
    the file; bf16 parameters."""
    import jax.numpy as jnp

    from ray_tpu.models import transformer as T

    n = conf["num_hidden_layers"]
    kinds = [KINDS[k] for k in conf["layer_types"][:n]]
    heads = dict(zip(kinds, conf["num_attention_heads_per_layer"][:n]))
    if conf["model_type"] != "laguna" or conf["attention_bias"] \
            or conf["tie_word_embeddings"] or conf["mlp_only_layers"] != [0] \
            or conf["mlp_layer_types"][:n] != ["dense"] + ["sparse"] * (n - 1) \
            or kinds[0] != "full" or conf["gating"] != "per-head" \
            or conf["moe_router_logit_softcapping"] \
            or conf["moe_apply_router_weight_on_input"] \
            or conf["decoder_sparse_step"] != 1 or any(
                h != heads[k] for k, h in
                zip(kinds, conf["num_attention_heads_per_layer"][:n])):
        raise ValueError("models/laguna.py runs one leading full layer with a "
                         "dense MLP, then sparse layers of two kinds with one "
                         "head count each, a gate a head and no bias")
    period = next(  # the shortest period the layers behind the first repeat
        tuple(kinds[1:1 + size]) for size in range(1, n)
        if (n - 1) % size == 0
        and kinds[1:] == kinds[1:1 + size] * ((n - 1) // size))
    full, window = (conf["rope_parameters"][k] for k in
                    ("full_attention", "sliding_attention"))
    if full["rope_type"] != "yarn" or window["rope_type"] != "default" \
            or float(window["partial_rotary_factor"]) != 1:
        raise ValueError("full layers rotate by YaRN, window layers plainly "
                         "over the whole head")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        conf["torch_dtype"]]
    return T.TransformerConfig(
        vocab_size=conf["vocab_size"], hidden=conf["hidden_size"],
        mlp_hidden=conf["moe_intermediate_size"], layers=n,
        heads=heads["full"], kv_heads=conf["num_key_value_heads"],
        head_dim=conf["head_dim"], max_seq=conf["max_position_embeddings"],
        rope_theta=float(full["rope_theta"]),
        partial_rotary=float(full["partial_rotary_factor"]),
        rope_yarn=tuple(float(full[k]) for k in (
            "factor", "original_max_position_embeddings", "beta_fast",
            "beta_slow", "attention_factor")),
        norm_eps=float(conf["rms_norm_eps"]), remat=False,
        num_experts=conf["published"]["num_experts"],
        experts_per_token=conf["num_experts_per_tok"],
        norm_topk_prob=bool(conf["norm_topk_prob"]),
        routed_scale=float(conf["moe_routed_scaling_factor"]),
        experts_held=(int(conf["experts_held_first"]), conf["num_experts"]),
        shared_expert_hidden=conf["shared_expert_intermediate_size"],
        layer_kinds=period, window=conf["sliding_window"],
        window_heads=heads["window"],
        window_rope_theta=float(window["rope_theta"]),
        dense_mlp_hidden=conf["intermediate_size"], head_gate=True,
        dtype=dtype, param_dtype=dtype)


def reference_check(engine, config: dict, seed: int, prompt_len: int,
                    new_tokens: int, neighbours: int = 3,
                    second_readings=()) -> dict:
    """``replica.reference_check`` for this model: a seeded prompt through the
    batcher's own prefill program (logits at its TRUE last position, the
    prompt being shorter than its bucket and longer than the window) and,
    behind ``neighbours`` busy slots, through the scheduler's batched decode
    step (greedy tokens: the ring installed with the rows, then written round
    by the steps), against ONE full forward of ``reference_laguna`` over the
    prompt and the chosen tokens. The reference follows the sets of experts
    the programs took (their ``expert_choice``, which the batcher logs while
    ``route_log`` is a list) where its own probabilities call them a tie, and
    refuses them elsewhere. ``second_readings`` are (name, keyword
    arguments of ``reference_laguna.logits``) pairs: what the same comparison
    reads against a reference of a lower precision or without a part, which
    is how the limits were set (the builder's calibration alone asks)."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import reference_laguna
    from ray_tpu.models.decoding import SamplingParams

    batcher, cfg = engine.batcher, engine.model_config
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
    bucket = min(batcher._bucket(prompt_len), batcher.max_len)
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :prompt_len] = prompt
    # the bucket was warmed: the program the requests run, not a new one
    last, _, _, _, _, load, choice, _ = batcher._prefill_jits[bucket](
        batcher.params, jnp.asarray(toks), jnp.asarray([prompt_len], np.int32))
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
    ref, followed = reference_laguna.logits(
        batcher.params, seq[None], config, last=new_tokens,
        follow=route[:, :len(seq)])
    ref = np.asarray(ref[0])
    out = reference_laguna.compare_logits(
        np.asarray(last, np.float32)[None], ref[:1])
    out.update(tol=LOGITS_RMS_MAX,
               ok=bool(out["rms_err_over_std"] <= LOGITS_RMS_MAX))
    out["tokens"] = reference_laguna.compare_tokens(chosen, ref)
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
        other, told = reference_laguna.logits(
            batcher.params, seq[None], config, last=new_tokens,
            follow=route[:, :len(seq)], **kwargs)
        other = np.asarray(other[0])
        out.setdefault("second_readings", {})[name] = dict(
            refused=told["refused"], followed=told["followed"],
            max_followed_gap=told["max_followed_gap"],
            rms_err_over_std=reference_laguna.compare_logits(
                np.asarray(last, np.float32)[None], other[:1]
            )["rms_err_over_std"],
            max_shortfall_over_std=reference_laguna.compare_tokens(
                chosen, other)["max_shortfall_over_std"])
    out.update(prompt_len=prompt_len, bucket=bucket,
               neighbour_lens=[int(n) for n in lengths],
               op_scopes={readers.DECODE_PROGRAM: decode_op_scopes(batcher)})
    return out


def decode_op_scopes(batcher) -> dict:
    """``scope_ops.op_scopes`` of the decode program as the pump runs it, by
    this configuration's ``SCOPES`` (``serve_zaya.decode_op_scopes`` says why
    it is read off the compiled text, here, before the window)."""
    import jax
    import numpy as np

    from benchmarks import scope_ops

    def like(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=a.sharding), tree)

    def per_slot(dtype):
        return jax.ShapeDtypeStruct((batcher.slots,), dtype)

    compiled = batcher._decode_jit.lower(
        like(batcher.params), per_slot(np.int32), like(batcher.cache),
        like(batcher._rng), per_slot(np.float32), per_slot(np.int32),
        per_slot(np.bool_)).compile()
    return scope_ops.op_scopes(compiled.as_text(), SCOPES)


def batcher_request(prompt, new_tokens: int):
    """A request as ``ContinuousBatcher.submit`` builds it, kept so that its
    entries of the route log can be told from its neighbours'."""
    from concurrent.futures import Future

    from ray_tpu.models.continuous_batching import _Request
    from ray_tpu.models.decoding import SamplingParams

    return _Request(list(map(int, prompt)),
                    SamplingParams(max_tokens=new_tokens), Future(), None)


def build_application(llm_config, config: dict):
    """``replica.build_application`` with the reference check replaced."""
    from ray_tpu.serve.deployment import Deployment

    app = replica.build_application(llm_config, config)

    class LagunaBenchLLMServer(app.deployment._target):
        def bench_reference_check(self, seed: int, prompt_len: int,
                                  new_tokens: int) -> dict:
            return reference_check(self.engine, config, seed, prompt_len,
                                   new_tokens)

    return Deployment(LagunaBenchLLMServer, app.deployment._config).bind()


class LagunaDeployed(serve.Deployed):
    def __init__(self, cell: dict, args: dict):
        """``serve.Deployed.__init__`` but for the model: it asks
        ``harness.model_config``, which refuses a sliding window."""
        self.cell, self.args = cell, args
        self.traffic, self.toy = cell["traffic"], cell["toy"]
        self.conf = toy_config(cell["config"]) if self.toy else cell["config"]
        self.sv = dict(self.conf["serve"], **(
            self.traffic.get("toy_serve", {}) if self.toy else {}))
        self.cfg = laguna_model_config(self.conf)
        self.n_new = int(self.traffic["new_tokens"])
        self.tok = replica.IdTokenizer()
        self.problems = []


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


serve.Deployed = LagunaDeployed
serve.account = account
serve.replica = types.SimpleNamespace(
    IdTokenizer=replica.IdTokenizer, build_application=build_application)
run = serve.run
