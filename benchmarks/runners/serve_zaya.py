"""Runner of the ZAYA1 serve cell: ``runners/serve.py``'s deployment, traffic
and accounting, with the three things this configuration needs replaced (the
way ``serve_olmoe.py`` replaces two).

- The model: ``harness.model_config`` reads the dense keys of a published
  ``config.json`` and refuses tied embeddings; this configuration's keys
  (``moe_intermediate_size``, ``router_hidden_size``, ``partial_rotary_factor``,
  ``rope_parameters``, ``cca_time0/1``, ``tie_word_embeddings``) go into a
  ``TransformerConfig`` built here, ``attention="cca"``, ``router="zaya_mlp"``.
- The reference check: through ``reference_zaya.py``, through the same timed
  programs (the batcher's own warmed prefill, then the batched decode beside
  busy slots through the scheduler). One expert a token, so the reference is
  told the route the programs took (their ``expert_choice``, which the batcher
  logs while ``route_log`` is a list) and follows it where it is a tie.
- The window also carries the engine's expert and state counters.

Everything else (the front door, the replica, the load generator, the window)
is ``serve.py``'s own code, loaded as a private copy of that module whose
``Deployed``, ``account`` and ``replica`` names are pointed here.
"""

from __future__ import annotations

import types

from benchmarks import harness, readers, replica

serve = harness.load_module("runners", "serve")  # a copy of our own to rebind
_account = serve.account
COUNTED = ("moe_assignments", "moe_rows", "state_installs", "state_resets")
# Limits of the comparison that decides `correct`, each between its two
# readings at the published widths on the chip (PERF.md section 6, PR 30):
# what the system gives over seeds, and what a reference gives that computes
# with a bfloat16 accumulator (`reference_zaya.logits(precision="bfloat16")`,
# the nearest precision below the system's float32 sums) or leaves out a
# convolution, the value shift or the q-k mean.
# Prefill logits, RMS over the reference's standard deviation: the system
# 0.86-0.90% in eight runs (an average over 262,272 logits: it hardly moves);
# the bfloat16 accumulator 4.3%, a dropped part 27-55%. `reference.py`'s 5%
# would pass the accumulator.
LOGITS_RMS_MAX = 0.025
# The 8 greedy tokens keep `reference.compare_tokens`' 0.15 standard
# deviations: the system 0-0.003, the accumulator 0.195, a dropped part
# 0.07-2.0. Routes the reference cannot follow as a tie
# (`reference_zaya.ROUTE_TIE_MARGIN`, which has its readings): none; the
# accumulator leaves 5 or more, a dropped part thousands.
ROUTES_REFUSED_MAX = 0


def zaya_model_config(conf: dict, toy: bool = False):
    """The program's TransformerConfig for the published ``config.json`` of a
    ``zaya`` model. Every width comes from the file; bf16 parameters."""
    import jax.numpy as jnp

    from ray_tpu.models import transformer as T

    if conf["model_type"] != "zaya" or set(conf["layer_types"]) != {"hybrid"} \
            or conf["sliding_window"] or conf["hidden_act"] != "silu" \
            or conf["attention_bias"] or conf["lm_head_bias"] \
            or (conf["cca_time0"], conf["cca_time1"]) != (2, 2):
        raise ValueError("models/zaya.py runs 'hybrid' layers with two-tap "
                         "convolutions, SiLU experts and no bias or window")
    rope = conf["rope_parameters"]["hybrid"]
    return T.config(
        "zaya1_8b", vocab_size=conf["vocab_size"], hidden=conf["hidden_size"],
        # --toy narrows `intermediate_size`, the key the dense models read
        mlp_hidden=conf["intermediate_size" if toy else
                        "moe_intermediate_size"],
        layers=conf["num_hidden_layers"], heads=conf["num_attention_heads"],
        kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        max_seq=conf["max_position_embeddings"],
        rope_theta=float(rope["rope_theta"]),
        partial_rotary=float(rope["partial_rotary_factor"]),
        norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        num_experts=conf["num_experts"],
        experts_per_token=conf["num_experts_per_tok"],
        router_hidden=32 if toy else conf["router_hidden_size"],
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)


def reference_check(engine, config: dict, seed: int, prompt_len: int,
                    new_tokens: int, neighbours: int = 3) -> dict:
    """``replica.reference_check`` for this model: a seeded prompt through the
    batcher's own prefill program (logits at its TRUE last position, the
    prompt being shorter than its bucket) and, behind ``neighbours`` busy
    slots, through the scheduler's batched decode step (greedy tokens: the
    state installed with the row, advanced for active slots alone), against
    ONE full forward of ``reference_zaya`` over the prompt and the chosen
    tokens. The reference follows the route the programs took where its own
    probabilities call it a tie, and refuses it elsewhere."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import reference_zaya
    from ray_tpu.models.decoding import SamplingParams

    batcher, cfg = engine.batcher, engine.model_config
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
    bucket = min(batcher._bucket(prompt_len), batcher.max_len)
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :prompt_len] = prompt
    # the bucket was warmed: the program the requests run, not a new one
    last, _, _, _, load, choice = batcher._prefill_jits[bucket](
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
    # the route of the chosen request: its admit's positions (the first entry
    # that names it), then its slot's column of every step that advanced it
    entries = [(slot, c) for reqs, c in log for slot, r in reqs.items()
               if r is mine]
    routes = [entries[0][1][:, :prompt_len]] + [
        c[:, [slot]] for slot, c in entries[1:]]
    seq = np.concatenate([prompt, np.asarray(chosen[:-1], np.int32)])
    route = np.concatenate(routes, axis=1)
    ref, followed = reference_zaya.logits(
        batcher.params, seq[None], config, last=new_tokens,
        follow=route[:, :len(seq)])
    ref = np.asarray(ref[0])
    # the prompt's last position is the first of the `last`: the prefill
    # program's logits against it, as the scheduler's own admit routed it
    out = reference_zaya.compare_logits(
        np.asarray(last, np.float32)[None], ref[:1])
    out.update(tol=LOGITS_RMS_MAX,
               ok=bool(out["rms_err_over_std"] <= LOGITS_RMS_MAX))
    out["tokens"] = reference_zaya.compare_tokens(chosen, ref)
    out["routes"] = dict(
        {k: v for k, v in followed.items() if k != "chosen"},
        logged=int(route.shape[1]), wanted=len(seq),
        admit_is_the_program=bool(np.array_equal(
            routes[0], np.asarray(choice)[:, :prompt_len])))
    out["ok"] = bool(out["ok"] and out["tokens"]["ok"]
                     and route.shape[1] == len(seq)
                     and out["routes"]["admit_is_the_program"]
                     and followed["refused"] <= ROUTES_REFUSED_MAX)
    # dropless, pad rows not counted: the prefill program's own counter
    out["prefill_assignments"] = int(np.asarray(load).sum())
    want = prompt_len * cfg.experts_per_token * cfg.layers
    if out["prefill_assignments"] != want:
        out.update(ok=False, prefill_assignments_expected=want)
    out.update(prompt_len=prompt_len, bucket=bucket,
               neighbour_lens=[int(n) for n in lengths],
               op_scopes={readers.DECODE_PROGRAM: decode_op_scopes(batcher)})
    return out


def decode_op_scopes(batcher) -> dict:
    """``scope_ops.op_scopes`` of the decode program as the pump runs it: the
    engine's own jit lowered for the arrays it is called with and compiled
    (a read of the compile cache: the step was warmed), here, before the
    window, because only the compiled text ties an operation's name to the
    ``jax.named_scope`` it was traced under."""
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
    return scope_ops.op_scopes(compiled.as_text())


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

    class ZayaBenchLLMServer(app.deployment._target):
        def bench_reference_check(self, seed: int, prompt_len: int,
                                  new_tokens: int) -> dict:
            return reference_check(self.engine, config, seed, prompt_len,
                                   new_tokens)

    return Deployment(ZayaBenchLLMServer, app.deployment._config).bind()


class ZayaDeployed(serve.Deployed):
    def __init__(self, cell: dict, args: dict):
        """``serve.Deployed.__init__`` but for the model: it asks
        ``harness.model_config``, which refuses tied embeddings."""
        self.cell, self.args = cell, args
        self.conf, self.traffic, self.toy = \
            cell["config"], cell["traffic"], cell["toy"]
        self.sv = dict(self.conf["serve"], **(
            self.traffic.get("toy_serve", {}) if self.toy else {}))
        self.cfg = zaya_model_config(self.conf, self.toy)
        self.n_new = int(self.traffic["new_tokens"])
        self.tok = replica.IdTokenizer()
        self.problems = []


def account(dep, traffic, schedule, played, marks) -> dict:
    """``serve.account`` plus the window's expert and state counters."""
    win = _account(dep, traffic, schedule, played, marks)
    opened, closed = marks["engine_open"], marks["engine_close"]
    win["moe"] = dict(
        {k: closed[k] - opened[k] for k in COUNTED},
        expert_load=[b - a for a, b in zip(opened["moe_expert_load"],
                                           closed["moe_expert_load"])],
        layers=dep.cfg.layers)
    return win


serve.Deployed = ZayaDeployed
serve.account = account
serve.replica = types.SimpleNamespace(
    IdTokenizer=replica.IdTokenizer, build_application=build_application)
run = serve.run
