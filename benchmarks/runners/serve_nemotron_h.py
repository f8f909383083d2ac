"""Runner of the Nemotron-H serve cell: ``runners/serve.py``'s deployment,
traffic and accounting, with what this configuration needs replaced (the way
``serve_longcat.py`` replaces it). From ``serve_kimi_linear.py``, loaded as a
private copy whose scope names are pointed here, come the pieces that fit as
they stand: the warmed prefill's arguments, a request that can be told from
its neighbours in the route log, the compiled programs' operations by scope
and the capture of one warmed prefill (a model with a state that a prefill
scans and a decode step rewrites, as that one).

- The model: this configuration's own keys (``hybrid_override_pattern``, the
  ``mamba_*`` / ``ssm_state_size`` / ``n_groups`` / ``conv_kernel`` keys,
  ``moe_latent_size``, ``moe_shared_expert_intermediate_size``,
  ``mlp_hidden_act``, the held share of ``n_routed_experts``) go into a
  ``TransformerConfig`` built here; what ``models/nemotron_h.py`` does not
  run is refused by name. ``--toy`` narrows them too (``toy_config``).
- The reference check: through ``reference_nemotron_h.py``, through the same
  timed programs (the batcher's own warmed prefill of the 512 bucket, then
  the batched decode beside busy slots through the scheduler).
- The window also carries the engine's expert counters and the bytes of state
  its steps rewrote, and the replica maps the decode program's operations to
  this configuration's scopes (``SCOPES``).
- A traced run also times ONE warmed prefill of the cell's bucket under a
  profiler capture of its own, before the window: ``ssm_prefill``.
"""

from __future__ import annotations

import types

from benchmarks import harness, readers, replica

serve = harness.load_module("runners", "serve")  # a copy of our own to rebind
kimi = harness.load_module("runners", "serve_kimi_linear")
_account = serve.account
COUNTED = ("moe_assignments", "moe_rows", "moe_assignments_held",
           "moe_experts_reached", "state_installs", "state_resets",
           "state_bytes_rewritten")
# outermost first, as `scope_ops.SCOPES`
SCOPES = ("ssm.project", "ssm.conv", "ssm.state", "ssm.prefill_scan",
          "ssm.norm", "ssm.out", "attn.gqa", "lmoe.down", "lmoe.up",
          "moe.shared", "moe_router", "moe_experts", "lm_head", "sample")
kimi.SCOPES = SCOPES  # what its op-scope maps and its capture sort by
kimi.KDA_SCOPES = tuple(s for s in SCOPES if s.startswith("ssm."))
KINDS = {"M": "ssm", "*": "gqa", "E": "lmoe"}
# Limits of the comparison that decides `correct`, each between its two
# readings at the published widths on the chip (my chip runs, PR 54; PERF.md
# section 6: 400 tokens through the 512 bucket, then 8 greedy steps beside
# busy slots; the reference following the system's sets of experts where they
# are ties): what the system gives over fourteen weight seeds, and what
# `reference_nemotron_h` gives with a bfloat16 accumulator (all matmuls but
# the routed experts' and the recurrence's own), with a bfloat16 STATE, or
# with one part dropped.
# Prefill logits at the prompt's last position, RMS over the reference's
# standard deviation: the system 0.0148-0.0167; the bfloat16 accumulator
# 0.259, the factor 5 dropped 0.54, ReLU for ReLU^2 0.89, the decay, the
# gate, D or the shared expert dropped 1.23-1.31. Two readings stay inside
# the system's own band: the selection bias dropped 0.0170 (it moves the sets
# alone, which the reference then follows: the routes and the tokens refuse
# it), and the bfloat16 state 0.0162-0.0164 (a state's rounding, 2^-9 a value
# and position and independent, averages out over the 128 states a channel
# reads: NO limit of this check sees it; `tests/test_nemotron_h.py` does, at
# toy widths in float32, 4.7e-4 against 7e-7, and `tests/test_chip_compile.py`
# holds the cache's `mat` to float32).
LOGITS_RMS_MAX = 0.05
# The 8 greedy tokens keep `reference.compare_tokens`' 0.15 standard
# deviations: the system 0 in fourteen checks; the bias dropped 0.38, the
# accumulator 0.45, the factor 0.92, the other parts 3.6-4.8.
# Sets of experts the reference cannot follow as a tie
# (`reference_nemotron_h.ROUTE_TIE_MARGIN`, which has its readings), of 4,070
# (layer, token) pairs: the system 0; the bias dropped 801, the accumulator
# 2,482, the parts 3,582 to all.
ROUTES_REFUSED_MAX = 8
TOY = dict(
    num_hidden_layers=11, hybrid_override_pattern="MEMEM*EMEM*",
    num_key_value_heads=2, head_dim=16, mamba_num_heads=8, mamba_head_dim=8,
    expand=0.5, n_groups=2, ssm_state_size=16, chunk_size=8,
    moe_latent_size=32, moe_intermediate_size=48, intermediate_size=48,
    moe_shared_expert_intermediate_size=96, n_routed_experts=8,
    num_experts_per_tok=4, torch_dtype="float32")


def toy_config(conf: dict) -> dict:
    """``--toy``: the configuration file at debug widths
    (``harness.TOY_MODEL`` names the dense keys; this family's own follow
    here), every mechanism kept: 5 mixers (8 heads of 8 in 2 groups, a state
    of 16, chunks of 8), 2 attentions of 4 heads on 2, 4 expert layers of
    top-4 of 16 ReLU^2 experts in a latent of 32, 8 held. In float32, as
    Laguna's toy and for its reason."""
    return dict(conf, **TOY,
                published=dict(conf["published"], n_routed_experts=16))


def nemotron_model_config(conf: dict):
    """The program's TransformerConfig for the published ``config.json`` of a
    ``nemotron_h`` model, cut to the prefix of its layers and to the share of
    the routed experts and of the vocabulary that the file states. Every
    width comes from the file; bf16 parameters."""
    import jax.numpy as jnp

    from ray_tpu.models import transformer as T

    pattern = conf["hybrid_override_pattern"]
    inner = conf["mamba_num_heads"] * conf["mamba_head_dim"]
    if conf["model_type"] != "nemotron_h" or conf["tie_word_embeddings"] \
            or conf["mamba_hidden_act"] != "silu" \
            or conf["mlp_hidden_act"] != "relu2" \
            or not conf["use_conv_bias"] or conf["mamba_proj_bias"] \
            or conf["attention_bias"] or conf["mlp_bias"] or conf["use_bias"] \
            or conf["n_group"] != 1 or conf["topk_group"] != 1 \
            or conf["n_shared_experts"] != 1 or conf["sliding_window"] \
            or inner != conf["expand"] * conf["hidden_size"] \
            or len(pattern) != conf["num_hidden_layers"] \
            or set(pattern) - set(KINDS):
        raise ValueError(
            "models/nemotron_h.py runs layers of ONE sublayer each, one "
            "letter of hybrid_override_pattern a layer (M, * or E): Mamba-2 "
            "mixers of expand x hidden_size channels with a convolution bias "
            "and no other, full attention without a bias, ReLU^2 experts in "
            "a latent with one shared expert and an ungrouped router; SiLU "
            "in the mixer, untied")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        conf["torch_dtype"]]
    return T.TransformerConfig(
        vocab_size=conf["vocab_size"], hidden=conf["hidden_size"],
        mlp_hidden=conf["moe_intermediate_size"],
        layers=conf["num_hidden_layers"], heads=conf["num_attention_heads"],
        kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        max_seq=conf["max_position_embeddings"],
        norm_eps=float(conf["layer_norm_epsilon"]), remat=False,
        num_experts=conf["published"]["n_routed_experts"],
        experts_per_token=conf["num_experts_per_tok"],
        norm_topk_prob=bool(conf["norm_topk_prob"]),
        routed_scale=float(conf["routed_scaling_factor"]),
        experts_held=(int(conf["experts_held_first"]),
                      conf["n_routed_experts"]),
        shared_expert_hidden=conf["moe_shared_expert_intermediate_size"],
        lead_kind="", layer_kinds=tuple(KINDS[c] for c in pattern),
        ssm_heads=conf["mamba_num_heads"],
        ssm_head_dim=conf["mamba_head_dim"], ssm_groups=conf["n_groups"],
        ssm_state=conf["ssm_state_size"], ssm_conv=conf["conv_kernel"],
        ssm_chunk=conf["chunk_size"], moe_latent=conf["moe_latent_size"],
        expert_act="relu2", router_score="sigmoid", dtype=dtype,
        param_dtype=dtype)


def reference_check(engine, config: dict, seed: int, prompt_len: int,
                    new_tokens: int, neighbours: int = 3,
                    second_readings=()) -> dict:
    """``serve_kimi_linear.reference_check`` for this model: a seeded prompt
    through the batcher's own prefill program (logits at its TRUE last
    position, the prompt being shorter than its bucket and no whole number of
    the scan's chunks) and, behind ``neighbours`` busy slots, through the
    scheduler's batched decode step (greedy tokens: the states, windows and
    K/V rows installed, then rewritten and appended to by the steps), against
    ONE full forward of ``reference_nemotron_h`` over the prompt and the
    chosen tokens, the recurrence a position at a time. The reference follows
    the sets of experts the programs took where its own scores call them a
    tie, and refuses them elsewhere. ``second_readings`` are (name, keyword
    arguments of ``reference_nemotron_h.logits``) pairs: how the limits were
    set (the builder's calibration alone asks)."""
    import numpy as np

    from benchmarks import reference_nemotron_h as reference
    from ray_tpu.models.decoding import SamplingParams

    batcher, cfg = engine.batcher, engine.model_config
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
    program, arguments, bucket = kimi._warmed_prefill(batcher, prompt)
    last, *_, load, choice, _ = program(*arguments)
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
    route = np.concatenate(routes, axis=1)  # [expert layers, tokens, k]
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
    out.update(
        prompt_len=prompt_len, bucket=bucket,
        neighbour_lens=[int(n) for n in lengths],
        paths_traced=dict(grouped_matmul=batcher.moe_grouped_path,
                          prefill_attention=batcher.prefill_attention_path),
        op_scopes={readers.DECODE_PROGRAM: kimi.decode_op_scopes(batcher)})
    return out


def build_application(llm_config, config: dict):
    """``replica.build_application`` with the reference check replaced and
    the prefill's capture added."""
    from ray_tpu.serve.deployment import Deployment

    app = replica.build_application(llm_config, config)

    class NemotronHBenchLLMServer(app.deployment._target):
        def bench_reference_check(self, seed: int, prompt_len: int,
                                  new_tokens: int) -> dict:
            self._prefill_scopes = kimi.prefill_op_scopes(
                self.engine.batcher, prompt_len)
            return reference_check(self.engine, config, seed, prompt_len,
                                   new_tokens)

        def bench_ssm_prefill(self, seed: int, prompt_len: int) -> dict:
            return kimi.kda_prefill(self.engine, self._prefill_scopes, seed,
                                    prompt_len)

    return Deployment(NemotronHBenchLLMServer, app.deployment._config).bind()


class NemotronHDeployed(serve.Deployed):
    def __init__(self, cell: dict, args: dict):
        """``serve.Deployed.__init__`` but for the model: it asks
        ``harness.model_config``, which reads another family's keys."""
        self.cell, self.args = cell, args
        self.traffic, self.toy = cell["traffic"], cell["toy"]
        self.conf = toy_config(cell["config"]) if self.toy else cell["config"]
        self.sv = dict(self.conf["serve"], **(
            self.traffic.get("toy_serve", {}) if self.toy else {}))
        self.cfg = nemotron_model_config(self.conf)
        self.n_new = int(self.traffic["new_tokens"])
        self.tok = replica.IdTokenizer()
        self.problems = []

    def measure(self, traffic: dict, seed: int, seconds: float,
                trace: bool = False) -> dict:
        """``serve.Deployed.measure``; a traced run first captures one warmed
        prefill (before the window opens: the capture is set-up)."""
        captured = {}
        if trace:
            captured = self.handle.bench_ssm_prefill.remote(
                seed + 2, traffic["reference_prompt_tokens"]).result()
            harness.say("serve", ssm_prefill=captured)
        win = super().measure(traffic, seed, seconds, trace)
        win["ssm_prefill"] = captured
        return win


def account(dep, traffic, schedule, played, marks) -> dict:
    """``serve.account`` plus the window's expert counters and the bytes of
    state its steps rewrote; ``layers`` are the layers that ROUTE, so that
    ``moe_assignments_per_token`` divides by them."""
    win = _account(dep, traffic, schedule, played, marks)
    opened, closed = marks["engine_open"], marks["engine_close"]
    win["moe"] = dict(
        {k: closed[k] - opened[k] for k in COUNTED},
        expert_load=[b - a for a, b in zip(opened["moe_expert_load"],
                                           closed["moe_expert_load"])],
        layers=dep.cfg.sparse_layers)
    return win


serve.Deployed = NemotronHDeployed
serve.account = account
serve.replica = types.SimpleNamespace(
    IdTokenizer=replica.IdTokenizer, build_application=build_application)
run = serve.run
