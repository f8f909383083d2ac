"""Runner of the Solar-Open2 serve cell: ``runners/serve.py``'s deployment,
traffic and accounting, with what this configuration needs replaced (the way
``serve_kimi_linear.py`` replaces it, whose family of layers this is). From
``serve_kimi_linear.py``, loaded as a private copy whose scope names are
pointed here, come the pieces that fit as they stand: the warmed prefill's
arguments, a request that can be told from its neighbours in the route log,
the compiled programs' operations by scope and the capture of one warmed
prefill by the delta-rule layers' scopes; from ``serve_jamba.py`` where the
window's end fell among the waves (``phases``).

- The model: this configuration's own keys (``gqa_layers`` / ``gqa_interval``,
  ``use_rope``, ``use_gqa_gate``, ``kda_use_full_proj``,
  ``kda_allow_neg_eigval``, ``linear_attn_config``, ``first_k_dense_replace``
  0, ``n_shared_experts``, the held share of ``n_routed_experts``) go into a
  ``TransformerConfig`` built here; what ``models/kimi_linear.py`` does not
  run is refused by name. ``--toy`` narrows them too (``toy_config``).
- The reference check: through ``reference_solar_open2.py``, through the same
  timed programs (the batcher's own warmed prefill of the 8,192 bucket, then
  the batched decode beside busy slots through the scheduler), and which path
  each attention compiled (``KERNEL_PATHS``: a fallback is a problem of the
  run, not a slower result).
- The window also carries the engine's expert counters, the held share among
  them, and where its end fell among the waves; the replica maps the decode
  program's operations to this configuration's scopes (``SCOPES``).
- A traced run also times ONE warmed prefill of the cell's bucket under a
  profiler capture of its own, before the window: ``kda_prefill`` (the
  window's own trace holds the prefills it happens to hold).
"""

from __future__ import annotations

import types

from benchmarks import harness, readers, replica

serve = harness.load_module("runners", "serve")  # a copy of our own to rebind
kimi = harness.load_module("runners", "serve_kimi_linear")
phases = harness.load_module("runners", "serve_jamba").phases
_account = serve.account
COUNTED = kimi.COUNTED
# outermost first, as `scope_ops.SCOPES`
SCOPES = ("kda.project", "kda.conv", "kda.gate", "kda.state",
          "kda.prefill_scan", "kda.out", "gqa.project", "gqa.attend",
          "gqa.gate", "gqa.out", "moe.shared", "moe_router", "moe_experts",
          "lm_head", "sample")
kimi.SCOPES = SCOPES  # what its op-scope maps and its capture sort by
# What each program must have compiled on the chip (`engine_stats()` carries
# the same): the flash forward over a prefill's fresh rows, the decode kernel
# over the 8 bf16 KV heads' rows.
KERNEL_PATHS = {"prefill_attention": {"prefill_8192": "flash"},
                "decode_attention": {"decode": "kernel"}}
# Limits of the comparison that decides `correct`, each between its two
# readings at the published widths on the chip (PERF.md section 6, PR 67, my
# chip runs: 4,800 tokens through the 8,192 bucket, then 8 greedy steps
# beside busy slots; the reference following the system's sets of experts
# where they are ties): what the system gives over eight weight seeds, and
# what `reference_solar_open2` gives with one of ISSUE 67's seven faults (two
# weight seeds each).
# Prefill logits at the prompt's last position, RMS over the reference's
# standard deviation: the system 0.0309-0.0359 (the precision the
# configuration states reads 0.0351 / 0.0363 by itself,
# `precision="stated"`: the system's distance IS that precision's, 8 layers
# of a bfloat16 stream); the bfloat16 accumulator 0.352 / 0.364, beta =
# sigmoid alone 0.528 / 0.522, the gate dropped 1.17 / 1.18, the convolution
# 1.28 / 1.26, KV groups interleaved 1.34 / 1.39, the shared expert 1.37 /
# 1.40. One reading stays inside the system's own band: a bfloat16 STATE
# 0.0362 / 0.0363 beside the system's 0.0320 / 0.0334 on the same seeds (this
# limit does not see it, as PRs 54 and 60 found of the state-space mixers';
# `STATE_RMS_MAX` does).
LOGITS_RMS_MAX = 0.08
# The 8 greedy tokens: how far below the reference's first choice the
# system's token lies at most, in the logits' standard deviations
# (`reference.compare_tokens`, tie-aware): the system 0-0.105 in 64 tokens of
# eight checks (0.105 once, 0.029 and under in the seven others: a stream
# 0.03 off flips a near tie of 24,576 logits; the other runners' 0.15 would
# stand 1.4 times over it); the accumulator 0.66 / 1.23, beta 1.47 / 2.27,
# the other four 3.97-6.93 (the bfloat16 state 0.032 / 0.0).
TOKENS_SHORTFALL_MAX = 0.3
# Sets of experts the reference cannot follow as a tie
# (`reference_solar_open2.ROUTE_TIE_MARGIN` 0.025: the system's largest
# followed gap is 0.0171-0.0230), of 8 x 4,807 = 38,456 (layer, token) pairs:
# the system 0 in eight checks (7,439-7,700 pairs followed as ties; the
# stated precision 1 and 0); the accumulator 17,904 / 17,895, beta 25,151 /
# 24,800, the convolution and the shared expert 33,650, the gate 38,010,
# the groups all 38,456 (the bfloat16 state 0).
ROUTES_REFUSED_MAX = 12
# The delta rule ALONE, because the three limits above do not see its
# state's precision (what the stated precision does to 8 layers of stream,
# 0.035 by itself, covers a state's rounding of 2^-9): `state_check`'s two
# numbers, each the RMS error over the reference's standard deviation, over
# 8,192 positions of the prefill's chunked scan and 8 steps of the decode
# step's kernel at 64 heads, beta in (0, 2) and over 1 at half the positions:
# the programs 3.5e-5 to 4.8e-5 on the read-outs and 3.1e-5 to 4.4e-5 on the
# last state (three seeds, the kernel on the chip); the recurrence with a
# bfloat16 state 6.8e-3 to 8.0e-3 and 7.1e-3 to 8.2e-3. The limit is the
# geometric middle: twelve times over the one, eleven under the other.
STATE_RMS_MAX = {"o": 6e-4, "state": 6e-4}
TOY = dict(
    num_hidden_layers=8, num_attention_heads=8, num_key_value_heads=2,
    head_dim=16, gqa_layers=[0, 4],
    linear_attn_config=dict(head_dim=16, num_heads=8,
                            short_conv_kernel_size=4, num_kv_heads=None),
    n_routed_experts=4, num_experts_per_tok=4, moe_intermediate_size=64,
    torch_dtype="float32")


def toy_config(conf: dict) -> dict:
    """``--toy``: the configuration file at debug widths. ``harness.TOY_MODEL``
    has narrowed the dense keys; the pattern's own keys follow here, every
    mechanism kept (two periods of one gated gqa layer of 8 heads on 2 and
    three kda layers with steps up to 2, 16 experts of which 4 are held,
    top-4, one shared). In float32, as Laguna's toy and for its reason."""
    return dict(conf, **TOY,
                published=dict(conf["published"], n_routed_experts=16))


def solar_model_config(conf: dict):
    """The program's TransformerConfig for the published ``config.json`` of a
    ``solar_open2`` model, cut to ``num_hidden_layers`` and to the share of
    the experts and of the vocabulary that the file states. Every width comes
    from the file; bf16 parameters."""
    import jax.numpy as jnp

    from benchmarks.reference_solar_open2 import kinds_of
    from ray_tpu.models import transformer as T

    lin = conf["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    if conf["model_type"] != "solar_open2" or conf["tie_word_embeddings"] \
            or conf["use_rope"] or conf["kda_use_full_proj"] \
            or conf["first_k_dense_replace"] or conf["n_shared_experts"] != 1 \
            or lin["num_kv_heads"] not in (None, heads) \
            or conf["num_attention_heads"] != heads or conf["head_dim"] != d:
        raise ValueError(
            "models/kimi_linear.py's form without a lead runs whole periods "
            "of one unrotated gqa layer and delta-rule layers of as many "
            "heads of the same size, low-rank gates, every layer sparse with "
            "one shared expert; untied")
    kinds = kinds_of(conf)
    period = conf["gqa_interval"] + 1
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        conf["torch_dtype"]]
    return T.TransformerConfig(
        vocab_size=conf["vocab_size"], hidden=conf["hidden_size"],
        mlp_hidden=conf["moe_intermediate_size"],
        layers=conf["num_hidden_layers"], heads=heads,
        kv_heads=conf["num_key_value_heads"], head_dim=d,
        max_seq=conf["max_position_embeddings"],
        norm_eps=float(conf["rms_norm_eps"]), remat=False,
        num_experts=conf["published"]["n_routed_experts"],
        experts_per_token=conf["num_experts_per_tok"],
        norm_topk_prob=bool(conf["norm_topk_prob"]),
        routed_scale=float(conf["routed_scaling_factor"]),
        experts_held=(int(conf["experts_held_first"]),
                      conf["n_routed_experts"]),
        shared_expert_hidden=conf["moe_intermediate_size"], lead_kind="",
        layer_kinds=tuple(kinds[:period]),
        kda_conv=lin["short_conv_kernel_size"],
        gqa_gate=bool(conf["use_gqa_gate"]),
        kda_neg_eigval=bool(conf["kda_allow_neg_eigval"]),
        router_score="sigmoid", dtype=dtype, param_dtype=dtype)


def paths_traced(batcher) -> dict:
    """Which path each program's attention compiled, as `engine_stats()`
    carries them."""
    return {what: dict(getattr(batcher, f"{what}_path"))
            for what in KERNEL_PATHS}


def state_check(kda: dict, seed: int, positions: int, steps: int,
                second_readings=()) -> dict:
    """The programs' delta rule ALONE against the recurrence a position at a
    time (``reference_solar_open2.recur``, float32): one sequence of
    ``positions`` seeded positions through the prefill's chunked scan
    (``kimi_linear.kda_chunks``), then ``steps`` more through the decode
    step's update on the state it left (``ops.delta_rule.state_update`` where
    it takes the stack: on the chip, the kernel at 64 heads a grid step),
    with layer 0's own decay (``a_log``, ``dt_bias``) and steps ``beta`` in
    (0, 2): along a key the state's eigenvalue ``1 - beta`` is negative half
    the time. Read-outs of every position and the last state, RMS error over
    the reference's standard deviation. ``second_readings`` are (name,
    keyword arguments of ``recur``) pairs, each against the float32
    recurrence: how the limits were set."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import reference_solar_open2 as reference
    from ray_tpu.models import kimi_linear
    from ray_tpu.ops import delta_rule

    a_log, dt_bias = (jnp.asarray(kda[n][0], jnp.float32)
                      for n in ("a_log", "dt_bias"))
    nh, d = dt_bias.shape
    s = positions + steps
    keys = jax.random.split(jax.random.key(seed % (1 << 31)), 5)
    z = [jax.random.normal(key, (s, nh, d), jnp.float32) for key in keys[:4]]
    q, k = reference._l2norm(z[0]) / d ** 0.5, reference._l2norm(z[1])
    v = jax.nn.silu(z[2])
    log_a = -jnp.exp(a_log)[:, None] * jax.nn.softplus(z[3] + dt_bias)
    del z  # 0.27 GB each at the cell's size, beside the engine
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(keys[4], (s, nh)))
    xs = (q, k, v, log_a, beta)
    ref_state, ref_o = jax.jit(reference.recur)(*xs)

    state, o = jax.jit(kimi_linear.kda_chunks)(
        jnp.zeros((1, nh, d, d), jnp.float32),
        *(a[None, :positions] for a in xs))
    mat, outs = state[None], [o[0]]  # a stack of one layer
    takes = delta_rule.state_update_takes(mat)

    def plain(mat, layer, *step_xs):
        new, o_t = kimi_linear.kda_step(mat[layer], *step_xs)
        return new[None], o_t

    step = jax.jit(delta_rule.state_update if takes else plain,
                   static_argnums=1)
    for t in range(positions, s):
        mat, o_t = step(mat, 0, *(a[None, t] for a in xs))
        outs.append(o_t)

    def off(got, want):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        return float(np.sqrt(np.mean((got - want) ** 2)) / np.std(want))

    out = {"positions": positions, "steps": steps,
           "step_path": "kernel" if takes else "plain",
           "beta_over_one_share": float(np.mean(np.asarray(beta) > 1.0)),
           "o_rms_err_over_std": off(jnp.concatenate(outs), ref_o),
           "state_rms_err_over_std": off(mat[0, 0], ref_state),
           "tol": dict(STATE_RMS_MAX)}
    out["ok"] = bool(
        out["o_rms_err_over_std"] <= STATE_RMS_MAX["o"]
        and out["state_rms_err_over_std"] <= STATE_RMS_MAX["state"])
    for name, kwargs in second_readings:
        other_state, other_o = jax.jit(
            lambda *a, kw=kwargs: reference.recur(*a, **kw))(*xs)
        out.setdefault("second_readings", {})[name] = {
            "o_rms_err_over_std": off(other_o, ref_o),
            "state_rms_err_over_std": off(other_state, ref_state)}
    return out


def reference_check(engine, config: dict, seed: int, prompt_len: int,
                    new_tokens: int, neighbours: int = 3,
                    second_readings=(), state_readings=()) -> dict:
    """``serve_kimi_linear.reference_check`` for this model: a seeded prompt
    through the batcher's own prefill program (logits at its TRUE last
    position, the prompt being shorter than its bucket and 150 chunks of the
    scan long) and, behind ``neighbours`` busy slots, through the scheduler's
    batched decode step (greedy tokens: the matrix states, windows and K/V
    rows installed, then rewritten and appended to by the steps), against ONE
    full forward of ``reference_solar_open2`` over the prompt and the chosen
    tokens, the recurrence a position at a time and attention a query head at
    a time. The reference follows the sets of experts the programs took where
    its own scores call them a tie, and refuses them elsewhere.
    ``second_readings`` are (name, keyword arguments of
    ``reference_solar_open2.logits``) pairs: how the limits were set (the
    builder's calibration alone asks), and ``state_readings``
    ``state_check``'s. The check also holds the delta rule alone to the
    recurrence over the prompt's whole bucket and the greedy steps
    (``state_check``)."""
    import jax
    import numpy as np

    from benchmarks import reference_solar_open2 as reference
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
    route = np.concatenate(routes, axis=1)  # [layers, tokens, k]

    def read(**kwargs):
        ref, told = reference.logits(
            batcher.params, seq[None], config, last=new_tokens,
            follow=route[:, :len(seq)], **kwargs)
        ref = np.asarray(ref[0])
        return ref, told, reference.compare_logits(
            np.asarray(last, np.float32)[None], ref[:1])

    ref, followed, out = read()
    out.update(tol=LOGITS_RMS_MAX,
               ok=bool(out["rms_err_over_std"] <= LOGITS_RMS_MAX))
    out["tokens"] = reference.compare_tokens(chosen, ref)
    out["routes"] = dict(
        {k: v for k, v in followed.items() if k != "chosen"},
        logged=int(route.shape[1]), wanted=len(seq),
        admit_is_the_program=bool(np.array_equal(
            routes[0], np.asarray(choice)[:, :prompt_len])))
    out["paths_traced"] = paths = paths_traced(batcher)
    # on the chip a program that kept a plain spelling fails the check (off
    # the chip every program does, and says so)
    on_chip = jax.devices()[0].platform == "tpu"
    out["fell_back"] = {
        what: paths[what] for what, want in KERNEL_PATHS.items()
        if on_chip and any(paths[what].get(program) != path
                           for program, path in want.items())}
    out["tokens"].update(tol=TOKENS_SHORTFALL_MAX, ok=bool(
        out["tokens"].get("max_shortfall_over_std", float("inf"))
        <= TOKENS_SHORTFALL_MAX))
    out["state"] = state_check(batcher.params["blocks"]["kda"], seed, bucket,
                               new_tokens, state_readings)
    out["ok"] = bool(out["ok"] and out["tokens"]["ok"] and out["state"]["ok"]
                     and route.shape[1] == len(seq)
                     and out["routes"]["admit_is_the_program"]
                     and followed["refused"] <= ROUTES_REFUSED_MAX
                     and not out["fell_back"])
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
        other, told, off = read(**kwargs)
        out.setdefault("second_readings", {})[name] = dict(
            refused=told["refused"], followed=told["followed"],
            max_followed_gap=told["max_followed_gap"],
            rms_err_over_std=off["rms_err_over_std"],
            max_shortfall_over_std=reference.compare_tokens(
                chosen, other)["max_shortfall_over_std"])
    out.update(prompt_len=prompt_len, bucket=bucket,
               neighbour_lens=[int(n) for n in lengths],
               op_scopes={readers.DECODE_PROGRAM:
                          kimi.decode_op_scopes(batcher)})
    return out


def build_application(llm_config, config: dict):
    """``replica.build_application`` with the reference check replaced and
    the prefill's capture added."""
    from ray_tpu.serve.deployment import Deployment

    app = replica.build_application(llm_config, config)

    class SolarOpen2BenchLLMServer(app.deployment._target):
        def bench_reference_check(self, seed: int, prompt_len: int,
                                  new_tokens: int) -> dict:
            self._prefill_scopes = kimi.prefill_op_scopes(
                self.engine.batcher, prompt_len)
            return reference_check(self.engine, config, seed, prompt_len,
                                   new_tokens)

        def bench_kda_prefill(self, seed: int, prompt_len: int) -> dict:
            return kimi.kda_prefill(self.engine, self._prefill_scopes, seed,
                                    prompt_len)

    return Deployment(SolarOpen2BenchLLMServer,
                      app.deployment._config).bind()


class SolarOpen2Deployed(serve.Deployed):
    def __init__(self, cell: dict, args: dict):
        """``serve.Deployed.__init__`` but for the model: it asks
        ``harness.model_config``, which reads one kind of layer."""
        self.cell, self.args = cell, args
        self.traffic, self.toy = cell["traffic"], cell["toy"]
        self.conf = toy_config(cell["config"]) if self.toy else cell["config"]
        self.sv = dict(self.conf["serve"], **(
            self.traffic.get("toy_serve", {}) if self.toy else {}))
        self.cfg = solar_model_config(self.conf)
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
                seed + 2, traffic["warmup_prompt_tokens"][0]).result()
            harness.say("serve", kda_prefill=captured)
        win = super().measure(traffic, seed, seconds, trace)
        win["kda_prefill"] = captured
        return win


def account(dep, traffic, schedule, played, marks) -> dict:
    """``serve.account`` plus the window's expert counters (``layers`` are
    the layers that ROUTE, so that ``moe_assignments_per_token`` divides by
    them) and where the window's end fell among the waves (``phases``)."""
    win = _account(dep, traffic, schedule, played, marks)
    opened, closed = marks["engine_open"], marks["engine_close"]
    win["moe"] = dict(
        {k: closed[k] - opened[k] for k in COUNTED},
        expert_load=[b - a for a, b in zip(opened["moe_expert_load"],
                                           closed["moe_expert_load"])],
        layers=dep.cfg.sparse_layers)
    win["phases"] = phases(played, int(traffic["clients"]))
    harness.say("serve", phases=win["phases"])
    return win


serve.Deployed = SolarOpen2Deployed
serve.account = account
serve.replica = types.SimpleNamespace(
    IdTokenizer=replica.IdTokenizer, build_application=build_application)
run = serve.run
