"""Runner of the Jamba serve cell: ``runners/serve.py``'s deployment, traffic
and accounting, with what this configuration needs replaced (the way
``serve_nemotron_h.py`` replaces it: that runner builds its model from the
``nemotron_h`` keys and follows sets of experts, and this model has neither).
From ``serve_mimo.py``, loaded as a private copy whose scope names are pointed
here, come the pieces that fit as they stand: the compiled programs'
operations by scope and the capture of one warmed prefill of the 8,192
bucket; from ``serve_kimi_linear.py`` the warmed prefill's arguments.

- The model: this configuration's own keys (``attn_layer_period`` /
  ``attn_layer_offset``, the ``mamba_*`` keys, ``num_experts`` 1,
  ``tie_word_embeddings``) go into a ``TransformerConfig`` built here; what
  ``models/nemotron_h.py`` does not run is refused by name. ``--toy`` narrows
  them too (``toy_config``).
- The reference check: through ``reference_jamba.py``, through the same timed
  programs (the batcher's own warmed prefill of the 8,192 bucket, then the
  batched decode beside busy slots through the scheduler), the prefill's
  scan alone against the recurrence (``scan_check``), and which path
  each of the three kernels compiled (``paths_traced``: a fallback is a
  problem of the run, not a slower result).
- The window also says where its end fell among the waves (``phases``), and
  the replica maps the decode program's operations to this configuration's
  scopes (``SCOPES``).
- A traced run also times ONE warmed prefill of the cell's bucket under a
  profiler capture of its own, before the window: ``mamba1_prefill`` (the
  window's trace lies inside a decode phase and holds no prefill).
"""

from __future__ import annotations

import functools
import types

from benchmarks import harness, readers, replica

serve = harness.load_module("runners", "serve")  # a copy of our own to rebind
mimo = harness.load_module("runners", "serve_mimo")
kimi = mimo.kimi
_account = serve.account
# outermost first, as `scope_ops.SCOPES`
SCOPES = ("ssm1.project", "ssm1.conv", "ssm1.state", "ssm1.prefill_scan",
          "ssm1.gate", "ssm1.out", "attn.gqa", "mlp", "lm_head", "sample")
mimo.SCOPES = SCOPES  # what its op-scope maps and its capture sort by
# What each program must have compiled on the chip (`engine_stats()` carries
# the same): the scan and the state update as kernels, the flash forward over
# a prefill's fresh rows, the decode kernel over the ONE KV head's rows.
KERNEL_PATHS = {"ssm": {"prefill_8192": "scan:kernel",
                        "decode": "state:kernel"},
                "prefill_attention": {"prefill_8192": "flash"},
                "decode_attention": {"decode": "kernel"}}
# Limits of the comparison that decides `correct`, each between its two
# readings at the published widths on the chip (PERF.md section 6, PR 60, my
# chip runs: 4,800 tokens through the 8,192 bucket, then 8 greedy steps beside
# busy slots): what the system gives over weight seeds, and what
# `reference_jamba` gives in a lower precision or with one part dropped.
# Prefill logits at the prompt's last position, RMS over the reference's
# standard deviation: the system 0.037-0.052 over twenty weight seeds (the
# first two runs were held to the other serve runners' 0.02 and failed it:
# the precision the configuration states reads 0.048-0.053 by itself where
# the system reads 0.040-0.052, `reference_jamba`'s `precision="stated"`: 56
# sublayers of a bfloat16 stream, and a step's error goes through an
# exponential into 4,800 positions of state); the bfloat16 accumulator 0.445;
# the three norms dropped 0.99, the convolution's bias 1.30, the dt bias
# 1.36, D 1.40. Two readings stay inside
# the system's own band: a bfloat16 STATE 0.0495 and a bfloat16 sum in the
# read-out 0.0450 beside the system's 0.0446 on the same seed (a rounding of
# 2^-9 a value and position, independent, averages out over the 16 states a
# channel reads and the 5,120 channels a projection sums: this limit sees
# neither, as PR 54 found of Mamba-2's; `SCAN_RMS_MAX` does).
LOGITS_RMS_MAX = 0.12
# The 8 greedy tokens: how far below the reference's first choice the
# system's token lies at most, in the logits' standard deviations
# (`reference.compare_tokens`, tie-aware): the system 0-0.091 in 160 tokens of
# twenty checks (a stream 0.04 off flips the near ties of 65,536 logits); the
# accumulator 0.62, the parts 4.6-5.8. The other runners' 0.15 would stand
# 1.6 times over this model's largest reading; the limit lies between its own
# two.
TOKENS_SHORTFALL_MAX = 0.25
# The scan ALONE, because the limits above do not see its state's precision:
# `scan_check`'s two numbers, each the RMS error of the program's scan over
# the recurrence's spread on seeded float32 inputs of a whole bucket, [8,192
# x 5,120] from the model's own rates (my chip runs, PR 60, eight weight
# seeds): the kernel 0.0 on both, the same bits (the plain spelling off the
# chip 1.0e-7 / 4.6e-8: float32 sums in another order); a bfloat16 state
# 5.4e-3 on both, a bfloat16 sum in the read-out 1.66e-3 on the outputs.
SCAN_RMS_MAX = 3e-5
TOY = dict(
    num_hidden_layers=8, attn_layer_period=4, attn_layer_offset=2,
    num_key_value_heads=1, intermediate_size=192, mamba_d_state=16,
    mamba_dt_rank=10, torch_dtype="float32")


def toy_config(conf: dict) -> dict:
    """``--toy``: the configuration file at debug widths
    (``harness.TOY_MODEL`` names the dense keys; this family's own follow
    here), every mechanism kept: two periods of four layers with the
    attention third, 256 channels with a state of 16 through a rank of 10, 4
    heads on ONE KV head, the tied head. In float32, as Laguna's toy and for
    its reason."""
    return dict(conf, **TOY)


def jamba_model_config(conf: dict):
    """The program's TransformerConfig for the published ``config.json`` of a
    ``jamba`` model: a published layer is two entries of ``layer_kinds``, its
    mixer or attention and then its MLP. Every width comes from the file;
    bf16 parameters."""
    import jax.numpy as jnp

    from benchmarks import reference_jamba
    from ray_tpu.models import transformer as T

    if conf["model_type"] != "jamba" or conf["num_experts"] != 1 \
            or conf["num_experts_per_tok"] != 1 \
            or conf["hidden_act"] != "silu" or not conf["mamba_conv_bias"] \
            or conf["mamba_proj_bias"] or conf["sliding_window"] \
            or not conf["tie_word_embeddings"] \
            or conf["hidden_size"] % conf["num_attention_heads"]:
        raise ValueError(
            "models/nemotron_h.py's \"ssm1\" and \"mlp\" kinds run Mamba-1 "
            "mixers with a convolution bias and no projection bias, full "
            "attention without a bias, a window or a rotation, and ONE dense "
            "SwiGLU MLP a layer (num_experts 1); SiLU, the head tied")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        conf["torch_dtype"]]
    kinds = tuple(k for mixer in reference_jamba.layer_types(conf)
                  for k in (mixer, "mlp"))
    return T.TransformerConfig(
        vocab_size=conf["vocab_size"], hidden=conf["hidden_size"],
        mlp_hidden=conf["intermediate_size"], layers=len(kinds),
        heads=conf["num_attention_heads"],
        kv_heads=conf["num_key_value_heads"],
        head_dim=conf["hidden_size"] // conf["num_attention_heads"],
        max_seq=conf["max_position_embeddings"],
        norm_eps=float(conf["rms_norm_eps"]), remat=False,
        tie_embeddings=True, lead_kind="", layer_kinds=kinds,
        ssm_heads=conf["mamba_expand"] * conf["hidden_size"], ssm_head_dim=1,
        ssm_state=conf["mamba_d_state"], ssm_conv=conf["mamba_d_conv"],
        ssm_dt_rank=conf["mamba_dt_rank"], dtype=dtype, param_dtype=dtype)


def paths_traced(batcher) -> dict:
    """Which path each kernel's program compiled, as `engine_stats()` has
    it."""
    return {"ssm": dict(batcher.ssm_path),
            "prefill_attention": dict(batcher.prefill_attention_path),
            "decode_attention": dict(batcher.decode_attention_path)}


def scan_check(a_log, seed: int, positions: int, chunk: int,
               second_readings=()) -> dict:
    """The prefill's selective scan alone, as ``ssm1_mixer`` calls it (the
    kernel where ``selective_scan_takes``, as every program of the cell on
    the chip), on seeded float32 inputs of one mixer's shape over
    ``positions`` positions from a zero state, against
    ``reference_jamba.recurrence`` a position at a time: the RMS error of
    the outputs ``o`` and of the state the scan leaves, each over the
    reference's spread. The rates are the model's own (``a_log`` [state,
    channels]); dt is drawn log-uniform over the initialiser's range, x as a
    SiLU of a unit normal, B and C as unit normals (they come out of a norm).
    The whole check's error is the stream's (bfloat16 into dt and x), under
    which a bfloat16 state hides; here nothing but the scan differs, and a
    float32 kernel and a bfloat16 state stand orders of magnitude apart.
    ``second_readings``: (name, keyword arguments of the recurrence) pairs,
    read against the float32 recurrence the same way (the calibration's)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import reference_jamba as reference
    from ray_tpu.ops import ssd

    n, chans = a_log.shape
    a = -jnp.exp(jnp.asarray(a_log, jnp.float32))  # [state, channels]
    keys = jax.random.split(jax.random.key(seed % (1 << 31)), 4)
    dt = jnp.exp(jax.random.uniform(
        keys[0], (positions, chans), jnp.float32, np.log(1e-3), np.log(1e-1)))
    x = jax.nn.silu(jax.random.normal(keys[1], (positions, chans)))
    b, c = (jax.random.normal(k, (positions, n)) for k in keys[2:])
    zero = jnp.zeros((1, n, chans), jnp.float32)
    kernel = ssd.selective_scan_takes(zero, positions)
    scan = ssd.selective_scan if kernel else functools.partial(
        ssd.selective_scan_plain, chunk=chunk)  # the model's `ssm_chunk`
    state, o = jax.jit(scan)(zero, a, dt[None], (dt * x)[None], b[None],
                             c[None])
    recur = jax.jit(reference.recurrence,
                    static_argnames=("state", "scan_sum"))
    ref_state, ref_o = recur(x, dt, b, c, a.T)

    def off(got, want):
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        return float(np.sqrt(np.mean((got - want) ** 2)) / np.std(want))

    out = {"o_rms_err_over_std": off(o[0], ref_o),
           "state_rms_err_over_std": off(state[0].T, ref_state),
           "tol": SCAN_RMS_MAX, "path": "kernel" if kernel else "plain",
           "positions": positions, "channels": chans}
    out["ok"] = bool(max(out["o_rms_err_over_std"],
                         out["state_rms_err_over_std"]) <= SCAN_RMS_MAX)
    for name, kwargs in second_readings:
        other_state, other_o = recur(x, dt, b, c, a.T, **kwargs)
        out.setdefault("second_readings", {})[name] = {
            "o_rms_err_over_std": off(other_o, ref_o),
            "state_rms_err_over_std": off(other_state, ref_state)}
    return out


def reference_check(engine, config: dict, seed: int, prompt_len: int,
                    new_tokens: int, neighbours: int = 3,
                    second_readings=(), scan_readings=()) -> dict:
    """``replica.reference_check`` for this model: a seeded prompt through the
    batcher's own prefill program (logits at its TRUE last position, the
    prompt being shorter than its bucket: the pads behind it must leave no
    trace in the state) and, behind ``neighbours`` busy slots, through the
    scheduler's batched decode step (greedy tokens: the states, windows and
    K/V rows installed, then rewritten and appended to by the steps), against
    ONE full forward of ``reference_jamba`` over the prompt and the chosen
    tokens, the recurrence a position at a time. ``second_readings`` are
    (name, keyword arguments of ``reference_jamba.logits``) pairs: how the
    limits were set (the builder's calibration alone asks), and
    ``scan_readings`` ``scan_check``'s. The check also holds the scan alone
    to the recurrence over the prompt's whole bucket (``scan_check``)."""
    import numpy as np

    from benchmarks import reference_jamba as reference
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
                                      last=new_tokens)[0])
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
    import jax

    on_chip = jax.devices()[0].platform == "tpu"
    out["fell_back"] = {
        what: paths[what] for what, want in KERNEL_PATHS.items()
        if on_chip and any(paths[what].get(program) != path
                           for program, path in want.items())}
    out["scan"] = scan_check(
        batcher.params["blocks"]["ssm1"]["a_log"][0], seed, bucket,
        cfg.ssm_chunk, scan_readings)
    out["ok"] = bool(out["ok"] and out["tokens"]["ok"]
                     and not out["fell_back"] and out["scan"]["ok"])
    for name, kwargs in second_readings:
        other = np.asarray(reference.logits(
            batcher.params, seq[None], config, last=new_tokens,
            **kwargs)[0])
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
               op_scopes={readers.DECODE_PROGRAM:
                          mimo.decode_op_scopes(batcher)})
    return out


def build_application(llm_config, config: dict):
    """``replica.build_application`` with the reference check replaced and
    the prefill's capture added."""
    from ray_tpu.serve.deployment import Deployment

    app = replica.build_application(llm_config, config)

    class JambaBenchLLMServer(app.deployment._target):
        def bench_reference_check(self, seed: int, prompt_len: int,
                                  new_tokens: int) -> dict:
            self._prefill_scopes = mimo.prefill_op_scopes(
                self.engine.batcher, prompt_len)
            return reference_check(self.engine, config, seed, prompt_len,
                                   new_tokens)

        def bench_mamba1_prefill(self, seed: int, prompt_len: int) -> dict:
            return mimo.mimo_prefill(self.engine, self._prefill_scopes, seed,
                                     prompt_len)

    return Deployment(JambaBenchLLMServer, app.deployment._config).bind()


class JambaDeployed(serve.Deployed):
    def __init__(self, cell: dict, args: dict):
        """``serve.Deployed.__init__`` but for the model: it asks
        ``harness.model_config``, which reads another family's keys."""
        self.cell, self.args = cell, args
        self.traffic, self.toy = cell["traffic"], cell["toy"]
        self.conf = toy_config(cell["config"]) if self.toy else cell["config"]
        self.sv = dict(self.conf["serve"], **(
            self.traffic.get("toy_serve", {}) if self.toy else {}))
        self.cfg = jamba_model_config(self.conf)
        self.n_new = int(self.traffic["new_tokens"])
        self.tok = replica.IdTokenizer()
        self.problems = []

    def measure(self, traffic: dict, seed: int, seconds: float,
                trace: bool = False) -> dict:
        """``serve.Deployed.measure``; a traced run first captures one warmed
        prefill (before the window opens: the capture is set-up)."""
        captured = {}
        if trace:
            captured = self.handle.bench_mamba1_prefill.remote(
                seed + 2, traffic["warmup_prompt_tokens"][0]).result()
            harness.say("serve", mamba1_prefill=captured)
        win = super().measure(traffic, seed, seconds, trace)
        win["mamba1_prefill"] = captured
        return win


def _longest_wait(times, t_open: float) -> dict:
    """The longest wait between two chunks of a wave's LAST admitted request
    (every chunk of it but the first comes from a decode step: the requests
    before it also wait through the admits behind them, 5.9 s for the first)
    and when it ended on the window's clock: a stall of the whole batch shows
    here and not in the ITL table, whose last row is the 99.9th of 80,000
    gaps."""
    waits = [(b - a, b - t_open) for a, b in zip(times, times[1:])]
    wait, at = max(waits, default=(0.0, 0.0))
    return {"longest_wait_s": wait, "longest_wait_at_s": at}


def phases(played: dict, clients: int) -> dict:
    """Where the window's end fell: the requests in the order of their first
    tokens are waves of ``clients`` (a closed loop of as many clients as
    slots: a wave is admitted, a prefill each, then decodes together); each
    wave's edges in seconds on the window's clock (it opens at 0: the clients
    started ``ramp_s`` before) and its longest wait, and how far through the
    SECOND wave's decode phase the window closed (ISSUE 60 holds the traffic
    to 0.30-0.75)."""
    t_open = played["t_open"]
    close = played["t_close"] - t_open
    done = sorted((r for r in played["records"] if r.chunk_times),
                  key=lambda r: r.chunk_times[0])
    waves = [{"admit_from_s": min(r.due for r in wave) - t_open,
              "admit_to_s": wave[-1].chunk_times[0] - t_open,
              "decode_to_s": max(r.chunk_times[-1] for r in wave) - t_open,
              **_longest_wait(wave[-1].chunk_times, t_open)}
             for wave in (done[at:at + clients]
                          for at in range(0, len(done), clients))]
    out = {"waves": waves, "close_s": close}
    if len(waves) > 1 and waves[1]["decode_to_s"] > waves[1]["admit_to_s"]:
        out["closed_through_second_decode"] = \
            (close - waves[1]["admit_to_s"]) \
            / (waves[1]["decode_to_s"] - waves[1]["admit_to_s"])
    return out


def account(dep, traffic, schedule, played, marks) -> dict:
    """``serve.account`` plus where the window's end fell among the waves
    (``phases``)."""
    win = _account(dep, traffic, schedule, played, marks)
    win["phases"] = phases(played, int(traffic["clients"]))
    harness.say("serve", phases=win["phases"])
    return win


serve.Deployed = JambaDeployed
serve.account = account
serve.replica = types.SimpleNamespace(
    IdTokenizer=replica.IdTokenizer, build_application=build_application)
run = serve.run
