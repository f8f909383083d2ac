"""Runner of the sparse serve cells: ``runners/serve.py``'s deployment, traffic
and accounting, with the two things a sparse configuration needs replaced.

- The model: ``harness.model_config`` reads the dense keys of a published
  ``config.json``; the sparse ones (``num_experts``, ``num_experts_per_tok``,
  ``norm_topk_prob``, and the QK-norm every ``OlmoeAttention`` has) go
  through its ``**extra``. ``intermediate_size`` is one expert's width.
- The reference check: ``replica.reference_check`` is wired to
  ``reference.py``'s dense block; this one goes through
  ``reference_olmoe.py``, through the same timed programs (the batcher's own
  prefill, then the batched decode beside busy slots).

Everything else (the front door, the replica, the load generator, the
window, the counters) is ``serve.py``'s own code, loaded as a private copy of
that module whose ``Deployed`` and ``replica`` names are pointed here. The
window also carries the engine's expert counters.
"""

from __future__ import annotations

import types

from benchmarks import harness, replica

serve = harness.load_module("runners", "serve")  # a copy of our own to rebind
_account = serve.account
MOE_KEYS = ("moe_assignments", "moe_rows")


def sparse_model_config(conf: dict, **extra):
    return harness.model_config(
        conf, num_experts=conf["num_experts"],
        experts_per_token=conf["num_experts_per_tok"],
        norm_topk_prob=bool(conf["norm_topk_prob"]), qk_norm=True, **extra)


def system_routing(cfg, params, tokens):
    """The experts the SYSTEM chooses for ``tokens`` [1, S] in every layer,
    [L, S, k]: its own block functions (``_attention_cached``, ``moe_router``,
    ``moe_dropless``) replayed a layer at a time in the system's precision.
    Not the timed programs, which return no routing: the same code."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import decoding as D
    from ray_tpu.models import transformer as T

    s = tokens.shape[1]
    positions = jnp.arange(s)[None, :]
    kv_mask = jnp.ones((1, s), bool)

    @jax.jit
    def layer(x, p):
        row = jnp.zeros((1, s, cfg.kv_heads, cfg.hd), cfg.dtype)
        x, _, _ = D._attention_cached(cfg, x, p, None, positions, row, row,
                                      kv_mask)
        y = T._rms_norm(x, p["ln_mlp"], cfg.norm_eps)
        _, chosen = T.moe_router(cfg, y[0], p)
        return x + T.moe_dropless(cfg, y, p)[0], chosen

    x = params["embed"].astype(cfg.dtype)[jnp.asarray(tokens, jnp.int32)]
    routing = []
    for i in range(cfg.layers):
        x, chosen = layer(x, jax.tree.map(lambda a: a[i], params["blocks"]))
        routing.append(chosen)
    return jnp.stack(routing)


def reference_check(engine, config: dict, seed: int, prompt_len: int,
                    new_tokens: int, neighbours: int = 3) -> dict:
    """``replica.reference_check`` for a sparse model: a seeded prompt through
    the batcher's own prefill program (logits at its last position) and, behind
    ``neighbours`` busy slots, through the batched decode step (greedy tokens),
    against ONE full forward of ``reference_olmoe``. The reference routes by
    itself; how many (layer, token) pairs the system routed to another set of
    experts is counted and reported (``reference_olmoe.count_routing_
    differences`` says why that is not judged)."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import reference_olmoe
    from ray_tpu.models.decoding import SamplingParams

    batcher, cfg = engine.batcher, engine.model_config
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
    bucket = min(batcher._bucket(prompt_len), batcher.max_len)
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :prompt_len] = prompt
    # the bucket was warmed: the program the requests run, not a new one
    last, _, _, load = batcher._prefill_jits[bucket](
        batcher.params, jnp.asarray(toks), jnp.asarray([prompt_len], np.int32))
    lengths = np.minimum(rng.integers(bucket // 2 + 1, bucket + 1, neighbours),
                         batcher.max_len - 2 * new_tokens - 1)
    others = [batcher.submit(rng.integers(0, cfg.vocab_size, int(n)).tolist(),
                             SamplingParams(max_tokens=2 * new_tokens))
              for n in lengths]
    chosen = batcher.submit(prompt.tolist(),
                            SamplingParams(max_tokens=new_tokens)).result(600)
    for other in others:
        other.result(600)
    seq = np.concatenate([prompt, np.asarray(chosen[:-1], np.int32)])
    ref, ref_routing = reference_olmoe.logits(batcher.params, seq[None],
                                              config, last=new_tokens)
    ref = np.asarray(ref[0])
    out = reference_olmoe.compare_logits(
        np.asarray(last, np.float32)[None], ref[:1])
    out["tokens"] = reference_olmoe.compare_tokens(chosen, ref)
    out["ok"] = out["ok"] and out["tokens"]["ok"]
    out["routing"] = reference_olmoe.count_routing_differences(
        system_routing(cfg, batcher.params, seq[None]), ref_routing)
    # dropless, pad rows not counted: the prefill program's own counter
    out["prefill_assignments"] = int(np.asarray(load).sum())
    want = prompt_len * cfg.experts_per_token * cfg.layers
    if out["prefill_assignments"] != want:
        out.update(ok=False, prefill_assignments_expected=want)
    out.update(prompt_len=prompt_len, bucket=bucket,
               neighbour_lens=[int(n) for n in lengths])
    return out


def build_application(llm_config, config: dict):
    """``replica.build_application`` with the reference check replaced."""
    from ray_tpu.serve.deployment import Deployment

    app = replica.build_application(llm_config, config)

    class SparseBenchLLMServer(app.deployment._target):
        def bench_reference_check(self, seed: int, prompt_len: int,
                                  new_tokens: int) -> dict:
            return reference_check(self.engine, config, seed, prompt_len,
                                   new_tokens)

    return Deployment(SparseBenchLLMServer, app.deployment._config).bind()


class SparseDeployed(serve.Deployed):
    def __init__(self, cell: dict, args: dict):
        super().__init__(cell, args)
        self.cfg = sparse_model_config(self.conf)


def account(dep, traffic, schedule, played, marks) -> dict:
    """``serve.account`` plus the window's expert counters."""
    win = _account(dep, traffic, schedule, played, marks)
    opened, closed = marks["engine_open"], marks["engine_close"]
    win["moe"] = dict(
        {k: closed[k] - opened[k] for k in MOE_KEYS},
        expert_load=[b - a for a, b in zip(opened["moe_expert_load"],
                                           closed["moe_expert_load"])],
        layers=dep.cfg.layers)
    return win


serve.Deployed = SparseDeployed
serve.account = account
serve.replica = types.SimpleNamespace(
    IdTokenizer=replica.IdTokenizer, build_application=build_application)
run = serve.run
