"""reference.py against models/transformer.py at debug width on the CPU:
the plain float32 block and the program's block compute the same function
of the same weights, with the same gradients, and the tolerances bite."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness, reference
from ray_tpu.models import transformer as T

CONF = dict(harness.TOY_MODEL, rope_theta=1e6, rms_norm_eps=1e-5,
            max_position_embeddings=512, torch_dtype="float32")


@pytest.fixture(scope="module")
def model():
    cfg = T.config(harness.model_config(CONF, lora_rank=4, lora_alpha=8.0,
                                        remat=False), dtype=jnp.float32)
    params = T.init_params(cfg, jax.random.key(3))
    # B starts at zero: give the adapters something to do
    params["lora"] = jax.tree.map(
        lambda a: a if a.any() else 0.05 * jax.random.normal(
            jax.random.key(a.size), a.shape, a.dtype), params["lora"])
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 48))
    return cfg, params, tokens.astype(np.int32)


def test_logits_match_the_program_in_float32(model):
    cfg, params, tokens = model
    with jax.default_matmul_precision("highest"):
        want = np.asarray(T.forward(cfg, params, jnp.asarray(tokens)))
    got = np.asarray(reference.logits(params, tokens, CONF, lora_alpha=8.0))
    # float32 on both sides: only the order of the sums differs
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    last = np.asarray(reference.logits(params, tokens, CONF, lora_alpha=8.0,
                                       last=3))
    np.testing.assert_allclose(last, got[:, -3:], atol=1e-5)
    assert reference.compare_logits(want, got)["ok"]


def test_loss_matches_and_the_tolerance_bites(model):
    cfg, params, tokens = model
    with jax.default_matmul_precision("highest"):
        want = float(T.loss_fn(cfg, params, {"tokens": jnp.asarray(tokens)})[0])
    got = reference.loss_and_lora_grads(params, tokens, CONF, lora_alpha=8.0)[0]
    assert reference.compare_loss(want, got)["rel_diff"] < 1e-5
    # the adapters are part of the function: without them the logits are
    # outside the tolerance, so leaving them out would be caught (the loss
    # of a random model barely moves: it is the weaker of the two checks)
    bare = {k: v for k, v in params.items() if k != "lora"}
    assert not reference.compare_logits(
        reference.logits(bare, tokens, CONF),
        reference.logits(params, tokens, CONF, lora_alpha=8.0))["ok"]
    # and a model run in bfloat16 end to end (storage and accumulation)
    # is outside the logit tolerance that bfloat16 storage alone meets
    rough = np.asarray(reference.logits(params, tokens, CONF, lora_alpha=8.0))
    noisy = rough + 0.2 * rough.std() * np.sign(rough)
    assert not reference.compare_logits(noisy, rough)["ok"]


def test_adapter_gradients_match_the_program_and_the_tolerance_bites(model):
    cfg, params, tokens = model

    def loss_of(lora):
        return T.loss_fn(cfg, dict(params, lora=lora),
                         {"tokens": jnp.asarray(tokens)})[0]

    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(loss_of)(params["lora"])
        # a backward pass that loses half of one adapter's gradient
        wrong = dict(want, wv_b=want["wv_b"] * 0.5)
    loss, got, last = reference.loss_and_lora_grads(
        params, tokens, CONF, lora_alpha=8.0, last=3)
    assert loss == pytest.approx(float(want_loss), rel=1e-5)
    assert {k: v.shape for k, v in got.items()} == \
        {k: v.shape for k, v in want.items()}
    check = reference.compare_grads(want, got)
    assert check["ok"] and max(check["rel_err"].values()) < 1e-4
    np.testing.assert_allclose(
        last, reference.logits(params, tokens, CONF, lora_alpha=8.0, last=3),
        atol=1e-5)
    check = reference.compare_grads(wrong, got)
    assert not check["ok"] and check["rel_err"]["wv_b"] == pytest.approx(0.5)
    # bfloat16 rounding of the gradients themselves is inside the tolerance
    rounded = jax.tree.map(lambda g: g.astype(jnp.bfloat16), want)
    assert reference.compare_grads(rounded, got)["ok"]


def test_the_token_tolerance_takes_a_near_tie_and_no_other_token(model):
    _, params, tokens = model
    ref = np.asarray(reference.logits(params, tokens, CONF, lora_alpha=8.0)[0])
    first = ref.argmax(-1)
    assert reference.compare_tokens(first, ref)["ok"]
    # the runner-up where it is within the tolerance of the first: fine
    order = np.argsort(ref, axis=-1)
    near = ref.copy()
    near[np.arange(len(ref)), order[:, -2]] = ref.max(-1) - 0.1 * ref.std(-1)
    check = reference.compare_tokens(order[:, -2], near)
    assert check["ok"] and check["argmax_agree"] == 0.0
    # one token from another row (a wrong slot, a stale cache row): caught
    wrong = first.copy()
    wrong[7] = first[8] if first[8] != first[7] else first[9]
    check = reference.compare_tokens(wrong, ref)
    assert not check["ok"] and check["max_shortfall_over_std"] > 0.15
    assert not reference.compare_tokens(first[:-1], ref)["ok"]  # a short answer
