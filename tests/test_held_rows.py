"""How many rows a shard that holds a share of the experts gathers for a call
(`transformer.held_rows_cap`), that the capped layout gives what the whole one
gives (`moe_dropless`), and that a long prompt's expert pieces are cut by the
rows they gather (`pattern.expert_rows`, `pattern.sparse_mlp`)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import kimi_linear as K
from ray_tpu.models import pattern as P
from ray_tpu.models import transformer as T

# (held, router outputs, k) of the held-expert cells: the agent cell's
# thirty-second (16 of 512 routed + 256 zero-compute), the rollout and
# sink-window cells' sixteenth, the state-space cell's eighth, the code
# cell's half, and a configuration that holds them all
SHARES = {
    "a thirty-second": (16, 768, 12), "a sixteenth": (16, 256, 8),
    "an eighth": (64, 512, 22), "a half": (128, 256, 10),
    "none held": (None, 64, 8)}
# rows of a call: a decode step's 32 slots, a prompt in the 2,048 bucket, a
# long prompt's piece of `pattern.MLP_ROWS` and of `WHOLE_ROWS_MAX`
CALLS = {"a decode step": 32, "a prefill": 2048, "a piece": 1024,
         "a long piece": 4096}
# the table of ISSUE 59: the cap, or None = the whole layout
TABLE = {
    ("a thirty-second", "a decode step"): 64,  # of 384: what PR 44 gave it
    ("a thirty-second", "a prefill"): 2048,  # of 24,576
    ("a thirty-second", "a piece"): 1024,  # of 12,288
    ("a thirty-second", "a long piece"): 4096,  # of 49,152
    ("a sixteenth", "a decode step"): 64,  # of 256
    ("a sixteenth", "a prefill"): 4096,  # of 16,384
    ("a sixteenth", "a piece"): 2048,  # of 8,192
    ("a sixteenth", "a long piece"): 8192,  # of 32,768: a 1,024 piece's rows
}


def _shaped(share, preset="kimi_linear_debug"):
    count, outputs, k = SHARES[share]
    return T.config(preset, num_experts=outputs, experts_per_token=k,
                    experts_held=None if count is None else (0, count))


@pytest.mark.parametrize("call", CALLS)
@pytest.mark.parametrize("share", SHARES)
def test_the_cap_by_held_share_and_call(share, call):
    """A thin share (four even shares leave at most a quarter of the layout)
    is capped at four even shares, 64 rows at least; an eighth, a half and
    no held share keep the whole layout at every size."""
    cfg = _shaped(share)
    n = CALLS[call] * cfg.experts_per_token
    cap = T.held_rows_cap(cfg, n)
    assert cap == TABLE.get((share, call))
    chose = jnp.zeros((CALLS[call], cfg.experts_per_token), jnp.int32)
    assert (T.layout_counted(cfg, chose) is None) == (cap is None)
    if cap is not None:
        count, outputs, _ = SHARES[share]
        assert cap % 16 == 0 and 2 * cap <= n
        assert cap >= 4 * n * count / outputs  # four even shares fit


@pytest.mark.parametrize("share, rows", [
    ("a thirty-second", 4096), ("a sixteenth", 4096), ("an eighth", 1024),
    ("a half", 1024), ("none held", 1024)])
def test_a_long_prompts_pieces_by_the_rows_they_gather(share, rows):
    """An 8,192-row prompt's expert layers: where the cap answers, 4,096 rows
    a call (a sixteenth gathers 8,192 for them, what a 1,024-row piece
    gathers whole; never more than `WHOLE_ROWS_MAX`, the whole-layout
    branch's size); without a cap `MLP_ROWS` as before."""
    cfg = _shaped(share)
    assert P.expert_rows(cfg, 8192) == rows
    assert P.expert_rows(cfg, 5 * 1024) == 1024  # no larger piece divides it
    gathered = T.held_rows_cap(cfg, rows * cfg.experts_per_token) \
        or rows * cfg.experts_per_token
    assert gathered <= P.MLP_ROWS * cfg.experts_per_token


def _sparse_layer(cfg, layer, key):
    """(small parameters of `layer`, the held experts' whole stacks)."""
    sparse = T.init_params(cfg, jax.random.key(key))["blocks"]["sparse"]
    small = {n: a[layer] for n, a in sparse.items()
             if n not in P.EXPERT_LEAVES}
    return small, {n: sparse[n] for n in P.EXPERT_LEAVES}


# a sixteenth held at toy widths: Kimi-Linear's top-4 times 2.446, MiMo's
# top-8 renormalised; 256 assignments a call, 64 gathered
TOYS = {
    "kimi": ("kimi_linear_debug", dict(num_experts=64, experts_held=(8, 4)),
             64),
    "mimo": ("mimo_v2_debug", dict(num_experts=128, experts_per_token=8,
                                   experts_held=(16, 8)), 32)}


@pytest.mark.parametrize("crowded", [False, True],
                         ids=["the first rows", "the whole layout"])
@pytest.mark.parametrize("family", TOYS)
def test_the_capped_layout_is_the_whole_layouts_result(family, crowded,
                                                       monkeypatch):
    """`moe_dropless` with the cap against the same configuration with the
    cap switched off: output to float32 rounding, `load` to the count; with
    the router crowded onto the held experts more than `cap` assignments are
    held and the `lax.cond` takes the whole layout, nothing dropped."""
    preset, changed, rows = TOYS[family]
    cfg = T.config(preset, **changed)
    first, count = cfg.experts_held
    small, experts = _sparse_layer(cfg, 1, 3)
    if crowded:  # the stored selection bias lifts the held experts
        small = dict(small, router_bias=small["router_bias"].at[
            first:first + count].add(10.0))
    y = jax.random.normal(jax.random.key(4), (1, rows, cfg.hidden))
    real = jnp.arange(rows)[None] < rows - 3  # three pad rows
    routing = K.router(cfg, y[0], small)
    n = rows * cfg.experts_per_token
    held = int(((routing[1] >= first) & (routing[1] < first + count)).sum())
    assert T.held_rows_cap(cfg, n) == 64 and n == 256
    assert (held > 64) == crowded and held > 0
    np.testing.assert_array_equal(
        T.layout_counted(cfg, routing[1]), [n if crowded else 64, crowded])

    def run():
        return jax.jit(lambda y, w, e: T.moe_dropless(
            cfg, y, dict(small, **experts), real, 1, (w, e)))(y, *routing)

    capped, load = run()
    monkeypatch.setattr(T, "held_rows_cap", lambda cfg, n: None)
    whole, load_whole = run()
    np.testing.assert_allclose(capped, whole, atol=1e-5)
    assert np.abs(np.asarray(whole)).max() > 0.01
    np.testing.assert_array_equal(load, load_whole)
    assert int(load.sum()) == (rows - 3) * cfg.experts_per_token


@pytest.mark.parametrize("family, rows", [("kimi", 64), ("mimo", 32)])
def test_a_long_prompt_in_its_pieces_is_the_whole_call(family, rows,
                                                       monkeypatch):
    """128 rows through `sparse_mlp` with `WHOLE_ROWS_MAX` 64 and `MLP_ROWS`
    16 or 8 (64 gathered rows a piece): the capped configuration takes them
    `rows` a call (the cap's floor of 64 rows: 64 of 256 assignments with
    k = 4 or 8), and gives the stream, the load and the choices of ONE call
    over all 128 rows; with the share thickened to a half the pieces are
    `MLP_ROWS`."""
    preset, changed, _ = TOYS[family]
    cfg = T.config(preset, **changed)
    k = cfg.experts_per_token
    small, experts = _sparse_layer(cfg, 1, 3)
    p = dict(small, **experts)
    x = jax.random.normal(jax.random.key(5), (1, 128, cfg.hidden))
    real = jnp.arange(128)[None] < 101
    monkeypatch.setattr(P, "WHOLE_ROWS_MAX", 64)
    monkeypatch.setattr(P, "MLP_ROWS", 64 // k)
    assert P.long_prompt(x) and P.expert_rows(cfg, 128) == rows
    thick = dataclasses.replace(
        cfg, experts_held=(0, cfg.num_experts // 2))
    assert P.expert_rows(thick, 128) == 64 // k
    pieces = jax.jit(lambda x: P.sparse_mlp(cfg, x, p, real, 1, K.router))(x)
    monkeypatch.setattr(P, "WHOLE_ROWS_MAX", 128)
    assert not P.long_prompt(x)
    whole = jax.jit(lambda x: P.sparse_mlp(cfg, x, p, real, 1, K.router))(x)
    np.testing.assert_allclose(pieces[0], whole[0], atol=1e-5)
    np.testing.assert_array_equal(pieces[1], whole[1])  # load
    np.testing.assert_array_equal(pieces[2], whole[2])  # choices
    # reached alike; calls of 64 gathered rows against one of the cap of
    # all 128 rows' assignments; no call took the whole layout
    np.testing.assert_array_equal(
        pieces[3], [int(whole[3][0]), 128 // rows * 64, 0])
    np.testing.assert_array_equal(
        whole[3][1:], [T.held_rows_cap(cfg, 128 * k), 0])
    assert int(pieces[1].sum()) == 101 * k
