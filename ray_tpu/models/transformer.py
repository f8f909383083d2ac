"""Flagship model family: Llama-style decoder-only transformer, TPU-first.

Pure-functional JAX (no module framework): a model is (config, params
pytree, apply fn). Every parameter leaf has a matching *logical axis*
tuple (see ``param_axes``) that ray_tpu.parallel.sharding maps onto the
device mesh — so DP/FSDP/TP/SP are all just rule-table choices over one
program (SURVEY.md §2.3 "parallelism strategies").

The reference framework has no native models (it defers to torch/vLLM;
SURVEY.md §2.4) — here the flagship model lives inside the framework
because Train/Serve/bench all drive it.

Design notes (TPU):
- matmuls in bfloat16 with fp32 accumulation (``preferred_element_type``),
  params kept fp32 by default (master weights), cast per-step.
- attention = ops.flash_attention (pallas on TPU) or ops.ring_attention
  when the sequence axis is sharded. The same forward kernel attends a serve
  prefill's fresh rows from position 0 (``decoding.attend_held``, where
  ``flash_attention_takes`` the shape).
- ``jax.checkpoint`` per block with a policy: beside the block's input the
  backward keeps the named results of the block's matmuls and of the flash
  forward kernel (``REMAT_LADDER``) and recomputes only elementwise work
  (norms, the rotation, the SiLU product); the step builder steps down the
  ladder, to the bare checkpoint at last, where the device's memory is short.
- rotary embeddings computed on the fly (no cached tables → no host
  transfers, fuses into the kernel).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ray_tpu.models import families
from ray_tpu.models.families import Kept
from ray_tpu.ops.attention import FLASH_KEPT, flash_attention, gqa_expand
from ray_tpu.ops.grouped_matmul import grouped_matmul
from ray_tpu.parallel import ring
from ray_tpu.parallel.sharding import constrain

Params = Dict[str, Any]
# What a block's checkpoint keeps for its backward, by `checkpoint_name`,
# richest first; `train/step.py::make_train_step` takes the first rung whose
# compiled step fits the device. q, k and v as `attn_fn` gets them (k and v
# before `gqa_expand`: the KV heads, not the query heads) and the kernel's
# `out` and `lse` are what the flash backward reads; `gate` and `up` are what
# the SiLU product's reads; the residual after attention saves `wo`'s second
# run, and an adapter's `x @ a` (rank values a token, read by b's gradient)
# its own. With all of them no matmul and no flash forward kernel runs twice.
# The sparse MLP names nothing: its experts are recomputed on every rung.
REMAT_ATTENTION = ("attn_q", "attn_k", "attn_v") + FLASH_KEPT
REMAT_LADDER = (
    REMAT_ATTENTION + ("mlp_gate", "mlp_up", "resid_attn", "lora_xa"),
    REMAT_ATTENTION,
    (),  # the bare checkpoint: the block's input alone
)
HELD_ROWS_ROOM = 4  # `held_rows_cap`: a cap is this many even shares of rows


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    """Every model's hyperparameters, one flat record. The fields in
    `COMMON` are every family's; each of the others belongs to the families
    that declare it (`families.py`: a module's `FIELDS`, `check`, `kept`),
    and is refused by name where another family's configuration sets it."""

    vocab_size: int = 32000
    hidden: int = 4096
    mlp_hidden: int = 11008
    layers: int = 32
    heads: int = 32
    kv_heads: int = 32
    head_dim: Optional[int] = None  # default hidden // heads
    max_seq: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16  # activation/compute dtype
    param_dtype: Any = jnp.float32  # master weights
    remat: bool = True  # jax.checkpoint each block
    lora_rank: int = 0  # 0 = dense training; >0 = LoRA adapters on attn+mlp
    lora_alpha: float = 16.0
    # Mixture-of-experts (0 = dense MLP): top-k token-choice routing. The
    # TRAINING forward alone (`_moe_mlp`) drops past `capacity_factor` and
    # dispatches with dense einsums, so that GSPMD partitions the expert
    # dim over the "expert" mesh axis; every SERVED sparse model runs
    # `moe_dropless` (the cached forward): no capacity, nothing dropped.
    num_experts: int = 0
    experts_per_token: int = 2
    capacity_factor: float = 1.25
    # True: the k router weights are divided by their sum (Mixtral);
    # False: used as the softmax gave them (OLMoE, norm_topk_prob false)
    norm_topk_prob: bool = True
    # learned RMSNorm over the whole q and k projections, before the split
    # into heads and before RoPE (OLMoE's q_norm / k_norm)
    qk_norm: bool = False
    # Which attention sublayer: "gqa" (grouped-query attention over K/V rows,
    # the block below) or "cca" (ZAYA1's compressed convolutional attention,
    # models/zaya.py: attention inside the latent heads x head_dim with two
    # causal convolutions and a value shift, which keep one position of
    # state beside the K/V rows). The cached forward alone runs "cca".
    attention: str = "gqa"
    # Which router a sparse layer has: "linear" (one matrix, `moe_router`)
    # or "zaya_mlp" (models/zaya.py: a small MLP on a `router_hidden`-wide
    # projection that also adds the layer before's projection)
    router: str = "linear"
    router_hidden: int = 0
    # share of each head's dimensions that RoPE rotates ("cca", and the
    # full layers of a layer pattern)
    partial_rotary: float = 1.0
    # A layer pattern (models/laguna.py; the cached forward alone runs it):
    # one leading layer of full attention with a dense MLP `dense_mlp_hidden`
    # wide, then (layers - 1) / len(layer_kinds) periods of these kinds,
    # "window" or "full", every one with sparse experts. () = one kind of
    # layer, the block below. A window layer has `window_heads` query heads
    # (`heads` are a full layer's), attends to the last `window` positions,
    # the token's own among them, and keeps that many K/V rows a sequence in
    # a ring (`KVCache.ring_k`); its RoPE is plain at `window_rope_theta`
    # over the whole head, a full layer's is `rope_theta` over
    # `partial_rotary` of the head, scaled by `rope_yarn` = (factor,
    # original positions, beta_fast, beta_slow, attention_factor).
    layer_kinds: Tuple[str, ...] = ()
    window: int = 0
    window_heads: int = 0
    window_rope_theta: float = 10000.0
    rope_yarn: Optional[Tuple[float, ...]] = None
    dense_mlp_hidden: int = 0
    # one sigmoid gate a query head, from the layer's normed input, on the
    # head's output before `wo` (a layer pattern's attention)
    head_gate: bool = False
    # What else a pattern of window and full layers may state (models/
    # laguna.py; XiaomiMiMo/MiMo-V2-Flash does). `lead_kind` "": `layer_kinds`
    # is EVERY layer's kind in order, the first a full layer with the dense
    # MLP (no period is read into it). `window_kv_heads`: a window layer's KV
    # heads (0: `kv_heads`, a full layer's). `value_dim`: the width of a
    # head's values where it is not the keys' `hd` (0). `window_sink`: one
    # learned logit a query head of a window layer that joins its softmax's
    # denominator and carries no value. `value_scale`: a factor on the
    # values. `window_partial_rotary`: the share of a window layer's head
    # that its RoPE rotates (`partial_rotary` is a full layer's).
    window_kv_heads: int = 0
    value_dim: int = 0
    window_sink: bool = False
    value_scale: float = 1.0
    window_partial_rotary: float = 1.0
    # Expert layers of `moe_dropless` (the cached forward). The k router
    # weights times `routed_scale`; a dense SwiGLU of `shared_expert_hidden`
    # that every token runs beside its k experts; and `experts_held` =
    # (first, count): the experts whose weights are HERE, where two or more
    # chips share a layer. The router still scores all `num_experts`; what
    # the absent ones would add is left out.
    routed_scale: float = 1.0
    shared_expert_hidden: int = 0
    experts_held: Optional[Tuple[int, int]] = None
    # A pattern of the kinds "kda" and "mla" (models/kimi_linear.py; the
    # cached forward alone runs it): a leading layer of `lead_kind` with the
    # dense MLP, whole periods of `layer_kinds`, then the layers `tail_kinds`,
    # every layer behind the first with sparse experts.
    # "kda": delta-rule linear attention of `heads` heads of `hd` that keeps
    # a float32 [hd, hd] matrix a head and the last `kda_conv - 1` inputs of
    # three depthwise convolutions (`KVCache.mat` / `.conv`); "mla": latent
    # attention of `heads` heads that keeps `mla_latent + mla_rope_dim`
    # values a position (`KVCache.latent`), no rotation applied. A pattern of
    # "window" and "full" leads with "full" and has no tail.
    # The same family's second form (upstage/Solar-Open2-250B): `lead_kind`
    # "", whole periods of `layer_kinds` ("gkv", "kda", "kda", "kda") and
    # nothing before or behind them, EVERY layer with sparse experts.
    # "gkv": grouped attention of `heads` query heads on `kv_heads` K/V heads
    # over K/V rows (`KVCache.k` / `.v`), no rotation; `gqa_gate`: its
    # output times the sigmoid of a projection of the sublayer's normed
    # input, elementwise, before `wo`. `kda_neg_eigval`: a "kda" layer's step
    # is `2 * sigmoid(.)`, so that `I - beta k k^T` has an eigenvalue in
    # (-1, 1) along k.
    lead_kind: str = "full"
    tail_kinds: Tuple[str, ...] = ()
    kda_conv: int = 0
    gqa_gate: bool = False
    kda_neg_eigval: bool = False
    mla_latent: int = 0
    mla_rope_dim: int = 0
    # How a linear router scores: "softmax" (`moe_router`), or "sigmoid" with
    # a stored selection bias that chooses the k experts and stays out of
    # their weights (`kimi_linear.router`)
    router_score: str = "softmax"
    # Latent attention as DeepSeek-V3's family publishes it (an "mla" layer's
    # and a "scmoe" double layer's; all off for Kimi-Linear). `mla_q_rank`: a
    # low-rank query, hidden -> `mla_q_rank`, RMSNorm, -> heads x (hd +
    # mla_rope_dim); `mla_rotate`: the query's `mla_rope_dim` part and the ONE
    # shared key part are rotated by position (`rope_theta`, pairs
    # INTERLEAVED), the key before it is cached; `mla_scales` = (a factor on
    # the query behind its low-rank norm, a factor on the normed latent, so on
    # the keys' unrotated part and on the values).
    mla_q_rank: int = 0
    mla_rotate: bool = False
    mla_scales: Tuple[float, float] = (1.0, 1.0)
    # A pattern of the one kind "scmoe" (models/longcat.py; the cached forward
    # alone runs it): `layers` DOUBLE layers and nothing before or behind them
    # (`lead_kind` ""), each two latent-attention sublayers (two cache layers:
    # `latent_layers`), two dense SwiGLU MLPs of `dense_mlp_hidden` and one
    # expert layer on a shortcut beside the second attention and MLP.
    # `zero_experts`: router outputs behind the `num_experts` routed ones that
    # multiply nothing: a choice among them adds its weight times the expert
    # layer's input (`moe_dropless`), here, whatever `experts_held` says.
    zero_experts: int = 0
    # A pattern of layers that are ONE sublayer each, `x + f(norm(x))`
    # (models/nemotron_h.py; the cached forward alone runs it): `layer_kinds`
    # is every layer's kind in order, no lead, period or tail read into it
    # ("ssm", "gqa", "lmoe"). "ssm": a Mamba-2 mixer of `ssm_heads` heads of
    # `ssm_head_dim` whose B and C are shared by the heads of one of
    # `ssm_groups` groups; it keeps a float32 [ssm_state, ssm_heads *
    # ssm_head_dim] state and the last `ssm_conv - 1` inputs of its
    # convolution a sequence (`KVCache.mat` / `.conv`); a prefill runs the
    # recurrence `ssm_chunk` positions at a time. "gqa": grouped attention
    # over K/V rows, no rotation. "lmoe": an expert layer whose experts work
    # on a `moe_latent`-wide projection of the stream (0: on the stream).
    # "ssm1": a Mamba-1 mixer of `ssm_heads * ssm_head_dim` channels whose
    # decay differs by channel and by state index, its steps projected
    # through `ssm_dt_rank`; it keeps what an "ssm" layer keeps. "mlp": a
    # dense SwiGLU of `mlp_hidden`.
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 0
    ssm_state: int = 0
    ssm_conv: int = 0
    ssm_chunk: int = 128
    ssm_dt_rank: int = 0
    moe_latent: int = 0
    # What one expert of `moe_dropless` is (and a pattern's shared expert):
    # "swiglu", three matrices, `(silu(x Wg) * (x Wu)) Wd`; or "relu2", two,
    # `relu(x Wu)^2 Wd`
    expert_act: str = "swiglu"
    # A LOOPED model of the one block (ByteDance/Ouro-2.6B, `total_ut_steps`):
    # the SAME `layers` layers run `loop_steps` times a token, the final norm
    # at the end of every pass and its output the next pass's input; a pass
    # attends its OWN pass's rows, so a sequence keeps `loop_steps * layers`
    # K/V layers (`kept`), pass t's layer i as cache layer `t * layers + i`.
    # An exit gate (hidden -> 1 with a bias, `params["exit_w"]` / `["exit_b"]`)
    # reads every pass's normed output: `forward_cached`'s `aux["exit_pdf"]`.
    # `exit_threshold`: the cumulated exit probability at which a token
    # leaves the loop; 1.0, every token runs every pass, is all that runs.
    # `sandwich`: a second RMSNorm BEHIND each sublayer, on its output before
    # the residual sum (`ln_attn_post`, `ln_mlp_post`).
    loop_steps: int = 1
    sandwich: bool = False
    exit_threshold: float = 1.0

    def __post_init__(self):
        if self.attention not in ("gqa", "cca"):
            raise ValueError(f"unknown attention {self.attention!r}")
        if self.router not in ("linear", "zaya_mlp"):
            raise ValueError(f"unknown router {self.router!r}")
        if self.router_score not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown router_score {self.router_score!r}")
        if self.expert_act not in ("swiglu", "relu2"):
            raise ValueError(f"unknown expert_act {self.expert_act!r}")
        if self.experts_held is not None:
            first, count = self.experts_held
            if not (self.num_experts and 0 <= first and count >= 1
                    and first + count <= self.num_experts):
                raise ValueError(
                    f"experts_held {self.experts_held} is no share of "
                    f"num_experts {self.num_experts}")
        family = families.of(self)
        refused = [f.name for f in dataclasses.fields(self)
                   if f.name not in COMMON and f.name not in family.FIELDS
                   and getattr(self, f.name) != f.default]
        if refused:
            raise ValueError(
                f"{', '.join(refused)}: no field of {family.__name__}, the "
                "family of layers this configuration is (families.py); it "
                f"reads {', '.join(sorted(family.FIELDS))} beside the common "
                "fields")
        family.check(self)

    @property
    def hd(self) -> int:
        return self.head_dim or self.hidden // self.heads

    @property
    def kinds(self) -> Tuple[str, ...]:
        """Every layer's kind, in order; () without a pattern."""
        if not self.layer_kinds:
            return ()
        lead = (self.lead_kind,) if self.lead_kind else ()
        return (*lead, *self.layer_kinds * self.periods, *self.tail_kinds)

    def layers_of(self, kind: str) -> int:
        """How many of a pattern's layers are of `kind`."""
        return self.kinds.count(kind)

    @property
    def router_outputs(self) -> int:
        """What a sparse layer's router scores: the routed experts, then
        the zero-compute ones."""
        return self.num_experts + self.zero_experts

    @property
    def latent_row(self) -> int:
        """Width of a cached latent row (`KVCache.latent`): its `mla_latent
        + mla_rope_dim` values in whole 128-lane words, the rest zeros. The
        chip's tiling keeps a 576-value bfloat16 row in 640 lanes whatever
        its logical width; stated so, a copy takes whole rows."""
        return -(-(self.mla_latent + self.mla_rope_dim) // 128) * 128

    def kept(self, max_len: Optional[int] = None) -> Tuple[Kept, ...]:
        """What this configuration's layers keep a sequence in a cache of
        `max_len` rows a slot (`max_seq` if None): its family's statement
        (`families.Kept`), the entries that have a layer. Remembered on the
        instance (its fields are frozen, and no field): the engine's pump
        asks every step (`_kv_rows`)."""
        max_len = max_len or self.max_seq
        memo = self.__dict__.setdefault("_kept", {})
        if max_len not in memo:
            memo[max_len] = tuple(k for k in families.of(self).kept(
                self, max_len) if k.layers)
        return memo[max_len]

    @property
    def keeps(self) -> Tuple[str, ...]:
        """The per-slot fields of `decoding.KVCache` that this
        configuration's layers keep for a sequence: rows appended a position
        at a time ("k", "v", "ring_k", "ring_v", "latent") and states read
        and rewritten every step ("state", "mat", "conv")."""
        return tuple(name for kept in self.kept() for name in kept.fields)

    @property
    def stateful(self) -> bool:
        """Whether a sequence keeps more than rows between steps: something
        its layers read and rewrite every step (`KVCache.STATES`)."""
        return any(kept.rows is None for kept in self.kept())

    def _layers_kept(self, field: str) -> int:
        return sum(k.layers for k in self.kept() if field in k.fields)

    @property
    def periods(self) -> int:
        """Whole periods of `layer_kinds` behind the leading layer, if the
        pattern has one (0: no pattern)."""
        return (self.layers - bool(self.lead_kind) - len(self.tail_kinds)) \
            // len(self.layer_kinds) if self.layer_kinds else 0

    @property
    def full_layers(self) -> int:
        """Layers whose K/V rows are slots of `max_len` (`KVCache.k`): all
        of them without a pattern; with one, its "full" ones."""
        return self._layers_kept("k")

    @property
    def window_layers(self) -> int:
        """Layers whose K/V rows are a ring of `window` (`KVCache.ring_k`)."""
        return self._layers_kept("ring_k")

    @property
    def latent_layers(self) -> int:
        """Layers of `KVCache.latent`: one an "mla" layer, two a "scmoe"
        double layer (its two attention sublayers), in the layers' order."""
        return self._layers_kept("latent")

    @property
    def sparse_layers(self) -> int:
        """Layers that route: all of a sparse model's, or all but a
        pattern's leading dense one (a double layer routes once); a family
        whose layers do not all route states its own (`sparse_layers`)."""
        if not self.num_experts:
            return 0
        stated = getattr(families.of(self), "sparse_layers", None)
        if stated is not None:
            return stated(self)
        return self.layers - bool(self.lead_kind and self.layer_kinds)

    def flops_per_token(self) -> float:
        """Approx forward+backward FLOPs/token (6*N + attention), for MFU.
        For a sparse model N counts ALL experts, not the experts_per_token
        a token runs: benchmarks/moe_cost.py is the yardstick there."""
        n_params = self.num_params()
        attn = 12 * self.layers * self.hidden * self.max_seq  # rough
        return 6 * n_params + attn

    def num_params(self) -> int:
        h, m, l, v = self.hidden, self.mlp_hidden, self.layers, self.vocab_size
        hd, nh, nkv = self.hd, self.heads, self.kv_heads
        if self.layer_kinds:
            return families.of(self).num_params(self)
        mlp = 3 * h * m
        if self.num_experts:
            mlp = self.num_experts * 3 * h * m + h * self.num_experts  # + router
        per_layer = h * (nh * hd) + 2 * h * (nkv * hd) + (nh * hd) * h + mlp + 2 * h
        if self.qk_norm:
            per_layer += nh * hd + nkv * hd
        if self.sandwich:
            per_layer += 2 * h
        if self.attention == "cca" or self.router == "zaya_mlp":
            per_layer += families.of(self).extra_params(self)
        emb = v * h * (1 if self.tie_embeddings else 2)
        gate = h + 1 if self.loop_steps > 1 else 0
        return l * per_layer + emb + h + gate


# The fields every family reads, or that no check has ever policed (the last
# five: set where nothing reads them, they change nothing). Every other field
# is the families' that declare it in their `FIELDS`.
COMMON = frozenset({
    "vocab_size", "hidden", "mlp_hidden", "layers", "heads", "kv_heads",
    "head_dim", "max_seq", "rope_theta", "norm_eps", "dtype", "param_dtype",
    "remat", "num_experts", "experts_per_token", "norm_topk_prob",
    "lora_alpha", "capacity_factor", "router_hidden", "window_rope_theta",
    "routed_scale"})

# -- the one block as a family (`families.py`): dense, Mixtral, OLMoE ---------
# `SHARED`: what the one block's other sublayers (`zaya.py`) read too; a
# looped model's three fields are the one block's with its own sublayers alone
SHARED = frozenset({"tie_embeddings", "lora_rank", "qk_norm"})
FIELDS = SHARED | {"loop_steps", "sandwich", "exit_threshold"}


def check(cfg: TransformerConfig) -> None:
    """The one block needs of its fields what their types do not say: of a
    looped model, that every token runs every pass and no layer routes."""
    if cfg.loop_steps < 1:
        raise ValueError(f"loop_steps {cfg.loop_steps}: a model runs its "
                         "layers once a token at least")
    if cfg.exit_threshold < 1.0:
        raise ValueError(
            f"exit_threshold {cfg.exit_threshold} below 1: tokens of one "
            "batch would leave the loop at different passes, and the rows of "
            "the passes a token skipped, which later tokens' later passes "
            "read, would never be written (decoding.forward_cached runs "
            "every pass for every token: the threshold 1)")
    if cfg.loop_steps > 1 and (cfg.num_experts or cfg.lora_rank):
        raise ValueError(
            f"loop_steps {cfg.loop_steps} with num_experts "
            f"{cfg.num_experts} / lora_rank {cfg.lora_rank}: the looped block "
            "is the dense one, served (what the expert layers count is a "
            "pass's, and loss_fn has no loss over the exits for an adapter "
            "to train under)")


def kept(cfg: TransformerConfig, max_len: int) -> Tuple[Kept, ...]:
    """Every layer's K/V rows of every pass, `max_len` a slot."""
    return (Kept(("k", "v"), cfg.loop_steps * cfg.layers, max_len,
                 (cfg.kv_heads, cfg.hd)),)


# Presets: name -> field values; `config` constructs (the family's module
# checks, imported then and not with this one). llama2_7b mirrors the target
# (BASELINE.md "Train Llama-2-7B LoRA ... v5e-64").
PRESETS: Dict[str, TransformerConfig] = {
    "debug": dict(
        vocab_size=512, hidden=128, mlp_hidden=352, layers=2, heads=4,
        kv_heads=2, max_seq=128, remat=False,
    ),
    "tiny": dict(
        vocab_size=2048, hidden=256, mlp_hidden=704, layers=4, heads=8,
        kv_heads=4, max_seq=512,
    ),
    "llama2_7b": dict(),
    "llama2_7b_lora": dict(lora_rank=16),
    "llama3_8b": dict(
        vocab_size=128256, hidden=4096, mlp_hidden=14336, layers=32,
        heads=32, kv_heads=8, max_seq=8192, rope_theta=500000.0,
    ),
    # Mixtral-8x7B-shaped MoE (EP flagship)
    "mixtral_8x7b": dict(
        vocab_size=32000, hidden=4096, mlp_hidden=14336, layers=32,
        heads=32, kv_heads=8, max_seq=8192, rope_theta=1e6,
        num_experts=8, experts_per_token=2,
    ),
    "moe_debug": dict(
        vocab_size=512, hidden=128, mlp_hidden=256, layers=2, heads=4,
        kv_heads=2, max_seq=128, remat=False, num_experts=4,
        experts_per_token=2,
    ),
    # allenai/OLMoE-1B-7B-0125-Instruct: MHA with QK-norm, 64 dropless
    # SwiGLU experts of width 1024, top-8 without renormalisation
    "olmoe_1b_7b": dict(
        vocab_size=50304, hidden=2048, mlp_hidden=1024, layers=16, heads=16,
        kv_heads=16, max_seq=4096, rope_theta=1e4, norm_eps=1e-5,
        num_experts=64, experts_per_token=8, norm_topk_prob=False,
        qk_norm=True,
    ),
    "olmoe_debug": dict(
        vocab_size=512, hidden=128, mlp_hidden=64, layers=2, heads=4,
        kv_heads=4, max_seq=128, remat=False, num_experts=16,
        experts_per_token=4, norm_topk_prob=False, qk_norm=True,
        dtype=jnp.float32,
    ),
    # Zyphra/ZAYA1-8B (models/zaya.py): attention in a compressed latent
    # with a convolution state, a router that carries its representation
    # from layer to layer, top-1 of 16 wide experts, a tied 262k vocabulary
    "zaya1_8b": dict(
        vocab_size=262272, hidden=2048, mlp_hidden=2048, layers=40, heads=8,
        kv_heads=2, head_dim=128, max_seq=131072, rope_theta=5e6,
        norm_eps=1e-5, tie_embeddings=True, num_experts=16,
        experts_per_token=1, norm_topk_prob=False, attention="cca",
        router="zaya_mlp", router_hidden=256, partial_rotary=0.5,
    ),
    # ByteDance/Ouro-2.6B's looped block at debug widths: 3 layers run twice a
    # token (6 cache layers a sequence), 4 query heads = 4 KV heads, a norm
    # behind each sublayer, the exit gate. The published widths are the
    # benchmark's to build (benchmarks/runners/serve_ouro.py)
    "ouro_debug": dict(
        vocab_size=512, hidden=128, mlp_hidden=352, layers=3, heads=4,
        kv_heads=4, max_seq=128, remat=False, rope_theta=1e6, norm_eps=1e-6,
        loop_steps=2, sandwich=True, dtype=jnp.float32,
    ),
    "zaya_debug": dict(
        vocab_size=512, hidden=128, mlp_hidden=64, layers=3, heads=4,
        kv_heads=2, head_dim=16, max_seq=128, remat=False,
        tie_embeddings=True, num_experts=8, experts_per_token=1,
        norm_topk_prob=False, attention="cca", router="zaya_mlp",
        router_hidden=32, partial_rotary=0.5, dtype=jnp.float32,
    ),
    # poolside/Laguna-S-2.1's block at debug widths (models/laguna.py): one
    # full layer with a dense MLP, then two periods of three window layers
    # (6 heads, a ring of 8) and one full layer (4 heads), a gate a head,
    # top-4 of 16 experts times 2.5 of which 8 are held, a shared expert.
    # The published widths are the benchmark's to build from `config.json`
    # (benchmarks/runners/serve_laguna.py)
    "laguna_debug": dict(
        vocab_size=512, hidden=128, mlp_hidden=64, layers=9, heads=4,
        kv_heads=2, head_dim=16, max_seq=128, remat=False, rope_theta=5e5,
        norm_eps=1e-6, num_experts=16, experts_per_token=4,
        norm_topk_prob=True, partial_rotary=0.5,
        layer_kinds=("window", "window", "window", "full"), window=8,
        window_heads=6, window_rope_theta=1e4,
        rope_yarn=(4.0, 16.0, 32.0, 1.0, 1.1386294361119891),
        dense_mlp_hidden=192, head_gate=True, routed_scale=2.5,
        shared_expert_hidden=64, experts_held=(0, 8), dtype=jnp.float32,
    ),
    # XiaomiMiMo/MiMo-V2-Flash's block at debug widths (models/laguna.py, the
    # list form): full (dense MLP), 2 window, full, 3 window; 8 query heads
    # on 2 KV heads (full) and 4 (window), keys of 24 (8 rotated) beside
    # values of 16 times 0.707, a window of 8 with a learned sink, sigmoid
    # top-2 of 8 experts of which 4 are held, no shared expert, no gate. The
    # published widths are the benchmark's to build
    # (benchmarks/runners/serve_mimo.py)
    "mimo_v2_debug": dict(
        vocab_size=512, hidden=128, mlp_hidden=64, layers=7, heads=8,
        kv_heads=2, head_dim=24, max_seq=128, remat=False, rope_theta=5e6,
        norm_eps=1e-5, num_experts=8, experts_per_token=2,
        norm_topk_prob=True, partial_rotary=0.334, lead_kind="",
        layer_kinds=("full", "window", "window", "full", "window", "window",
                     "window"), window=8, window_heads=8, window_kv_heads=4,
        window_rope_theta=1e4, window_partial_rotary=0.334, value_dim=16,
        window_sink=True, value_scale=0.707, dense_mlp_hidden=192,
        router_score="sigmoid", experts_held=(0, 4), dtype=jnp.float32,
    ),
    # moonshotai/Kimi-Linear-48B-A3B-Instruct's block at debug widths
    # (models/kimi_linear.py): a leading kda layer with a dense MLP, two
    # periods of (kda, kda, mla, kda) and the trailing (kda, mla), 4 heads of
    # 16, a latent of 32 + 8, sigmoid top-4 of 16 experts times 2.446 of
    # which 8 are held, a shared expert. The published widths are the
    # benchmark's to build (benchmarks/runners/serve_kimi_linear.py)
    "kimi_linear_debug": dict(
        vocab_size=512, hidden=128, mlp_hidden=64, layers=11, heads=4,
        kv_heads=4, head_dim=16, max_seq=128, remat=False, norm_eps=1e-5,
        num_experts=16, experts_per_token=4, norm_topk_prob=True,
        layer_kinds=("kda", "kda", "mla", "kda"), lead_kind="kda",
        tail_kinds=("kda", "mla"), kda_conv=4, mla_latent=32, mla_rope_dim=8,
        router_score="sigmoid", dense_mlp_hidden=192, routed_scale=2.446,
        shared_expert_hidden=64, experts_held=(0, 8), dtype=jnp.float32,
    ),
    # upstage/Solar-Open2-250B's pattern at debug widths, the family's form
    # without a lead (models/kimi_linear.py): two periods of one gated
    # grouped-attention layer (8 query heads on 2 K/V heads: groups of 4)
    # and three delta-rule layers whose steps reach 2, every layer sparse:
    # sigmoid top-4 of 16 experts, 4 held, one shared. The published widths
    # are the benchmark's to build (benchmarks/runners/serve_solar_open2.py)
    "solar_open2_debug": dict(
        vocab_size=512, hidden=128, mlp_hidden=64, layers=8, heads=8,
        kv_heads=2, head_dim=16, max_seq=128, remat=False, norm_eps=1e-5,
        num_experts=16, experts_per_token=4, norm_topk_prob=True,
        layer_kinds=("gkv", "kda", "kda", "kda"), lead_kind="", kda_conv=4,
        gqa_gate=True, kda_neg_eigval=True, router_score="sigmoid",
        shared_expert_hidden=64, experts_held=(0, 4), dtype=jnp.float32,
    ),
    # meituan-longcat/LongCat-Flash-Chat's double layer at debug widths
    # (models/longcat.py): 3 double layers of two latent attentions (4 heads
    # of 16 + 8 rotated, a latent of 32, a query through 24), two dense MLPs
    # and one expert layer on the shortcut: softmax top-4 of 16 routed (4
    # held) + 8 zero-compute outputs, not renormalised, times 6. The
    # published widths are the benchmark's to build
    # (benchmarks/runners/serve_longcat.py)
    "longcat_debug": dict(
        vocab_size=512, hidden=128, mlp_hidden=64, layers=3, heads=4,
        kv_heads=4, head_dim=16, max_seq=128, remat=False, rope_theta=1e7,
        norm_eps=1e-5, num_experts=16, experts_per_token=4,
        norm_topk_prob=False, layer_kinds=("scmoe",), lead_kind="",
        mla_latent=32, mla_rope_dim=8, mla_q_rank=24, mla_rotate=True,
        mla_scales=((128 / 24) ** 0.5, 2.0), zero_experts=8,
        dense_mlp_hidden=192, routed_scale=6.0, experts_held=(0, 4),
        dtype=jnp.float32,
    ),
    # nvidia/NVIDIA-Nemotron-3-Super-120B-A12B's layers at debug widths
    # (models/nemotron_h.py): 11 layers of ONE sublayer each, the published
    # string's own order of kinds (M E M E M * E M E M *): 5 Mamba-2 mixers
    # (8 heads of 8 in 2 groups, a state of 16, 4 taps), 2 attentions of 4
    # heads on 2 KV heads without rotation, 4 expert layers of sigmoid top-4
    # of 16 ReLU^2 experts in a latent of 32 (8 held) times 5 with a shared
    # expert on the full width. The published widths are the benchmark's to
    # build (benchmarks/runners/serve_nemotron_h.py)
    "nemotron_h_debug": dict(
        vocab_size=512, hidden=128, mlp_hidden=48, layers=11, heads=4,
        kv_heads=2, head_dim=16, max_seq=128, remat=False, norm_eps=1e-5,
        num_experts=16, experts_per_token=4, norm_topk_prob=True,
        layer_kinds=("ssm", "lmoe", "ssm", "lmoe", "ssm", "gqa", "lmoe",
                     "ssm", "lmoe", "ssm", "gqa"), lead_kind="",
        ssm_heads=8, ssm_head_dim=8, ssm_groups=2, ssm_state=16, ssm_conv=4,
        ssm_chunk=8, moe_latent=32, expert_act="relu2",
        router_score="sigmoid", routed_scale=5.0, shared_expert_hidden=96,
        experts_held=(0, 8), dtype=jnp.float32,
    ),
    # ai21labs/AI21-Jamba2-3B's layers at debug widths (models/nemotron_h.py):
    # two periods of four published layers with the attention third, each a
    # mixer or an attention and then a dense MLP = 16 sublayers: 6 Mamba-1
    # mixers (256 channels, a state of 16, 4 taps, steps through a rank of 10:
    # no multiple of 8), 2 attentions of 4 heads on ONE KV head without
    # rotation, 8 SwiGLU MLPs; the head tied to the embedding. The published
    # widths are the benchmark's to build (benchmarks/runners/serve_jamba.py)
    "jamba_debug": dict(
        vocab_size=512, hidden=128, mlp_hidden=192, layers=16, heads=4,
        kv_heads=1, head_dim=32, max_seq=128, remat=False, norm_eps=1e-6,
        layer_kinds=("ssm1", "mlp", "ssm1", "mlp", "gqa", "mlp", "ssm1",
                     "mlp") * 2, lead_kind="", tie_embeddings=True,
        ssm_heads=256, ssm_head_dim=1, ssm_state=16, ssm_conv=4, ssm_chunk=8,
        ssm_dt_rank=10, dtype=jnp.float32,
    ),
}


def config(name_or_cfg, **overrides) -> TransformerConfig:
    if isinstance(name_or_cfg, str):
        return TransformerConfig(**{**PRESETS[name_or_cfg], **overrides})
    return dataclasses.replace(name_or_cfg, **overrides) if overrides \
        else name_or_cfg


# ---------------------------------------------------------------------------
# Parameter init + logical axes
# ---------------------------------------------------------------------------

def _dense_init(key, shape, dtype, fan_in):
    return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)).astype(dtype)


def init_params(cfg: TransformerConfig, key: jax.Array) -> Params:
    """Initialize the parameter pytree. Layer params are STACKED on a
    leading ``layers`` dim so the forward is one ``lax.scan`` — one XLA
    while-loop body compiled once, not ``layers`` inlined copies (compile
    time and HBM win on TPU)."""
    if cfg.layer_kinds:  # stacked by kind, never held twice
        return families.of(cfg).init_params(cfg, key)
    h, m, v, l = cfg.hidden, cfg.mlp_hidden, cfg.vocab_size, cfg.layers
    hd, nh, nkv = cfg.hd, cfg.heads, cfg.kv_heads
    pd = cfg.param_dtype
    keys = jax.random.split(key, 13)

    def stack(k, shape, fan_in):
        ks = jax.random.split(k, l)
        return jnp.stack([_dense_init(ks[i], shape, pd, fan_in) for i in range(l)])

    blocks: Params = {
        "wq": stack(keys[1], (h, nh, hd), h),
        "wk": stack(keys[2], (h, nkv, hd), h),
        "wv": stack(keys[3], (h, nkv, hd), h),
        "wo": stack(keys[4], (nh, hd, h), nh * hd),
        "ln_attn": jnp.ones((l, h), pd),
        "ln_mlp": jnp.ones((l, h), pd),
    }
    if cfg.qk_norm:
        blocks["ln_q"] = jnp.ones((l, nh * hd), pd)
        blocks["ln_k"] = jnp.ones((l, nkv * hd), pd)
    if cfg.sandwich:
        blocks["ln_attn_post"] = jnp.ones((l, h), pd)
        blocks["ln_mlp_post"] = jnp.ones((l, h), pd)
    if cfg.attention == "cca" or cfg.router == "zaya_mlp":
        families.of(cfg).init_block_params(cfg, blocks, stack,
                                           jax.random.fold_in(key, 13))
    if cfg.num_experts:
        e = cfg.num_experts
        if cfg.router == "linear":
            blocks["router"] = stack(keys[5], (h, e), h)
        blocks["wi_gate"] = stack(keys[6], (e, h, m), h)
        blocks["wi_up"] = stack(keys[7], (e, h, m), h)
        blocks["wo_mlp"] = stack(keys[8], (e, m, h), m)
    else:
        blocks["wi_gate"] = stack(keys[5], (h, m), h)
        blocks["wi_up"] = stack(keys[6], (h, m), h)
        blocks["wo_mlp"] = stack(keys[7], (m, h), m)
    params: Params = {
        "embed": _dense_init(keys[0], (v, h), pd, h),  # scaled like output
        "blocks": blocks,
        "ln_f": jnp.ones((h,), pd),
    }
    if not cfg.tie_embeddings:
        # keys[12]: own key — keys[8] seeds the MoE wo_mlp stack, and
        # sharing it would correlate the two inits (advisor finding, r1)
        params["unembed"] = _dense_init(keys[12], (h, v), pd, h)
    if cfg.loop_steps > 1:  # the exit gate: hidden -> 1 with a bias
        params["exit_w"] = _dense_init(jax.random.fold_in(key, 14), (h,), pd, h)
        params["exit_b"] = jnp.zeros((1,), pd)
    if cfg.lora_rank:
        r = cfg.lora_rank
        def lz(shape):  # LoRA B starts at zero
            return jnp.zeros(shape, pd)
        params["lora"] = {
            "wq_a": stack(keys[9], (h, r), h), "wq_b": jnp.zeros((l, r, nh * hd), pd),
            "wv_a": stack(keys[10], (h, r), h), "wv_b": jnp.zeros((l, r, nkv * hd), pd),
            "wi_a": stack(keys[11], (h, r), h), "wi_b": lz((l, r, m)),
        }
    return params


def param_axes(cfg: TransformerConfig) -> Params:
    """Pytree of logical-axis tuples mirroring init_params output.
    Feed to parallel.sharding.tree_shardings(mesh, ...) for NamedShardings."""
    if cfg.layer_kinds:
        return families.of(cfg).param_axes(cfg)
    block_axes: Params = {
        "wq": ("layers", "embed", "heads", "head_dim"),
        "wk": ("layers", "embed", "kv_heads", "head_dim"),
        "wv": ("layers", "embed", "kv_heads", "head_dim"),
        "wo": ("layers", "heads", "head_dim", "embed"),
        "ln_attn": ("layers", "norm"),
        "ln_mlp": ("layers", "norm"),
    }
    if cfg.qk_norm:
        block_axes.update({"ln_q": ("layers", "heads"),
                           "ln_k": ("layers", "kv_heads")})
    if cfg.sandwich:
        block_axes.update({"ln_attn_post": ("layers", "norm"),
                           "ln_mlp_post": ("layers", "norm")})
    if cfg.attention == "cca" or cfg.router == "zaya_mlp":
        families.of(cfg).update_block_axes(cfg, block_axes)
    if cfg.num_experts:
        if cfg.router == "linear":  # the router stays replicated
            block_axes["router"] = ("layers", "embed", None)
        block_axes.update({
            "wi_gate": ("layers", "expert", "embed", "mlp"),
            "wi_up": ("layers", "expert", "embed", "mlp"),
            "wo_mlp": ("layers", "expert", "mlp", "embed"),
        })
    else:
        block_axes.update({
            "wi_gate": ("layers", "embed", "mlp"),
            "wi_up": ("layers", "embed", "mlp"),
            "wo_mlp": ("layers", "mlp", "embed"),
        })
    axes: Params = {
        "embed": ("vocab", "embed"),
        "blocks": block_axes,
        "ln_f": ("norm",),
    }
    if not cfg.tie_embeddings:
        axes["unembed"] = ("embed", "vocab")
    if cfg.loop_steps > 1:
        axes.update(exit_w=("norm",), exit_b=(None,))
    if cfg.lora_rank:
        axes["lora"] = {
            "wq_a": ("layers", "embed", "lora_rank"), "wq_b": ("layers", "lora_rank", "heads"),
            "wv_a": ("layers", "embed", "lora_rank"), "wv_b": ("layers", "lora_rank", "kv_heads"),
            "wi_a": ("layers", "embed", "lora_rank"), "wi_b": ("layers", "lora_rank", "mlp"),
        }
    return axes


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps)).astype(x.dtype) * w.astype(x.dtype)


def _rope(x, positions, theta):
    """Rotary embedding. x [B,S,H,D], positions [B,S] or [S]."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d // 2, dtype=jnp.float32) / (d // 2))
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [B,S,D/2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _lora_xa(x, a):
    """An adapter's first product, the rows times `a`: what its second
    product and `a`'s gradient read, so the checkpoint keeps it."""
    with jax.named_scope("lora"):
        return checkpoint_name(
            jnp.einsum("bsh,hr->bsr", x, a.astype(x.dtype)), "lora_xa")


def _lora_out(xa, b, scale):
    with jax.named_scope("lora"):
        return xa @ b.astype(xa.dtype) * scale


def _moe_mlp(cfg: TransformerConfig, y, p):
    """Top-k token-choice MoE with capacity drop (GShard/Mixtral recipe).

    Dense-dispatch formulation: routing becomes one-hot dispatch/combine
    tensors and the expert FFN is a single batched einsum with the expert
    dim sharded over the "expert" mesh axis — GSPMD inserts the
    all-to-alls; no dynamic gather on the TPU hot path.
    """
    b, s, h = y.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    t = b * s
    x = y.reshape(t, h)

    logits = jnp.einsum("th,he->te", x, p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = lax.top_k(probs, k)  # [T,k]
    if cfg.norm_topk_prob:
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)

    # capacity per expert; first-choice assignments get priority by
    # ordering the flattened (choice-major) token stream
    cap = max(4, int(cfg.capacity_factor * t * k / e))
    oh = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)          # [T,k,E]
    ohf = oh.transpose(1, 0, 2).reshape(k * t, e)                # choice-major
    pos = (jnp.cumsum(ohf, axis=0) - 1.0) * ohf                  # slot per entry
    keep = (pos < cap) & (ohf > 0)
    slot = pos.sum(-1).astype(jnp.int32)                         # [kT]
    slot_oh = jax.nn.one_hot(slot, cap, dtype=jnp.float32)       # [kT,C]
    dispatch = ohf[:, :, None] * slot_oh[:, None, :] * keep.any(-1)[:, None, None]
    gates_f = gate_vals.T.reshape(k * t)                         # choice-major
    combine = dispatch * gates_f[:, None, None]

    xk = jnp.tile(x, (k, 1)).astype(jnp.float32)                 # [kT,h]
    expert_in = jnp.einsum("pec,ph->ech", dispatch, xk).astype(y.dtype)
    expert_in = constrain(expert_in, ("expert", None, "act_embed"))
    gate = jnp.einsum("ech,ehm->ecm", expert_in, p["wi_gate"].astype(y.dtype))
    up = jnp.einsum("ech,ehm->ecm", expert_in, p["wi_up"].astype(y.dtype))
    act = jax.nn.silu(gate) * up
    act = constrain(act, ("expert", None, "mlp"))
    out_e = jnp.einsum("ecm,emh->ech", act, p["wo_mlp"].astype(y.dtype))
    yk = jnp.einsum("pec,ech->ph", combine.astype(y.dtype), out_e)  # [kT,h]
    out = yk.reshape(k, t, h).sum(0).reshape(b, s, h)
    return out


def _grouped_matmul(rows, weights, group_sizes, layer=None):
    """rows [R,h] in E adjoining groups times weights [E,h,m] -> [R,m],
    float32 accumulation and result; `weights` a pair (gate, up) -> the gated
    unit silu(rows x gate) * (rows x up) in the rows' dtype, from ONE call.

    With `layer` (a traced index) `weights` is the whole stack [L,E,h,m] and
    is read in place, INDEXED by `layer`: a scanned layer loop must not slice
    its layer out first, because the grouped matmul is a custom call either
    way, so XLA cannot fuse the slice into it and copies the layer's experts
    (226-805 MB a layer: 102-2,457 us a call beside the call's own 106-887
    at the four serve cells' decode shapes on the v5e, PERF.md PR 41).

    What runs where (`ops/grouped_matmul.py::takes`, which decides by the
    shapes it is given): on a TPU a call of at most one tile of rows a group
    (a decode step's 32-320 rows, a short prompt's) is a Pallas kernel that
    keeps the rows in fast memory and streams each reached expert's matrix
    from `weights[layer, expert]` in contiguous blocks of about 1 MB; a call
    of more rows than that (a long prompt's 16,384 over 64 groups, a held
    share's capped 1,024-8,192 over 16) is a second kernel that holds a
    reached group's matrices whole and passes the group's rows by them a
    tile of 256 at a time, a tile multiplied for ONE group (PR 63: the
    sparse document cell's layer 1.8 ms where `lax.ragged_dot` took 3.1-4.6,
    its values to the last bit). `layer` reaches both as a prefetched
    scalar, so their metadata is a layer's E groups. Off a TPU, and for
    widths that fill no lane, it is `lax.ragged_dot` over the stack VIEWED
    as L*E groups of which only that layer's hold rows (the compiler's
    kernel spends 18-22 us a call on metadata over 416-1,024 groups where
    16-128 cost 1-6)."""
    return grouped_matmul(rows, weights, group_sizes, layer)


def held_rows_cap(cfg: TransformerConfig, assignments: int):
    """How many of a call's `assignments` (T x k, static) `moe_dropless`
    gathers rows for where only a thin share of the router's outputs is held
    here, or None: all of them. The static T x k layout gathers, multiplies
    as no group's and unsorts a row for EVERY assignment, held or not: 48
    rows for each that meets a weight at 16 of 768 held, 16 at 16 of 256.
    The sorted order has the held groups' rows FIRST, so the first `cap`
    rows hold them all unless more than `cap` assignments are held:
    `HELD_ROWS_ROOM` (four) times the even share, 64 at least, in whole
    tiles; a call that holds more (`lax.cond` on the count) takes the whole
    layout, so nothing is ever dropped. ONE rule, read off the held share
    and the call: the cap is taken where it leaves at most one row in
    `HELD_ROWS_ROOM` of the layout (a share of a sixteenth or thinner: the
    `lax.cond` and the rows' sum by token have to be paid for) and, where
    the 64 rows decide, halves the rows at least. An eighth, a half and a
    configuration that holds every expert keep the whole layout and the
    programs they had (the readings: PERF.md section 6, PRs 44 and 59).
    Pad rows choose like any other and choose ALIKE: a piece of mostly
    padding whose common choice holds three experts here passes its cap and
    takes the whole layout (`layout_counted` counts such calls)."""
    if cfg.experts_held is None:
        return None
    count, outputs = cfg.experts_held[1], cfg.num_experts + cfg.zero_experts
    if count * HELD_ROWS_ROOM ** 2 > outputs:
        return None
    cap = max(64, HELD_ROWS_ROOM * -(-assignments * count // outputs))
    cap = -(-cap // 16) * 16
    return cap if 2 * cap <= assignments else None


def rows_gathered(cfg: TransformerConfig, experts):
    """How many rows `moe_dropless` gathers for a call whose rows chose
    `experts` [T, k] (int32 scalar): `held_rows_cap`'s, or all T x k where it
    has none or more assignments than that are held."""
    n = experts.size
    cap = held_rows_cap(cfg, n)
    if cap is None:
        return jnp.int32(n)
    first, count = cfg.experts_held
    held = ((experts >= first) & (experts < first + count)).sum()
    return jnp.where(held <= cap, cap, n).astype(jnp.int32)


def layout_counted(cfg: TransformerConfig, experts):
    """What a capped call whose rows chose `experts` [T, k] has to count,
    int32 [2]: the rows `moe_dropless` gathers for it (`rows_gathered`), and
    1 if it held more than its cap and took the whole layout (else 0). None
    where `held_rows_cap` gives the call no cap: its layout is static, T x k
    rows, and the program counts nothing for it (the engine knows the rows
    its programs run: `ContinuousBatcher._count_experts`)."""
    if held_rows_cap(cfg, experts.size) is None:
        return None
    gathered = rows_gathered(cfg, experts)
    return jnp.stack([gathered, gathered == experts.size]).astype(jnp.int32)


@jax.named_scope("moe_router")
def moe_router(cfg: TransformerConfig, x, p):
    """x [T,h] -> (weights [T,k] float32, experts [T,k] int32): the top-k of
    a float32 softmax over all experts, renormalised to sum to one only if
    `cfg.norm_topk_prob`, then times `cfg.routed_scale`."""
    logits = jnp.einsum("th,he->te", x, p["router"].astype(x.dtype),
                        preferred_element_type=jnp.float32)
    weights, experts = lax.top_k(jax.nn.softmax(logits, axis=-1),
                                 cfg.experts_per_token)
    if cfg.norm_topk_prob:
        weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    if cfg.routed_scale != 1.0:
        weights = weights * cfg.routed_scale
    return weights, experts


def moe_dropless(cfg: TransformerConfig, y, p, row_mask=None, layer=None,
                 routing=None):
    """Dropless top-k token-choice experts: y [B,S,h] -> (out [B,S,h],
    load [E] int32).

    Every token runs its k experts whatever their load (OLMoE, Megablocks):
    the T*k assignments are sorted by expert, their rows gathered, and the
    gate, up and down projections are grouped matmuls over the E groups of
    that sorted order, gate and up in one call and down in a second
    (`_grouped_matmul`: on a TPU one of two kernels, cut to a decode step's
    few rows a group or to a long prompt's many, `lax.ragged_dot` off a TPU;
    with
    `cfg.expert_act` "relu2" an expert is two matrices, up and down, and
    ReLU squared between them); then the rows go
    back to token order and are summed with the router's weights. Shapes are
    static: T*k rows always, only `group_sizes` is data. No [T*k, E, C]
    dispatch tensor exists and nothing is dropped. Differentiable.

    Routing is per token, so the rows of a padded prompt or of a free cache
    slot are routed and computed like any other and cannot change a real
    token's result; `row_mask` [B,S] (True = a real row) keeps them out of
    `load`, the assignments each expert received from real rows.

    y's width is the experts' input and output width, whatever the stream's
    (a family whose experts work in a latent hands the latent in and routes
    on the stream: `routing`). `p` is one layer's parameters; with `layer` its
    expert weights are
    the whole stacks [L,E,...] and `layer` the index to use
    (`_grouped_matmul` says why a layer scan wants that). `routing` is
    `moe_router`'s pair from a router of another kind (`zaya.router`).

    `cfg.experts_held` = (first, count) says which of the `num_experts` have
    their weights here (`p`'s expert stacks are [count, ...]): a layer that two or
    more chips share, each holding its experts. The router scores and
    chooses over all of them; the assignments to absent experts sort behind
    the held groups, are in no group, and their rows are set to zero before
    the weighted sum, so what they add is exactly nothing. T*k rows stay
    static. `load` still counts every expert: held / all is the share of
    the routed work that is done here.

    `cfg.zero_experts` router outputs behind the `num_experts` routed ones
    are a THIRD class, zero-compute experts that return their input: a choice
    among them (an index >= `num_experts`) multiplies nothing. Its assignment
    sorts behind the held groups with the absent ones' and is in no group;
    what the class adds is `(the sum of a token's weights on it) x the
    token's input`, one multiply a token under the scope `moe.zero`, done
    HERE whatever `experts_held` says (a token's home chip does it; nothing
    is exchanged for it). A token so does the work of 0 to k real experts.
    `load` is then [num_experts + zero_experts], the zero-compute outputs
    counted apart behind the routed ones.

    Where the held share is thin (`held_rows_cap`: a sixteenth of the
    router's outputs or less, by one rule read off the share and the call's
    assignments) the rows of the first `cap` sorted assignments alone are
    gathered and multiplied, and added to their tokens; a call with more
    held assignments than that takes the whole T*k layout: the result is the
    same either way, the same bfloat16 products and float32 weighted sum,
    absent and padded rows adding exactly nothing. `layout_counted` says per
    call what was gathered and whether the cap gave way.
    """
    b, s, h = y.shape
    e, k, zero = cfg.num_experts, cfg.experts_per_token, cfg.zero_experts
    t = b * s
    x = y.reshape(t, h)
    weights, experts = routing or moe_router(cfg, x, p)
    flat = experts.reshape(t * k)  # assignment a = token * k + choice
    held = cfg.experts_held
    group, groups = flat, e  # the group of weights an assignment multiplies
    if held is not None or zero:
        first, groups = held or (0, e)
        here = (flat >= first) & (flat < first + groups)
        group = jnp.where(here, flat - first, groups)  # the rest: behind all
    with jax.named_scope("moe_experts"):
        order = jnp.argsort(group, stable=True)  # sorted row -> assignment
        group_sizes = jnp.bincount(group, length=groups).astype(jnp.int32)

        def through_experts(order):
            """The rows of the sorted assignments `order` through their
            groups' gate, up and down matrices: [len(order), h] float32,
            zeros where an assignment is in no group."""
            rows = x[order // k]  # one expert's rows adjoin
            if cfg.expert_act == "relu2":  # two matrices, no gate
                up = _grouped_matmul(rows, p["wi_up"].astype(x.dtype),
                                     group_sizes, layer)
                act = jnp.square(jax.nn.relu(up)).astype(x.dtype)
            else:
                act = _grouped_matmul(
                    rows, (p["wi_gate"].astype(x.dtype),
                           p["wi_up"].astype(x.dtype)), group_sizes, layer)
            down = _grouped_matmul(act, p["wo_mlp"].astype(x.dtype),
                                   group_sizes, layer)
            if held is not None or zero:  # no group: whatever was left
                down = jnp.where(here[order][:, None], down, 0.0)
            return down

        def all_rows():
            # back to assignment order (a gather by the inverse
            # permutation), then the weighted sum over each token's k
            # experts, in float32
            inverse = jnp.zeros_like(order).at[order].set(jnp.arange(t * k))
            return (through_experts(order)[inverse].reshape(t, k, h)
                    * weights[..., None]).sum(1)

        def first_rows(cap):
            # the groups' rows lie first in the sorted order: those alone,
            # each added to its token with its weight
            first = order[:cap]
            return jnp.zeros((t, h), jnp.float32).at[first // k].add(
                through_experts(first)
                * weights.reshape(t * k)[first][:, None])

        cap = held_rows_cap(cfg, t * k)
        out = all_rows() if cap is None else lax.cond(
            group_sizes.sum() <= cap, functools.partial(first_rows, cap),
            all_rows)
    if zero:
        with jax.named_scope("moe.zero"):
            on_zero = jnp.where(experts >= e, weights, 0.0).sum(-1)
            out = out + on_zero[:, None] * x.astype(jnp.float32)
    if row_mask is None:
        load = group_sizes if held is None and not zero else jnp.bincount(
            flat, length=e + zero).astype(jnp.int32)
    else:
        load = jnp.bincount(
            flat, weights=jnp.repeat(row_mask.reshape(t).astype(jnp.int32), k),
            length=e + zero)
    return out.astype(y.dtype).reshape(b, s, h), load


def _qk_norm(cfg: TransformerConfig, q, k, p):
    """OLMoE's q_norm / k_norm: RMSNorm over the whole projection (all
    heads together), before RoPE."""
    b, s = q.shape[:2]
    q = _rms_norm(q.reshape(b, s, -1), p["ln_q"], cfg.norm_eps).reshape(q.shape)
    k = _rms_norm(k.reshape(b, s, -1), p["ln_k"], cfg.norm_eps).reshape(k.shape)
    return q, k


# The residual stream between a block's sublayers, by the rule table's names.
STREAM = ("batch", "act_rows", "act_embed")


def _ring_axes():
    """The mesh axes a block's rings are manual over, or None where the
    context's mesh has no `tensor` axis larger than one that is still the
    partitioner's: ("tensor",), or ("sequence", "tensor") where ring
    attention's axis cuts the rows too and no outer region has bound it."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return None
    free = lambda a: mesh.shape.get(a, 1) > 1 and a not in mesh.manual_axes
    if not free("tensor"):
        return None
    return tuple(a for a in ("sequence", "tensor") if free(a))


def tensor_ring(cfg: TransformerConfig, seq_len: int) -> Optional[Dict[str, int]]:
    """What a block of a forward over `seq_len` positions traces under the
    context's mesh: how many of its groups of projections are rings
    (`_block`: two that gather rows, two that sum and scatter them; a
    sparse MLP is the partitioner's), of how many turns, over how many rows
    a turn; None where `_block` traces whole products. Raises where the
    rows cannot be cut."""
    if _ring_axes() is None:
        return None
    mesh = jax.sharding.get_abstract_mesh()
    turns = mesh.shape["tensor"]
    cut = turns * mesh.shape.get("sequence", 1)
    if seq_len % cut:
        raise ValueError(
            f"a sequence of {seq_len} positions cannot be cut over this "
            f"mesh: between a block's sublayers its rows lie over `sequence` "
            f"x `tensor` = {cut} chips (the rule table's \"act_rows\"), "
            f"which does not divide it")
    return {"rings": 2 if cfg.num_experts else 4, "turns": turns,
            "rows": seq_len // cut}


def _qkv(y, p, lo, scale, xa):
    """Rows y [B,R,h] through `wq`, `wk`, `wv` and the adapters of `wq` and
    `wv`: q, k, v [B,R,heads here,D]. ``xa(name)`` is the rows' product with
    the adapter matrix `name`."""
    q = jnp.einsum("bsh,hnd->bsnd", y, p["wq"].astype(y.dtype))
    k = jnp.einsum("bsh,hnd->bsnd", y, p["wk"].astype(y.dtype))
    v = jnp.einsum("bsh,hnd->bsnd", y, p["wv"].astype(y.dtype))
    if lo is not None:
        q = q + _lora_out(xa("wq_a"), lo["wq_b"], scale).reshape(q.shape)
        v = v + _lora_out(xa("wv_a"), lo["wv_b"], scale).reshape(v.shape)
    return q, k, v


def _gated(y, p, lo, scale, xa):
    """Rows y [B,R,h] through the gate (with its adapter) and up projections
    and the activation: [B,R,mlp columns here]."""
    gate = jnp.einsum("bsh,hm->bsm", y, p["wi_gate"].astype(y.dtype))
    up = jnp.einsum("bsh,hm->bsm", y, p["wi_up"].astype(y.dtype))
    if lo is not None:
        gate = gate + _lora_out(xa("wi_a"), lo["wi_b"], scale)
    gate = checkpoint_name(gate, "mlp_gate")
    up = checkpoint_name(up, "mlp_up")
    act = jax.nn.silu(gate) * up
    return constrain(act, ("batch", "seq", "mlp"))


# The dimension of a block's weight that `tensor` cuts, as a ring's region
# takes it: the columns of a projection into a sublayer and of an adapter's
# `b`, the rows of a projection out of it.
_COLUMNS, _ROWS = P(None, "tensor"), P("tensor")
_RING_CUT = {"wq": _COLUMNS, "wk": _COLUMNS, "wv": _COLUMNS, "wo": _ROWS,
             "wi_gate": _COLUMNS, "wi_up": _COLUMNS, "wo_mlp": _ROWS,
             "wq_b": _COLUMNS, "wv_b": _COLUMNS, "wi_b": _COLUMNS}


def _ringed(axes, fn, arrays, arrays_spec, out_specs, p, lo, names):
    """``fn(index, arrays, p, lo)`` in a `shard_map` region manual over
    `axes` ALONE, where the rings of `parallel/ring.py` run: the other axes
    stay the partitioner's, so the frozen weights' gathers over `fsdp` stay
    its asynchronous ones, and no Pallas call is inside. `arrays` enter cut
    by `arrays_spec`, `p` and `lo` cut down to `names` and over `tensor` as
    `_RING_CUT` says, and `index` is the chip's place on `tensor`."""
    def some(tree):
        return None if tree is None else {n: tree[n] for n in names if n in tree}

    weights = (some(p), some(lo))
    turns = jax.sharding.get_abstract_mesh().shape["tensor"]
    return jax.shard_map(
        lambda index, arrays, weights: fn(index[0], arrays, *weights),
        in_specs=(P("tensor"), jax.tree.map(lambda _: arrays_spec, arrays),
                  tuple(None if w is None else {n: _RING_CUT[n] for n in w}
                        for w in weights)),
        out_specs=out_specs, axis_names=set(axes), check_vma=False,
    )(ring.chip_indices(turns), arrays, weights)


def _block(cfg: TransformerConfig, x, layer_params, lora_params, positions,
           attn_fn):
    """One decoder block. x [B,S,H_emb] in compute dtype, its rows cut as
    `STREAM` says.

    Under a mesh whose `tensor` axis is larger than one (`_ring_axes`) the
    three groups of projections that meet the stream run as rings of that
    many turns (`parallel/ring.py`), each group in a `shard_map` region
    manual over the rows' axes alone: `wq`/`wk`/`wv` and gate/up multiply a
    chip's rows while they travel on to the next chip, `wo` and the down
    projection send a partial sum on while they multiply the next chip's
    rows. q, k, v leave split by heads for the rotation and the kernels
    (`attn_fn` keeps its own region); what the stream adds arrives with this
    chip's rows whole. Everywhere else each is one product over all rows."""
    p, lo = layer_params, lora_params
    scale = cfg.lora_alpha / cfg.lora_rank if cfg.lora_rank else 0.0
    axes = _ring_axes()
    if axes is not None:
        rows = P(None, axes)  # [B, S, ...]: a chip's rows of the sequence
        # [B, S, heads, D]: heads over `tensor`, S as far as `sequence` cuts it
        by_heads = P(None, "sequence" if "sequence" in axes else None, "tensor")

    def xa_of(y):
        return lambda name: _lora_xa(y, lo[name])

    def carried(y, *names):
        # what a gathering ring carries round: a chip's rows and their
        # products with the adapters' `a`
        return {"y": y, "xa": {} if lo is None else {
            n: _lora_xa(y, lo[n]) for n in names}}

    y = _rms_norm(x, p["ln_attn"], cfg.norm_eps)
    if axes is None:
        q, k, v = _qkv(y, p, lo, scale, xa_of(y))
    else:
        def qkv(index, mine, p, lo):
            by_turn = [_qkv(held["y"], p, lo, scale, held["xa"].__getitem__)
                       for held in ring.gather_turns(mine, "tensor")]
            return tuple(ring.in_sequence_order(t, "tensor", index)
                         for t in zip(*by_turn))

        q, k, v = _ringed(axes, qkv, carried(y, "wq_a", "wv_a"), rows,
                          (by_heads,) * 3, p, lo,
                          ("wq", "wk", "wv", "wq_b", "wv_b"))
    if cfg.qk_norm:
        q, k = _qk_norm(cfg, q, k, p)
    q = _rope(q, positions, cfg.rope_theta)
    k = _rope(k, positions, cfg.rope_theta)
    q = constrain(q, ("batch", "seq", "heads", None))
    q, k, v = (checkpoint_name(t, name)
               for t, name in zip((q, k, v), REMAT_ATTENTION))
    attn = attn_fn(q, k, v)

    def through_wo(attn, p):
        return jnp.einsum("bsnd,ndh->bsh", attn, p["wo"].astype(attn.dtype))

    if axes is None:
        attn = through_wo(attn, p)
    else:
        attn = _ringed(
            axes, lambda index, attn, p, _: ring.scatter_sum(
                lambda turn: through_wo(
                    ring.rows_of_turn(attn, turn, "tensor", index), p),
                "tensor"),
            attn, by_heads, rows, p, None, ("wo",))
    if cfg.sandwich:
        attn = _rms_norm(attn, p["ln_attn_post"], cfg.norm_eps)
    x = checkpoint_name(x + constrain(attn, STREAM), "resid_attn")

    y = _rms_norm(x, p["ln_mlp"], cfg.norm_eps)

    def down(act, p):
        return jnp.einsum("bsm,mh->bsh", act, p["wo_mlp"].astype(act.dtype))

    if cfg.num_experts:
        out = _moe_mlp(cfg, y, p)
    else:
        with jax.named_scope("mlp"):
            if axes is None:
                out = down(_gated(y, p, lo, scale, xa_of(y)), p)
            else:
                def mlp(index, mine, p, lo):
                    acts = [_gated(held["y"], p, lo, scale,
                                   held["xa"].__getitem__)
                            for held in ring.gather_turns(mine, "tensor")]
                    return ring.scatter_sum(
                        lambda turn: down(acts[turn], p), "tensor")

                out = _ringed(axes, mlp, carried(y, "wi_a"), rows, rows, p, lo,
                              ("wi_gate", "wi_up", "wo_mlp", "wi_b"))
    if cfg.sandwich:
        out = _rms_norm(out, p["ln_mlp_post"], cfg.norm_eps)
    return x + constrain(out, STREAM)


def pass_end(cfg: TransformerConfig, params: Params, x):
    """What a pass of a looped model adds behind its layers: the model's
    final norm, whose output is the next pass's input (and the last pass's
    the head's), and the exit gate on it. Returns (the normed stream, the
    gate lam [B, S] in float32)."""
    with jax.named_scope("loop.pass_end"):
        x = _rms_norm(x, params["ln_f"], cfg.norm_eps)
        lam = jax.nn.sigmoid(
            jnp.einsum("bsh,h->bs", x.astype(jnp.float32),
                       params["exit_w"].astype(jnp.float32))
            + params["exit_b"].astype(jnp.float32)[0])
    return x, lam


def exit_pdf(lam):
    """The gates lam [passes, B, S] as the distribution over the pass a
    token leaves at: p_t = lam_t prod_{j<t} (1 - lam_j), the last pass what
    is left (its own gate is not read)."""
    stay = jnp.cumprod(1.0 - lam[:-1], axis=0)  # still in the loop behind t
    before = jnp.concatenate([jnp.ones_like(lam[:1]), stay[:-1]])
    return jnp.concatenate([lam[:-1] * before, stay[-1:]])


def _default_attn(cfg: TransformerConfig):
    def attn(q, k, v):
        k, v = gqa_expand(k, v, cfg.heads)
        return flash_attention(q, k, v, causal=True)
    return attn


def forward(cfg: TransformerConfig, params: Params, tokens: jax.Array,
            positions: Optional[jax.Array] = None,
            attn_fn=None, mesh=None,
            num_microbatches: Optional[int] = None,
            remat_kept: Tuple[str, ...] = REMAT_LADDER[0]) -> jax.Array:
    """tokens [B,S] int32 → logits [B,S,V] (compute dtype).

    ``attn_fn(q,k,v)->o`` overrides attention — ring_attention for
    sequence parallelism is passed in by the train-step builder.
    ``mesh`` with a "stage" axis > 1 switches the layer stack to
    pipeline parallelism (ops/pipeline.py) with ``num_microbatches``.
    ``remat_kept``: with ``cfg.remat``, the names each block's checkpoint
    keeps for the backward, a rung of ``REMAT_LADDER``.
    """
    if families.of(cfg).__name__ != __name__:
        raise ValueError(
            f"a layer pattern {cfg.layer_kinds!r} (layers of several kinds, "
            f"parameters stacked by kind), attention {cfg.attention!r} and "
            f"router {cfg.router!r} run in the cached forward alone "
            "(decoding.forward_cached): the training forward scans the one "
            "block and has no such sublayer")
    if positions is None:
        positions = jnp.arange(tokens.shape[1])
    attn_fn = attn_fn or _default_attn(cfg)
    tensor_ring(cfg, tokens.shape[1])  # rows the mesh cannot cut: raises
    x = params["embed"].astype(cfg.dtype)[tokens]
    x = constrain(x, STREAM)

    blocks, lora = params["blocks"], params.get("lora")

    def body_at(pos):
        def body(x, layer):
            lp = layer["p"]
            lo = layer.get("l")
            out = _block(cfg, x, lp, lo, pos, attn_fn)
            return out, None
        return body

    body = body_at(positions)

    layer_tree = {"p": blocks}
    if lora is not None:
        layer_tree["l"] = lora
    def _remat(fn):
        # A checkpoint a block: the backward has the block's input and the
        # results named in `remat_kept`, and recomputes the rest from them.
        # The flash kernel's `out` and `lse` are named inside its custom_vjp
        # (`ops/attention.py::_flash_fwd`), so the policy keeps the residuals
        # themselves and the forward kernel runs once.
        if not cfg.remat:
            return fn
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.save_only_these_names(*remat_kept))

    n_stage = mesh.shape.get("stage", 1) if mesh is not None else 1
    if n_stage > 1:
        from ray_tpu.ops.pipeline import pipelined_layers

        n_seq = mesh.shape.get("sequence", 1)
        seq_axis = "sequence" if n_seq > 1 else None

        def apply_stage(layers_local, h, pos_local):
            h, _ = lax.scan(_remat(body_at(pos_local)), h, layers_local)
            return h

        def run_layers(x):
            return pipelined_layers(
                mesh, apply_stage, layer_tree, x, positions,
                num_microbatches or 2 * n_stage,
                seq_axis=seq_axis,
            )
    else:
        def run_layers(x):
            return lax.scan(_remat(body), x, layer_tree)[0]

    if cfg.loop_steps > 1:
        # the same stacked layers every pass, the final norm between them
        x, _ = lax.scan(
            lambda x, _: (pass_end(cfg, params, run_layers(x))[0], None), x,
            None, length=cfg.loop_steps)
    else:
        x = _rms_norm(run_layers(x), params["ln_f"], cfg.norm_eps)
    unembed = params.get("unembed")
    if unembed is None:
        unembed = params["embed"].T
    logits = jnp.einsum("bsh,hv->bsv", x, unembed.astype(x.dtype))
    return constrain(logits, ("batch", "seq", "vocab"))


def loss_fn(cfg: TransformerConfig, params: Params, batch: Dict[str, jax.Array],
            attn_fn=None, mesh=None,
            num_microbatches: Optional[int] = None,
            remat_kept: Tuple[str, ...] = REMAT_LADDER[0]) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token cross-entropy. batch: tokens [B,S], optional loss_mask [B,S].
    Returns (loss, metrics)."""
    if cfg.loop_steps > 1:
        raise ValueError(
            f"loop_steps {cfg.loop_steps}: a looped model is trained under a "
            "loss over its exit distribution (every pass's cross-entropy "
            "weighted by `exit_pdf`, and an entropy term), which loss_fn does "
            "not have: it would train the last pass alone")
    tokens = batch["tokens"]
    # Forward over the FULL sequence (sequence-parallel shards must keep
    # S divisible by the mesh axis); shift at the logits instead.
    logits = forward(cfg, params, tokens, attn_fn=attn_fn, mesh=mesh,
                     num_microbatches=num_microbatches,
                     remat_kept=remat_kept)[:, :-1]
    targets = tokens[:, 1:]
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    tgt_logit = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - tgt_logit
    mask = batch.get("loss_mask")
    if mask is not None:
        mask = mask[:, 1:].astype(jnp.float32)
        denom = jnp.maximum(mask.sum(), 1.0)
        loss = (nll * mask).sum() / denom
    else:
        denom = jnp.asarray(nll.size, jnp.float32)
        loss = nll.mean()
    acc = (logits.argmax(-1) == targets).astype(jnp.float32)
    if mask is not None:
        acc = (acc * mask).sum() / denom
    else:
        acc = acc.mean()
    return loss, {"loss": loss, "accuracy": acc, "tokens": denom}


def trainable_mask(cfg: TransformerConfig, params: Params) -> Params:
    """True where a param trains: everything for dense, only adapters for
    LoRA (the reference's LoRA target trains adapters only)."""
    if not cfg.lora_rank:
        return jax.tree.map(lambda _: True, params)
    return jax.tree_util.tree_map_with_path(
        lambda path, _: any(getattr(k, "key", None) == "lora" for k in path),
        params,
    )
