"""Operations and bytes the algorithm REQUIRES, computed from shapes.

Kept with the benchmark so that no PR that claims a gain can change the
yardstick. Everything takes the published ``config.json`` keys. Nothing here
counts recomputation (remat, flash-attention's backward recompute of the
scores) or gradients of frozen weights: a program that does either spends
real time on it and its utilization on required operations falls, which is
the point.

Conventions: one multiply-add is 2 operations; causal attention over S
positions needs the lower triangle, S(S+1)/2 query-key pairs.
"""

from __future__ import annotations


def block_matmul_params(c: dict) -> int:
    """Weights of one decoder block that take part in a matmul."""
    h, m = c["hidden_size"], c["intermediate_size"]
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    return h * q + 2 * h * kv + q * h + 3 * h * m


def matmul_params(c: dict) -> int:
    """Block matmuls over the depth, plus the output head. The embedding is
    a lookup, not a matmul; norms are vectors."""
    return (c["num_hidden_layers"] * block_matmul_params(c)
            + c["hidden_size"] * c["vocab_size"])


def total_params(c: dict) -> int:
    h = c["hidden_size"]
    emb = c["vocab_size"] * h * (1 if c.get("tie_word_embeddings") else 2)
    return (c["num_hidden_layers"] * (block_matmul_params(c) + 2 * h)
            + emb + h)


def lora_params(c: dict, rank: int) -> int:
    """Adapters as the program places them: wq, wv and the MLP gate."""
    if not rank:
        return 0
    h, m = c["hidden_size"], c["intermediate_size"]
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    per_layer = (h * rank + rank * q) + (h * rank + rank * kv) \
        + (h * rank + rank * m)
    return c["num_hidden_layers"] * per_layer


def attention_forward_flops(c: dict, seq: int) -> float:
    """Causal self-attention over one sequence of ``seq`` tokens, all layers:
    QK^T and PV, 2 * head_dim operations each per query-key pair and head."""
    pairs = seq * (seq + 1) / 2
    return (c["num_hidden_layers"] * c["num_attention_heads"]
            * 2 * (2 * c["head_dim"]) * pairs)


def train_flops_per_token(c: dict, seq: int, lora_rank: int) -> float:
    """Required operations per trained token.

    Dense training: forward 2N, dX 2N, dW 2N. LoRA: the base weights are
    frozen, so their dW is not required (forward 2N + dX 2N), and the
    adapters pay forward, dX and dW (6 per adapter weight). Attention:
    forward, and twice that backward (dV, dP, dQ, dK).
    """
    n = matmul_params(c)
    attn = 3 * attention_forward_flops(c, seq) / seq
    if lora_rank:
        return 4 * n + 6 * lora_params(c, lora_rank) + attn
    return 6 * n + attn


def flash_kernel_cost(batch: int, seq: int, c: dict, chips_sharing: int = 1):
    """Operations and HBM bytes of the three Pallas attention kernels for
    one train step on ONE device, from the shapes they are called with:
    q, k, v, o of [batch, seq, heads, head_dim] in bf16 (the program expands
    K and V to all query heads before the call, so the kernel reads them at
    that size), ``chips_sharing`` devices splitting batch x heads.

    forward: 2 matmuls over the causal pairs; reads q, k, v, writes o.
    backward (dQ pass + dK/dV pass): 4 required matmuls (the recompute of
    the scores is not counted); each pass reads q, k, v, dO; writes dQ, or
    dK and dV.
    """
    heads, d, layers = (c["num_attention_heads"], c["head_dim"],
                        c["num_hidden_layers"])
    pairs = seq * (seq + 1) / 2
    per_matmul = batch * heads * 2 * d * pairs * layers / chips_sharing
    tensor = batch * seq * heads * d * 2 * layers / chips_sharing  # bytes
    return {"forward": {"flops": 2 * per_matmul, "bytes": 4 * tensor},
            "backward": {"flops": 4 * per_matmul, "bytes": (4 + 1) * tensor
                         + (4 + 2) * tensor}}
