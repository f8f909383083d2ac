"""What the compressed-latent attention of a configuration requires of the
chip in one decode step. The yardstick of ``cca_attention_roofline``.

Required work counts the published mathematics only: a decode step's query
attends to the positions its sequence HAS, so the k and v rows of those
positions are read once, in every layer, for every active sequence. The
program reads every slot's ``max_len`` rows under a mask; rows beyond a
sequence's length and free slots are not required work, so a roofline share
from these numbers cannot pass 100%.
"""

from __future__ import annotations

from benchmarks import peaks, readers, scope_ops

BYTES = 2  # the cache is bfloat16


def decode_attention_cost(config: dict, rows: float) -> dict:
    """Operations and bytes of ALL layers' attention for one decode step
    whose active sequences hold ``rows`` positions in all (the new token's
    own among them): k and v of ``num_key_value_heads x head_dim`` each, read
    once a layer; a dot product and a weighted sum of ``head_dim`` terms per
    query head and position."""
    layers, d = config["num_hidden_layers"], config["head_dim"]
    heads, kv_heads = config["num_attention_heads"], config["num_key_value_heads"]
    return {"flops": layers * rows * heads * d * 2 * 2,
            "bytes": layers * rows * 2 * kv_heads * d * BYTES}


def attention_roofline(ctx, rows):
    """The least time the chip could take for one traced decode step's
    attention (memory bound at these shapes) over the time its operations
    under ``cca.attend`` took."""
    took_ms = scope_ops.ms_per_run(ctx, readers.DECODE_PROGRAM,
                                   ("cca.attend",))
    if ctx["cell"]["toy"] or not took_ms or not rows:
        return None
    cost = decode_attention_cost(ctx["cell"]["config"], rows)
    least, _ = peaks.roofline_seconds(cost["flops"], cost["bytes"],
                                      ctx["device"]["kind"])
    return 100.0 * least * 1e3 / took_ms
