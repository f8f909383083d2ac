"""What the attention of a configuration whose keys are wider than its values,
with a KV-head count by kind of layer, requires of the chip. The yardstick of
``mimo_window_attention_roofline``, ``mimo_full_attention_roofline`` and
``mimo_prefill_attention_roofline``.

Required work counts the published mathematics only, whatever implements it.
A decode step's attention of one kind is memory bound: the K and V rows the
step's active sequences HOLD in that kind of layer, read once, a key of
``head_dim`` (192) and a value of ``v_head_dim`` (128) a KV head of that kind:
every position in a full layer, at most ``sliding_window`` in a window layer.
The lanes a key is padded to where it lies (256), rows of whole blocks beyond
a sequence's length, free slots and the sink's 64 scalars are not required
work. The layers' projection weights are NOT counted although the scope that
is timed runs the projections (``laguna_cost`` says why), so a share from
these numbers cannot pass 100%.
A prefill's causal attention from position 0 over S positions is compute
bound: the causal half of the two products, ``S (S + 1) / 2`` pairs a query
head, ``head_dim`` terms a logit and ``v_head_dim`` a weighted sum; q, k, v
and o cross HBM once (k and v by their own KV heads).
"""

from __future__ import annotations

from benchmarks import peaks, program_spans, readers, scope_ops

BYTES = 2  # weights, activations and cache are bfloat16
SCOPE = {"full": "attn.full", "window": "attn.window"}


def layers_of(config: dict, kind: str) -> int:
    """How many of the layers that run are of ``kind``."""
    want = int(kind == "window")
    return sum(int(bool(k)) == want for k in
               config["hybrid_layer_pattern"][:config["num_hidden_layers"]])


def kv_heads(config: dict, kind: str) -> int:
    return config["swa_num_key_value_heads" if kind == "window"
                  else "num_key_value_heads"]


def decode_attention_cost(config: dict, kind: str, rows: float) -> dict:
    """Operations and bytes of ALL of ``kind``'s layers' attention for one
    decode step whose active sequences hold ``rows`` positions in such a
    layer in all (the new token's own among them): a key of ``head_dim`` and
    a value of ``v_head_dim`` a KV head of that kind, read once a layer; a
    dot product of ``head_dim`` terms and a weighted sum of ``v_head_dim``
    per query head and position."""
    dk, dv, n = config["head_dim"], config["v_head_dim"], layers_of(
        config, kind)
    return {"flops": n * rows * config["num_attention_heads"] * (dk + dv) * 2,
            "bytes": n * rows * kv_heads(config, kind) * (dk + dv) * BYTES}


def prefill_attention_cost(config: dict, seq: int, kind: str = "full") -> dict:
    """Operations and bytes of ALL of ``kind``'s layers' causal attention of
    one prompt of ``seq`` positions from position 0: ``seq (seq + 1) / 2``
    pairs a query head, each ``head_dim + v_head_dim`` multiply-adds; q and o
    of every query head and k and v of ``kind``'s KV heads once."""
    dk, dv = config["head_dim"], config["v_head_dim"]
    heads, kv, n = config["num_attention_heads"], kv_heads(config, kind), \
        layers_of(config, kind)
    pairs = seq * (seq + 1) / 2
    return {"flops": n * heads * pairs * (dk + dv) * 2,
            "bytes": n * seq * (heads + kv) * (dk + dv) * BYTES}


def _share(ctx, cost, took_ms):
    if ctx["cell"]["toy"] or not took_ms:
        return None
    least, _ = peaks.roofline_seconds(cost["flops"], cost["bytes"],
                                      ctx["device"]["kind"])
    return 100.0 * least * 1e3 / took_ms


def decode_attention_roofline(ctx, kind: str):
    """The least time the chip could take for one traced decode step's
    attention of ``kind`` over the time the operations under its scope took.
    The rows are the traced steps' (`engine.decode_dispatch` spans' median
    ``rows`` for full layers, ``window_rows`` for window layers)."""
    rows = program_spans.read(
        ctx, program_spans.stat_median, program_spans.DECODE_DISPATCH,
        "rows" if kind == "full" else "window_rows")
    if not rows:
        return None
    return _share(ctx, decode_attention_cost(ctx["cell"]["config"], kind, rows),
                  scope_ops.ms_per_run(ctx, readers.DECODE_PROGRAM,
                                       (SCOPE[kind],)))


def prefill_attention_roofline(ctx):
    """The least time for the full layers' causal attention of the ONE
    captured prefill (its bucket's positions: pad positions are computed as
    any other) over the time its flash forward kernels took in that capture;
    None where the capture has none (another program, the dense spelling)."""
    captured = ctx["counters"].get("mimo_prefill") or {}
    if not captured.get("flash_ms") or not captured.get("bucket"):
        return None
    return _share(ctx, prefill_attention_cost(
        ctx["cell"]["config"], captured["bucket"]), captured["flash_ms"])
