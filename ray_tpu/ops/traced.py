"""Which implementation a choice made at trace time fell on.

An op that picks a kernel or its XLA spelling by what it can see of a call
books the pick under the choice's name (`book`); whoever traces a jitted
program around it collects every choice's picks in ONE `with booked() as
seen:`, so that a program that fell back says so in one look
(`engine_stats()`). A new choice is a line of `TOLD` and a `book` where it
is made.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars

# choice -> the attribute of the engine (`continuous_batching.
# PrefillPrograms`) and key of `engine_stats()` it is told under, {jitted
# program: paths}; a program that made no such choice has no entry.
TOLD = {
    # a sparse model's grouped expert matmuls (`ops.grouped_matmul`):
    # "kernel" or "ragged_dot"
    "grouped_matmul": "moe_grouped_path",
    # a prefill's fresh rows (`decoding.attend_fresh`: `attend_held`'s
    # prefills and a latent prefill's expanded rows): "flash" or "dense"
    "fresh_rows": "prefill_attention_path",
    # a decode step's held rows in a stack (`decoding.attend_held`):
    # "kernel" or "dense"
    "held_rows": "decode_attention_path",
    # a state-space mixer's recurrence (`ops.ssd.book`): "scan:kernel" (a
    # prefill's), "state:kernel" (a step's) or ":plain"
    "ssm": "ssm_path",
    # a delta-rule layer's recurrence (`ops.delta_rule.book`): "scan:kernel"
    # (a prefill's chunked scan, `kimi_linear.kda_chunks`), "state:kernel"
    # (a decode step's update, `kimi_linear.kda_attention`) or ":plain"
    "delta_rule": "kda_path",
}
_seen: contextvars.ContextVar = contextvars.ContextVar(
    "traced_choices", default=None)


@contextlib.contextmanager
def booked(choice: str = None):
    """{choice: the set of paths booked while the body ran}; with `choice`,
    that one's set alone."""
    seen = collections.defaultdict(set)
    token = _seen.set(seen)
    try:
        yield seen if choice is None else seen[choice]
    finally:
        _seen.reset(token)


def book(choice: str, path: str) -> None:
    """`path` for `choice`, one of `TOLD`'s, where the choice is made."""
    if choice not in TOLD:
        raise KeyError(f"{choice!r} is no line of traced.TOLD")
    seen = _seen.get()
    if seen is not None:
        seen[choice].add(path)
