"""Which implementation a choice made at trace time fell on.

An op that picks a kernel or its XLA spelling by what it can see of a call
(`grouped_matmul.takes`, `attention.flash_attention_takes`) books the pick;
whoever traces a jitted program around it collects the picks, so that a
program that fell back says so in one look (`engine_stats()`).
"""

from __future__ import annotations

import contextlib
import contextvars


class TracedPaths:
    """One choice's picks: `book(path)` where the choice is made,
    `with traced() as seen:` around a program's trace."""

    def __init__(self, name: str):
        self._seen: contextvars.ContextVar = contextvars.ContextVar(
            name, default=None)

    @contextlib.contextmanager
    def traced(self):
        """The set of paths booked while the body ran."""
        seen: set = set()
        token = self._seen.set(seen)
        try:
            yield seen
        finally:
            self._seen.reset(token)

    def book(self, path: str) -> None:
        seen = self._seen.get()
        if seen is not None:
            seen.add(path)
