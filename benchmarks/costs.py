"""What a cell's configuration answers to a question several configurations
share: found by name, as runners and readers are (PR 69).

Since PR 69 a per-layer entry is a QUESTION ("the active sequences' recurrent
states, read once and written once a layer, over the time under the kind's
state scope"), not a configuration's answer to it: one entry, one reader file,
the cells of every configuration that can answer in its list. What a
configuration's layers require of the chip STAYS in its cost module
(``laguna_cost``, ``mimo_cost``, ``kimi_linear_cost``, ``longcat_cost``,
``nemotron_h_cost``, ``jamba_cost``, ``ouro_cost``, ``solar_open2_cost``):
keys of 192 beside values of 128, two attention sublayers a double layer, an
expert of two matrices in a latent are each counted where they were. A reader
puts its question to ``answers/<runner>.py``, the file of the ``runner`` the
cell's configuration states, and does nothing else. That file's ``ANSWERS``
maps a question to what answers it: a function of ``ctx`` (and the question's
own arguments), the scopes of the decode program, or the key under which the
runner's record holds its capture of one warmed prefill. A runner with no
file, or a question its file lacks, gives None, as a reader without its spans
does.

So a cell of a new runner joins ``state_update_*``, ``state_project_*``,
``state_prefill_*``, ``whole_prefill_*`` and the shared rooflines by ADDING
``answers/<its runner>.py`` (and its name to the lists): it edits no file.
"""

from __future__ import annotations

import functools

from benchmarks import harness, readers, scope_ops


@functools.lru_cache(maxsize=None)
def answers(runner) -> dict:
    """``answers/<runner>.py``'s ``ANSWERS``; none where no such file is."""
    try:
        return harness.load_module("answers", runner).ANSWERS if runner else {}
    except FileNotFoundError:
        return {}


def of(ctx, question: str):
    """What answers ``question`` for the cell's configuration, or None."""
    return answers(ctx["cell"]["config"].get("runner")).get(question)


def ask(ctx, question: str, *args):
    """The configuration's own cost function's answer to ``question``."""
    answer = of(ctx, question)
    return answer(ctx, *args) if answer else None


def scopes_ms(ctx, question: str):
    """Self milliseconds a traced decode step under the scopes the
    configuration's recurrent kind runs ``question`` under."""
    scopes = of(ctx, question)
    return scope_ops.ms_per_run(ctx, readers.DECODE_PROGRAM, scopes) \
        if scopes else None


def captured_ms(ctx, question: str):
    """``ms_per_req`` of the capture of ONE warmed prefill that the cell's
    runner left in its record under the key its answers name."""
    key = of(ctx, question)
    return (ctx["counters"].get(key) or {}).get("ms_per_req") if key else None
