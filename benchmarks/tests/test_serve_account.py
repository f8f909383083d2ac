"""account(): from what the clients saw to one window's numbers, and the
problems that make a run incorrect."""

import types

import pytest

from benchmarks import loadgen, replica
from benchmarks.runners import serve

N_NEW = 3


def _window(answers, repeats, repeat_every=2, stats_close=None,
            proxy_close=None):
    """A played open-loop window of len(answers) requests, each answered
    with the given token ids (None: a 503)."""
    dep = types.SimpleNamespace(tok=replica.IdTokenizer(), n_new=N_NEW,
                                cfg=types.SimpleNamespace(vocab_size=100))
    records = []
    for i, ids in enumerate(answers):
        rec = loadgen.Record(i, 10.0 + i)
        if ids is None:
            rec.status, rec.error = 503, "shed"
        else:
            rec.status, rec.text = 200, dep.tok.decode(ids)
            rec.chunk_times = [10.1 + i + 0.05 * k for k in range(len(ids))]
        records.append(rec)
    played = {"records": records, "t_open": 10.0, "t_close": 30.0,
              "late_s": [0.0]}
    stats = {k: 0 for k in ("admitted", "finished", "failed", "steps",
                            "tokens_out")}
    marks = {"open_wall": 0.0, "engine_open": stats,
             "engine_close": dict(stats, **(stats_close or {})),
             "proxy_open": {}, "proxy_close": proxy_close or {}}
    if stats_close:  # a program that keeps the stream path's counters
        marks["engine_open"] = dict(stats, **dict.fromkeys(stats_close, 0.0))
        marks["proxy_open"] = dict.fromkeys(proxy_close, 0)
    traffic = {"loop": "open", "repeat_every": repeat_every}
    schedule = {"n_ramp": 0, "repeats": repeats, "prompts": answers}
    return serve.account(dep, traffic, schedule, played, marks)


def test_a_clean_window():
    win = _window([[1, 2, 3], [4, 5, 6], [1, 2, 3], [7, 8, 9]], [0, 2])
    assert win["problems"] == [] and win["failed"] == 0
    assert win["attempted"] == 4 and len(win["gaps_s"]) == 4 * (N_NEW - 1)
    assert win["out_tokens_per_s"] == pytest.approx(12 / 20.0)


@pytest.mark.parametrize("answers,repeats,problem", [
    # equal prompts, different answers
    ([[1, 2, 3], [4, 5, 6], [1, 2, 9], [7, 8, 9]], [0, 2], "different answers"),
    # the mix repeats a prompt, but only one answer to it came back: the
    # check above compared nothing (PR 22's review: repeat_every 50 in a
    # window of 46 requests)
    ([[1, 2, 3], [4, 5, 6], [7, 8, 9]], [0], "were not compared"),
    ([[1, 2, 3], [4, 5, 6], None], [0, 2], "were not compared"),
    # a short answer, a token outside the vocabulary
    ([[1, 2, 3], [4, 5], [1, 2, 3]], [0, 2], "request 1"),
    ([[1, 2, 3], [4, 5, 600], [1, 2, 3]], [0, 2], "request 1"),
])
def test_what_makes_a_window_incorrect(answers, repeats, problem):
    win = _window(answers, repeats)
    assert any(problem in p for p in win["problems"]), win["problems"]


def test_the_window_carries_the_stream_paths_counters_where_the_program_keeps_them():
    answers = [[1, 2, 3], [4, 5, 6], [1, 2, 3]]
    assert _window(answers, [0, 2])["stream_path"] is None  # an older program
    win = _window(answers, [0, 2], stats_close={
        "steps": 40, "pump_step_s": 18.0, "pump_sync_s": 9.0,
        "pump_cpu_s": 3.0, "process_cpu_s": 24.0}, proxy_close={
        "process_cpu_s": 26.0, "stream_items": 9, "stream_forward_s": 0.0018})
    c = win["stream_path"]
    assert c["window_s"] == win["window_s"] == 20.0 and c["steps"] == 40
    assert (c["replica_cpu_s"], c["frontdoor_cpu_s"], c["stream_items"]) == (
        24.0, 26.0, 9)
    # nothing that decides an end-to-end metric reads the key
    assert win["out_tokens_per_s"] == pytest.approx(9 / 20.0)
    assert win["problems"] == [] and win["failed"] == 0


def test_a_mix_without_repeats_is_not_asked_for_them():
    assert _window([[1, 2, 3], [4, 5, 6]], [], repeat_every=0)["problems"] == []
