"""The program's spans in a trace: the arithmetic on a hand-made structure,
the readers on a trace without such spans, and the whole on a sample
recorded on the chip."""

import json
import os

import pytest

from benchmarks import harness
from benchmarks import program_spans as P

MS = 1_000_000  # nanoseconds
PUMP, HANDLER = 3, 7  # lines of the host plane


def _step(start, number, admit=False, sync=60):
    """One engine.step on the pump's line: [admit 20 ms: prefill_dispatch 2,
    install_dispatch 1, first_token_sync 16], decode_dispatch 3 ms,
    sample_sync ``sync`` ms, emit 1 ms, and 1 ms of its own at the end."""
    t, spans = start, []
    if admit:
        spans.append([P.ADMIT, t, 20 * MS, PUMP,
                      {"bucket": 256, "prompt_len": 200,
                       "queued_ms": 40.0 + number}])
        spans.append([P.ENGINE + "prefill_dispatch", t, 2 * MS, PUMP, {}])
        spans.append([P.ENGINE + "install_dispatch", t + 2 * MS, 1 * MS, PUMP, {}])
        spans.append([P.FIRST_TOKEN_SYNC, t + 3 * MS, 16 * MS, PUMP, {}])
        t += 20 * MS
    spans.append([P.DECODE_DISPATCH, t, 3 * MS, PUMP, {"active": 4}])
    spans.append([P.SAMPLE_SYNC, t + 3 * MS, sync * MS, PUMP, {}])
    spans.append([P.ENGINE + "emit", t + (3 + sync) * MS, 1 * MS, PUMP, {}])
    end = t + (5 + sync) * MS
    spans.append([P.STEP, start, end - start, PUMP,
                  {"step": number}])
    return spans, end


def _parsed():
    """Three decode steps (the second admits), 50 ms of engine.idle, one
    more step, then 4 ms in which the pump is in no span at all. The device
    is busy from 1 ms after each decode dispatch opens until 1 ms before the
    sample sync ends, and for the 15 ms of the prefill."""
    spans, busy, t = [], [], 0
    for number, admit in ((0, False), (1, True), (2, False)):
        step, end = _step(t, number, admit)
        spans += step
        dispatch = t + (20 * MS if admit else 0)
        if admit:
            busy.append([t + 3 * MS, t + 18 * MS])  # the prefill
        busy.append([dispatch + 1 * MS, dispatch + 62 * MS])
        t = end
    spans.append([P.IDLE, t, 50 * MS, PUMP, {}])
    t += 50 * MS
    step, end = _step(t, 3)
    spans += step
    busy.append([t + 1 * MS, t + 62 * MS])
    busy.append([end + 4 * MS, end + 5 * MS])  # an operation nobody spans
    for i in range(4):
        spans.append([P.STREAM_YIELD, 10 * MS * i, (i + 1) * MS, HANDLER, {}])
    spans.sort(key=lambda s: (s[1], -s[2]))
    return {"spans": spans, "busy": {"/device:TPU:0": busy},
            "window": {"/device:TPU:0": [busy[0][0], busy[-1][1]]}}


def test_innermost_segments_of_nested_spans():
    segments = P.innermost_segments([
        ["a", 0, 100], ["b", 10, 30], ["c", 15, 5], ["d", 60, 10],
        ["e", 120, 10]])
    assert segments == [
        [0, 10, "a"], [10, 15, "b"], [15, 20, "c"], [20, 40, "b"],
        [40, 60, "a"], [60, 70, "d"], [70, 100, "a"], [120, 130, "e"]]


def test_idle_time_goes_to_the_span_that_covers_it():
    parsed = _parsed()
    idle = P.idle_by_span(parsed)
    ms = {k: round(v * 1e3, 6) for k, v in idle.items()}
    # per step: 1 ms at the head of the dispatch (the first step's lies
    # before the first operation, outside the window), then from 1 ms before
    # the sync ends: 1 ms of sync, 1 ms of emit, 1 ms of the step's own
    assert ms["decode_dispatch"] == 3.0
    assert ms["sample_sync"] == 4.0
    assert ms["emit"] == 4.0
    # the admitting step: prefill_dispatch's 2 ms, install's 1 ms, the last
    # 1 ms of first_token_sync and the 1 ms of admit after it
    assert ms["prefill_dispatch"] == 2.0 and ms["install_dispatch"] == 1.0
    assert ms["first_token_sync"] == 1.0 and ms["admit"] == 1.0
    assert ms["idle"] == 50.0
    # the step's own last 1 ms, four times, and nothing else of it
    assert ms["step"] == 4.0
    # 4 ms after the last step, under no span
    assert ms["unattributed"] == 4.0
    total = sum(idle.values())
    assert P.idle_attributed_share(parsed) == pytest.approx(
        100.0 * (total - 0.004) / total)


def test_a_gap_is_split_among_the_spans_it_crosses():
    parsed = {"spans": [[P.STEP, 0, 10 * MS, PUMP, {"step": 0}],
                        [P.SAMPLE_SYNC, 2 * MS, 3 * MS, PUMP, {}]],
              "busy": {"d": [[0, 1 * MS], [12 * MS, 13 * MS]]},
              "window": {"d": [0, 13 * MS]}}
    idle = P.idle_by_span(parsed)
    assert {k: round(v * 1e3, 6) for k, v in idle.items()} == {
        "step": 6.0, "sample_sync": 3.0, "unattributed": 2.0}


def test_step_numbers():
    parsed = _parsed()
    steps = P.decode_steps(parsed)
    assert len(steps) == 4
    # 65 ms, then the admitting step's 85 ms; the pair across the idle span
    # does not count
    assert P.step_period_ms(parsed) == pytest.approx(75.0)
    assert P.step_periods_ms(parsed) == [pytest.approx(65.0), pytest.approx(85.0)]
    # the step less its syncs: 5 ms, and 9 ms where it admits
    assert P.host_ms_per_step(parsed) == pytest.approx(5.0)
    assert P.mean_ms(parsed, P.ADMIT) == pytest.approx(20.0)
    assert P.stat_median(parsed, P.ADMIT, "queued_ms") == pytest.approx(41.0)
    assert P.mean_ms(parsed, P.STREAM_YIELD) == pytest.approx(2.5)
    assert P.pump_line(parsed) == PUMP


def test_a_step_that_never_dispatched_breaks_the_chain():
    parsed = _parsed()
    first_dispatch = next(s for s in parsed["spans"] if s[0] == P.DECODE_DISPATCH)
    parsed["spans"].remove(first_dispatch)  # step 0 found nothing active
    assert len(P.decode_steps(parsed)) == 3
    assert P.step_period_ms(parsed) == pytest.approx(85.0)


def test_a_trace_without_program_spans_gives_none():
    parsed = dict(_parsed(), spans=[])
    assert P.idle_by_span(parsed) is None
    assert P.idle_attributed_share(parsed) is None
    assert P.step_period_ms(parsed) is None
    assert P.host_ms_per_step(parsed) is None
    assert P.mean_ms(parsed, P.ADMIT) is None
    assert P.stat_median(parsed, P.ADMIT, "queued_ms") is None
    no_device = dict(_parsed(), busy={}, window={})
    assert P.idle_by_span(no_device) is None
    assert P.step_period_ms(no_device) == pytest.approx(75.0)


def test_without_a_trace_every_reader_returns_none(tmp_path, monkeypatch):
    cell = {"name": "serve-chat-steady"}
    untraced = {"cell": cell, "counters": {"gaps_s": [0.08]}, "trace": {},
                "device": {}}
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))  # no .bench_out here
    traced_no_file = dict(untraced, trace={"busy_s": 1.0, "window_s": 2.0})
    for entry in harness.load_benchmark()["per_layer"]:
        if entry["source"] != "program_span" or entry["layer"] == "input":
            continue
        reader = harness.load_reader(entry["name"])
        assert reader.read(untraced) is None, entry["name"]
        assert reader.read(traced_no_file) is None, entry["name"]


SAMPLE = os.path.join(os.path.dirname(__file__), "data",
                      "serve_chat_spans_sample.json.gz")


@pytest.mark.skipif(not os.path.exists(SAMPLE), reason="no recorded trace")
def test_recorded_chip_trace_reads_to_its_known_numbers():
    parsed = P.load_sample(SAMPLE)
    with open(SAMPLE.replace(".json.gz", ".expected.json")) as f:
        want = json.load(f)
    assert list(parsed["busy"]) == ["/device:TPU:0"]
    idle = P.idle_by_span(parsed)
    assert {k: pytest.approx(v, rel=1e-9) for k, v in want["idle_s_by_span"].items()} == idle
    assert P.idle_attributed_share(parsed) == pytest.approx(
        want["idle_attributed_share"], rel=1e-9)
    assert P.step_period_ms(parsed) == pytest.approx(want["step_period_ms"], rel=1e-9)
    assert P.host_ms_per_step(parsed) == pytest.approx(want["host_ms_per_step"], rel=1e-9)
    assert P.mean_ms(parsed, P.STREAM_YIELD) == pytest.approx(
        want["stream_yield_ms"], rel=1e-9)
    assert len(P.decode_steps(parsed)) == want["decode_steps"]
