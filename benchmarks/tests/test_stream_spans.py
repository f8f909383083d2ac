"""The stream path's spans and counters: the hand-off, the idle overlap and
the pump's clocks on a hand-made structure, and every reader of them on a run
without a trace and on a trace without such spans (the parent's checkout)."""

import pytest

from benchmarks import harness
from benchmarks import program_spans as P
from benchmarks import stream_spans as S

MS = 1_000_000  # nanoseconds
PUMP, HANDLER, OTHER = 3, 7, 8  # lines of the host plane
READERS = ("pump_cpu_ms_per_step", "pump_wait_ms_per_step",
           "stream_detokenize_ms_per_token",
           "stream_rpc_ms_per_token", "stream_handoff_ms_p50",
           "idle_stream_work_share")


def _step(start, number, clocks):
    """An engine.step of 10 ms on the pump's line whose emit opens 8 ms in,
    carrying the pump's clocks as they stood before it."""
    return [[P.STEP, start, 10 * MS, PUMP, dict(clocks, step=number)],
            [P.DECODE_DISPATCH, start, 2 * MS, PUMP, {}],
            [P.SAMPLE_SYNC, start + 2 * MS, 6 * MS, PUMP, {}],
            [S.EMIT, start + 8 * MS, 1 * MS, PUMP, {}]]


def _parsed():
    """Four steps 10 ms apart, the pump's clocks booked after each of the
    first three. The device is busy but for [8, 12) and
    [18, 22) ms. One handler thread decodes 1 ms after each emit opens
    (2 ms late behind the second, with one id more waiting) for 1 ms, and
    yields for 4 ms of which 3 are the call; another thread's yield covers
    the second gap whole, all of it inside its call."""
    spans = []
    for i in range(4):
        spans += _step(10 * i * MS, 100 + i, {
            "pump_step_s": 5.0 + 0.010 * i, "pump_sync_s": 3.0 + 0.006 * i,
            "pump_cpu_s": 1.0 + 0.001 * i})
    for emit, late, ids, backlog in ((8, 1, 5, 0), (18, 2, 6, 1), (28, 1, 7, 0)):
        t = (emit + late) * MS
        spans.append([S.DETOKENIZE, t, 1 * MS, HANDLER,
                      {"ids": ids, "backlog": backlog}])
        spans.append([P.STREAM_YIELD, t + 1 * MS, 4 * MS, HANDLER, {}])
        spans.append([S.STREAM_RPC, t + int(1.5 * MS), 3 * MS, HANDLER,
                      {"bytes": 12}])
    spans.append([P.STREAM_YIELD, 17 * MS, 6 * MS, OTHER, {}])
    spans.append([S.STREAM_RPC, 17 * MS + 1, 6 * MS - 2, OTHER, {"bytes": 12}])
    spans.sort(key=lambda s: (s[1], -s[2]))
    return {"spans": spans,
            "busy": {"/device:TPU:0": [[0, 8 * MS], [12 * MS, 18 * MS],
                                       [22 * MS, 30 * MS]]},
            "window": {"/device:TPU:0": [0, 30 * MS]}}


def test_the_hand_off_is_from_the_emit_to_the_decode_that_found_no_backlog():
    parsed = _parsed()
    # the second decode found an id waiting: it is not the newest's wait
    assert S.handoffs_ms(parsed) == [1.0, 1.0]
    assert S.handoff_ms_p50(parsed) == 1.0
    assert P.stat_median(parsed, S.DETOKENIZE, "ids") == 6
    first_of_a_stream = dict(parsed, spans=[
        s if s[0] != S.DETOKENIZE else s[:4] + [dict(s[4], ids=1)]
        for s in parsed["spans"]])
    assert S.handoffs_ms(first_of_a_stream) == []  # out of an admit, no emit
    assert S.handoff_ms_p50(first_of_a_stream) is None


def test_stream_work_is_the_decode_and_the_yield_outside_its_call():
    work = S.stream_work(_parsed())
    # per token: decode [t, t+1), then the yield's head [t+1, t+1.5) and
    # its tail [t+4.5, t+5); the other thread's yield is all call
    assert [[a / MS, b / MS] for a, b in work[:2]] == [[9, 10.5], [13.5, 14]]
    # the third token's yield opens as the window closes
    assert S._covered([[0, 30 * MS]], work) == pytest.approx(
        5 * MS + 2, abs=4)


def test_a_gap_half_covered_by_stream_work():
    share = S.idle_stream_work_share(_parsed())
    # idle [8, 12): work [9, 10.5) = 1.5 of 4; idle [18, 22): work
    # [20, 21.5) = 1.5 of 4, the other thread's call covering it not counted
    assert share["idle"] == pytest.approx(100 * 3.0 / 8.0, abs=0.01)
    assert share["window"] == pytest.approx(100 * 5.0 / 30.0, abs=0.01)
    half = _parsed()
    half["busy"]["/device:TPU:0"] = [[0, 9 * MS], [12 * MS, 30 * MS]]
    half["spans"] = [s for s in half["spans"] if s[1] < 11 * MS]
    assert S.idle_stream_work_share(half)["idle"] == pytest.approx(50.0)
    assert S.idle_stream_work_share(dict(half, busy={})) is None


def test_two_bookings_in_a_trace_give_the_counters_between_them():
    parsed = _parsed()
    # a pass that booked nothing shows the clocks of the pass before it and
    # is no mark: nothing changes when one is put behind the last booking
    last = [s for s in parsed["spans"] if s[0] == P.STEP][-1]
    parsed["spans"].append([P.STEP, 40 * MS, 10 * MS, PUMP,
                            dict(last[4], step=104)])
    c = S.traced_counters(parsed)
    assert c["steps"] == 2 and c["window_s"] == pytest.approx(0.020)
    assert c["pump_step_s"] == pytest.approx(0.020)
    assert S.pump_cpu_ms_per_step(c) == pytest.approx(1.0)
    assert S.pump_wait_ms_per_step(c) == pytest.approx(10 - 6 - 1)
    # the same arithmetic over a window's two snapshots, with the front door
    w = S.window_counters(
        {"steps": 10, "pump_step_s": 1.0, "pump_sync_s": 0.5,
         "pump_cpu_s": 0.2, "process_cpu_s": 4.0},
        {"steps": 110, "pump_step_s": 2.0, "pump_sync_s": 0.9,
         "pump_cpu_s": 0.4, "process_cpu_s": 4.5},
        {"process_cpu_s": 2.0, "stream_items": 5, "stream_forward_s": 0.001},
        {"process_cpu_s": 2.8, "stream_items": 405, "stream_forward_s": 0.021},
        window_s=1.0)
    assert S.pump_cpu_ms_per_step(w) == pytest.approx(2.0)
    assert S.pump_wait_ms_per_step(w) == pytest.approx(4.0)
    assert S.cpu_share(w["replica_cpu_s"], w["window_s"]) == pytest.approx(50)
    assert S.cpu_share(w["frontdoor_cpu_s"], w["window_s"]) == pytest.approx(80)
    assert S.proxy_forward_ms_per_item(w) == pytest.approx(0.05)
    # and through the one reader of them, as a runner's `account` hands
    # them over (the CPU shares have no entry: a traced window's hold the
    # profiler, and only traced runs report per-layer entries)
    ctx = {"cell": {}, "counters": {"stream_path": w}, "device": {}, "trace": {}}
    read = harness.load_reader("tput_proxy_forward_ms_per_item").read
    assert read(ctx) == pytest.approx(0.05)
    # a program without the counters: the key is there and holds None
    assert read(dict(ctx, counters={"stream_path": None})) is None
    assert read(dict(ctx, counters={})) is None


def test_a_program_without_the_counters_gives_none():
    parsed = _parsed()
    for s in parsed["spans"]:
        if s[0] == P.STEP:
            s[4] = {"step": s[4]["step"]}  # the parent's engine.step
    assert S.traced_counters(parsed) is None
    assert S.window_counters({"steps": 1}, {"steps": 2}, {}, {}, 1.0) is None
    assert S.pump_wait_ms_per_step(None) is None
    assert S.proxy_forward_ms_per_item({"stream_items": 0}) is None


def _parent(parsed):
    """The trace as a program from before these spans writes it."""
    return dict(parsed, spans=[
        s[:4] + [{"step": s[4]["step"]} if s[0] == P.STEP else s[4]]
        for s in parsed["spans"] if s[0] not in (S.DETOKENIZE, S.STREAM_RPC)])


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("prefix", ["", "tput_"])
def test_every_reader(name, prefix):
    read = harness.load_reader(prefix + name).read
    ctx = {"cell": {}, "counters": {}, "device": {}, "trace": {}}
    assert read(ctx) is None  # an untraced run
    assert read(dict(ctx, trace={"program_spans": {
        "spans": [], "busy": {}, "window": {}}})) is None
    assert read(dict(ctx, trace={"program_spans": _parent(_parsed())})) is None
    value = read(dict(ctx, trace={"program_spans": _parsed()}))
    assert value is not None and value >= 0


def test_the_entries_name_their_cells_and_what_they_move():
    per_layer = {m["name"]: m for m in harness.load_benchmark()["per_layer"]}
    long_out = {"serve-cca-reason-long-out", "serve-window-moe-code-long-out"}
    cells = {"": ({"serve-chat-steady"}, "itl_p95_ms"),
             "tput_": (long_out, "out_tokens_per_s")}
    for prefix, (listed, moves) in cells.items():
        for name in READERS:
            if prefix and name == "stream_handoff_ms_p50":
                # the long-output cells' handlers never find an empty queue
                assert prefix + name not in per_layer
                continue
            m = per_layer[prefix + name]
            # one entry a moved metric (PR 42); further cells join its list
            assert set(m["workloads"]) >= listed and m["moves"] == moves
            assert m["better"] == "lower" and m["layer"] in (
                "engine scheduler", "runtime stream path")
