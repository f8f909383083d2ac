"""The load generator's schedule: data in, the same schedule for the same
seed, a fixed amount of work for every seed, lateness reported."""

import json
import os
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from benchmarks import harness, loadgen

TRAFFIC = os.path.join(harness.HERE, "traffic")


def _mix(name):
    return harness.load_json(os.path.join(TRAFFIC, name + ".json"))


def test_same_seed_same_schedule_other_seed_same_work():
    chat = _mix("chat-steady")
    a = loadgen.make_schedule(chat, 5, 40.0, 32768)
    b = loadgen.make_schedule(chat, 5, 40.0, 32768)
    c = loadgen.make_schedule(chat, 6, 40.0, 32768)
    assert a["due"] == b["due"] and a["prompts"] == b["prompts"]
    assert a["due"] != c["due"]
    rate = chat["arrival"]["rate_per_s"]
    n_win = round(rate * 40.0)
    assert a["n_ramp"] == round(rate * chat["ramp_s"])
    assert len(a["prompts"]) == len(c["prompts"]) == a["n_ramp"] + n_win
    # arrivals: the ramp before 0, the window inside [0, seconds), sorted
    due = np.array(a["due"])
    assert (due[:a["n_ramp"]] < 0).all() and (due[a["n_ramp"]:] >= 0).all()
    assert (due < 40.0).all() and (np.diff(due[a["n_ramp"]:]) >= 0).all()
    # lengths: stratified, so two seeds offer nearly the same tokens and
    # the same number of prompts to every prefill bucket, give or take the
    # one whose stratum straddles an edge
    la = np.array([len(p) for p in a["prompts"][a["n_ramp"]:]])
    lc = np.array([len(p) for p in c["prompts"][c["n_ramp"]:]])
    assert la.min() >= 65 and la.max() <= 1024
    assert 220 <= np.median(la) <= 300
    assert abs(la.sum() - lc.sum()) / la.sum() < 0.05
    for edge in (128, 256, 512):
        assert abs((la > edge).sum() - (lc > edge).sum()) <= 1
    with pytest.raises(ValueError):
        loadgen.draw_lengths({"dist": "zipf"}, 4, np.random.default_rng(0))
    with pytest.raises(ValueError):
        loadgen.arrival_times({"process": "bursty"}, 4, 1.0,
                              np.random.default_rng(0))


@pytest.mark.parametrize("name", ["chat-steady", "doc-batch"])
def test_the_repeated_prompt_comes_back_inside_a_real_window(name):
    """At the cell's own rate and run length, not at a test's: the check
    'equal prompts give equal answers' needs answers to compare."""
    mix = _mix(name)
    seconds = float(harness.load_benchmark()["run_seconds"])
    s = loadgen.make_schedule(mix, 2, seconds, 32768)
    first, every = s["n_ramp"], mix["repeat_every"]
    assert s["repeats"][:3] == [first, first + every, first + 2 * every]
    if mix["loop"] == "open":
        assert len(s["repeats"]) >= 3  # every request of the window is sent
    else:
        # a closed loop sends what the replica completes: the document
        # cell measured 216 in a window (my chip run, PR 22)
        assert sum(i < 150 for i in s["repeats"]) >= 3
    same = s["prompts"][first]
    assert len(same) == mix["repeat_prompt_tokens"]
    assert all(s["prompts"][i] == same for i in s["repeats"])
    others = [p for i, p in enumerate(s["prompts"]) if i not in s["repeats"]]
    assert same not in others


def test_closed_loop_draws_for_as_long_as_the_window_may_last():
    doc = _mix("doc-batch")
    s = loadgen.make_schedule(doc, 1, 40.0, 32768)
    assert s["due"] is None and s["n_ramp"] == 0
    lens = np.array([len(p) for p in s["prompts"]])
    assert lens.min() >= 1100 and lens.max() <= 1984
    assert len(lens) == int(np.ceil(46.0 * doc["max_requests_per_s"]))


class _Streamer(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def do_POST(self):
        ids = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        self.send_response(200)
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()
        for i, tok in enumerate(ids.split()[:3]):
            data = json.dumps(tok if i == 0 else " " + tok).encode() + b"\n"
            self.wfile.write(f"{len(data):x}\r\n".encode() + data + b"\r\n")
        self.wfile.write(b"0\r\n\r\n")

    def log_message(self, *args):
        pass


def test_client_plays_an_open_loop_and_reports_lateness():
    server = ThreadingHTTPServer(("127.0.0.1", 0), _Streamer)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        mix = {"path": "/x", "loop": "open", "ramp_s": 0.2, "new_tokens": 3,
               "arrival": {"process": "poisson", "rate_per_s": 40.0},
               "prompt_tokens": {"dist": "uniform", "min": 5, "max": 9}}
        schedule = loadgen.make_schedule(mix, 3, 1.0, 100)
        opened = []
        played = loadgen.play(server.server_address[1], mix, schedule, 1.0,
                              on_open=lambda: opened.append(True))
    finally:
        server.shutdown()
        thread.join(timeout=5)
        server.server_close()
    assert opened == [True]
    records = played["records"]
    assert len(records) == len(schedule["prompts"]) == 48
    assert len(played["late_s"]) == 48 and max(played["late_s"]) < 0.5
    for rec, prompt in zip(records, schedule["prompts"]):
        assert rec.status == 200 and rec.error is None
        assert rec.text == loadgen.encode_prompt(prompt[:3])
        assert len(rec.chunk_times) == 3 and rec.chunk_times[0] >= rec.due
