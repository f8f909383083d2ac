"""BENCHMARK.json against the parts of the contract a test can check: names,
units, limits, files found by name, a reader for every per-layer metric."""

import os
import re

from benchmarks import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_benchmark_json_meets_the_contract():
    b = harness.load_benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmarks"] and b["command"][-1].startswith("benchmarks/")
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    assert os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json")) < 64 * 1024
    configs = {c["name"]: c for c in b["configs"]}
    files = [c["file"] for c in b["configs"]]
    assert len(set(files)) == len(files)
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("benchmarks/")
        conf = harness.load_json(os.path.join(harness.ROOT, c["file"]))
        assert conf["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not re.search(r"(_dim|_rank|hidden_size|intermediate)", key)
        assert os.path.exists(os.path.join(
            harness.HERE, "runners", conf["runner"] + ".py"))
    cells = [w["name"] for w in b["workloads"]]
    assert 2 <= len(cells) <= 24 and len(set(cells)) == len(cells)
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(1, len(cells) // 4)
    assert {w["config"] for w in b["workloads"]} == set(configs)
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        harness.find_data_file("traffic", w["traffic"])
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(set(names)) == len(names)
    assert 1 <= len(b["per_layer"]) <= 128
    # one entry a reader and a moved metric: what cells share is one entry
    # with the cells in its list, not an entry a cell under a prefix (PR 42)
    read_by = [(harness.load_reader(m["name"]).__file__, m["moves"])
               for m in b["per_layer"]]
    assert len(set(read_by)) == len(read_by), sorted(
        pair for pair in set(read_by) if read_by.count(pair) > 1)
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert callable(harness.load_reader(m["name"]).read), m["name"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
        # reported only where the metric it moves is
        moved_in = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", cells)) <= moved_in, m["name"]
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    for cell in cells:  # every cell: setup_s, another end-to-end, a layer
        mine = [m["name"] for m in b["end_to_end"]
                if cell in m.get("workloads", cells)]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(cell in m.get("workloads", cells) for m in b["per_layer"])


def test_a_twin_metric_is_read_by_the_file_of_its_name_without_the_prefix():
    assert harness.load_reader("tput_slot_occupancy").__file__.endswith(
        os.path.join("layer_metrics", "slot_occupancy.py"))
    assert harness.load_reader("slot_occupancy").__file__.endswith(
        os.path.join("layer_metrics", "slot_occupancy.py"))
    try:
        harness.load_reader("doc_no_such_metric")
    except FileNotFoundError:
        pass
    else:
        raise AssertionError("a metric without a reader was found one")
