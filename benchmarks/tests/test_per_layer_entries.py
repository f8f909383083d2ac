"""The per-layer list since PR 42: one entry for each pair (reader file,
moved metric), with the cells it is read in. Every entry that took the place
of several is held to the names it replaced: the same reader file. (No
reader sees its metric's name, so one file is one value: comparing the two
names' values could not fail. The span readers that find something in the
recorded chat trace are held to the values recorded from it instead.) The
table is also the way back from a new name to the names the ledger's lines
before PR 42 carry.

Since PR 69 an entry is a QUESTION and not a configuration's answer to it:
``benchmarks/renamed.json`` is the way back from an entry to the names the
ledger's lines up to PR 68 carry, each with what its reader (a file of 5-7
lines that PR 69 deleted) handed ``ctx`` to. The ``test_*_cost.py`` files
hold their configurations' COUNTS to hand counts and pin no list."""

import os

import pytest

from benchmarks import costs, harness
from benchmarks import program_spans as P

# entry -> the prefixes under which its reader was entered before PR 42
# ("-" is the reader's own name): `doc_slot_occupancy`, ... -> `tput_slot_occupancy`
MERGED = {
    "compile_s": "- moe",
    "replica_start_s": "- moe",
    "tput_proxy_refused_share": "doc moe",
    "tput_decode_steps_per_s": "doc moe reason code",
    "tput_slot_occupancy": "doc moe reason code rollout",
    "tput_prefill_device_ms_per_req": "doc moe",
    "doc_prefill_device_share": "- moe",
    "tput_device_idle_share": "doc moe reason code rollout",
    "tput_ttft_p50_ms": "doc moe",
    "tput_itl_p99_ms": "doc moe",
    "tput_engine_host_ms_per_step": "doc moe reason code",
    "tput_engine_admit_ms_per_req": "doc moe",
    "tput_idle_attributed_share": "doc moe",
    "tput_stream_yield_ms_per_token": "doc reason code",
    "tput_decode_step_device_ms": "moe reason code rollout",
    "moe_expert_ms_per_decode_step": "- reason code rollout",
    "moe_assignments_per_token": "- reason code rollout",
    "moe_expert_load_max_over_mean": "- reason",
    "tput_engine_step_period_ms": "reason code rollout",
    "head_sample_ms_per_decode_step": "- code",
    "held_experts_roofline": "- rollout",
    "tput_pump_cpu_ms_per_step": "reason code",
    "tput_pump_wait_ms_per_step": "reason code",
    "tput_stream_detokenize_ms_per_token": "reason code",
    "tput_stream_rpc_ms_per_token": "reason code",
    "tput_idle_stream_work_share": "reason code",
    "tput_stream_items_per_call": "reason code",
}
# what the merged span readers read on SAMPLE (the chat cell's recorded chip
# trace, reduced on the CPU: arithmetic over its spans, no new reading); the
# other merged span readers find no span of theirs in it and give None
RECORDED = {
    "tput_engine_host_ms_per_step": 6.2952595,
    "tput_idle_attributed_share": 98.03556279819415,
    "tput_stream_yield_ms_per_token": 4.929532090909091,
    "tput_engine_step_period_ms": 79.606649,
}
SAMPLE = os.path.join(os.path.dirname(__file__), "data",
                      "serve_chat_spans_sample.json.gz")


def replaced(entry: str) -> list:
    """The names ``entry`` took the place of."""
    reader = os.path.basename(harness.load_reader(entry).__file__)[:-3]
    return [reader if p == "-" else f"{p}_{reader}"
            for p in MERGED[entry].split()]


@pytest.fixture(scope="module")
def recorded():
    """A traced chat run as its runner leaves it to the readers, spans only."""
    return {"cell": {}, "counters": {}, "device": {},
            "trace": {"program_spans": P.load_sample(SAMPLE)}}


@pytest.mark.parametrize("entry", MERGED)
def test_a_merged_entry_is_read_by_the_file_of_every_name_it_replaced(entry):
    per_layer = {m["name"]: m for m in harness.load_benchmark()["per_layer"]}
    olds = replaced(entry)
    assert len(olds) >= 2 and len(set(olds)) == len(olds)
    assert all(old == entry or old not in per_layer for old in olds)
    reader = harness.load_reader(entry)
    for old in olds:
        assert harness.load_reader(old).__file__ == reader.__file__
    # as many cells as names: the rollout cell joined some lists besides
    assert len(per_layer[entry]["workloads"]) >= len(olds)


@pytest.mark.parametrize("entry", [
    m["name"] for m in harness.load_benchmark()["per_layer"]
    if m["name"] in MERGED and m["source"] == "program_span"])
def test_a_merged_span_reader_reads_the_recorded_value(entry, recorded):
    value = harness.load_reader(entry).read(recorded)
    if entry in RECORDED:
        assert value == pytest.approx(RECORDED[entry], rel=1e-9)
    else:
        assert value is None


def test_the_table_is_every_entry_that_several_cells_share():
    shared = {m["name"] for m in harness.load_benchmark()["per_layer"]
              if m["name"].startswith("tput_")}
    # new in PR 42 and nothing replaced; the two CPU shares that
    # `counters["stream_path"]` also holds have no entry: read in a traced
    # window, they hold the profiler's CPU (PERF.md, section 6, PR 42)
    assert shared - set(MERGED) == {"tput_proxy_forward_ms_per_item"}
    assert shared and all(m["moves"] == "out_tokens_per_s"
                          for m in harness.load_benchmark()["per_layer"]
                          if m["name"] in shared)
    with pytest.raises(FileNotFoundError):  # retired in PR 42, reader and all
        harness.load_reader("stream_hop_gap_ms")


# PR 69: ``renamed.json`` holds, frozen from the parent's ``layer_metrics/``,
# every name a merged entry took the place of, with the runners whose cells
# it was read in and what its reader handed ``ctx`` to.
RENAMED = harness.load_json(os.path.join(harness.HERE, "renamed.json"))["rows"]


def _named(what):
    """A frozen row's answer: ``module.function`` by name, a tuple of
    scopes, or a capture's key as it is."""
    if isinstance(what, list):
        return tuple(what)
    if "." in what:
        module, function = what.split(".")
        return getattr(__import__(f"benchmarks.{module}", fromlist=[function]),
                       function)
    return what


@pytest.mark.parametrize("row", RENAMED, ids=lambda row: row["old"])
def test_a_replaced_name_is_still_answered_as_it_was(row):
    """A SUBSET is held, so that a later cell joins a list, brings its
    ``answers/<runner>.py`` and adds entries without turning this red: every
    cell the old name was read in is on the new entry's list, and its
    configuration's runner gets the answer it had."""
    bench = harness.load_benchmark()
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    me = per_layer[row["new"]]
    # what the parent's entries stated, the merged ones still state
    assert (me["unit"], me["better"]) == (
        ("%", "higher") if row["new"].endswith("_roofline") else ("ms", "lower"))
    assert (me["source"], me["layer"], me["moves"]) == (
        "device_trace", "model", "out_tokens_per_s")
    reader = harness.load_reader(row["new"])
    if row["old"] != row["new"]:  # gone, entry and file: a name that still
        # finds a reader finds, as a prefixed name does, the one that took
        # its place
        assert row["old"] not in per_layer
        try:
            assert harness.load_reader(row["old"]).__file__ == reader.__file__
        except FileNotFoundError:
            pass
    assert set(row["cells"]) <= set(me["workloads"])
    for cell in row["cells"]:
        config = harness.load_cell(cell)["config"]
        assert config["runner"] in row["runners"]
        if row["question"]:
            assert costs.of({"cell": {"config": config}},
                            row["question"]) == _named(row["answer"])
    # a runner with no file under ``answers/`` has nothing to read
    nobody = {"cell": {"config": {"runner": "serve_nobody"}, "toy": False},
              "counters": {}, "device": {}, "trace": {}}
    if row["question"]:
        assert reader.read(nobody) is None
