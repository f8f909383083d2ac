"""The reduction from a trace to numbers: its arithmetic on a hand-made
trace, and the whole of it on a small trace recorded on the chip."""

import os

import pytest

from benchmarks import trace_reduce as R

MS = 1_000_000  # nanoseconds


def _trace():
    # one device, two "steps": a while (10 ms) holding a fusion (4 ms) and a
    # kernel (3 ms), then an exposed all-reduce (2 ms), then 5 ms of nothing
    ops = []
    for base in (0, 20 * MS):
        ops += [["while.1", base, 10 * MS, ""],
                ["fusion.7", base + 1 * MS, 4 * MS, ""],
                ["closed_call.9", base + 6 * MS, 3 * MS,
                 "tpu_custom_call/3in/2out"],
                ["all-reduce.3", base + 10 * MS, 2 * MS, ""]]
    ops.append(["fusion.9", 35 * MS, 1 * MS, ""])
    spans = [["bench.between-steps", 0, 40 * MS],
             ["bench.data", 12 * MS, 7 * MS],
             ["bench.wait", 32 * MS, 3 * MS]]
    return {"devices": {"/device:TPU:0": {
        "ops": ops, "programs": [["jit_step(5)", 0, 12 * MS],
                                 ["jit_step(5)", 20 * MS, 12 * MS]],
        "other_lines": {}}}, "spans": spans}


def test_names_and_tags_from_hlo_text():
    kernel = ('%closed_call.9 = (bf16[128,2048,128]{2,1,0:T(8,128)(2,1)}, '
              'f32[128,1,2048]{2,1,0:T(1,128)}) custom-call(bf16[128,2048,128]'
              '{2,1,0:T(8,128)(2,1)} %bitcast.748, bf16[128,2048,128]{2,1,0} '
              '%bitcast.742, bf16[128,2048,128]{2,1,0} %bitcast.743), '
              'custom_call_target="tpu_custom_call", operand_layout_constraints'
              '={bf16[128,2048,128]{2,1,0}, bf16[128,2048,128]{2,1,0}, '
              'bf16[128,2048,128]{2,1,0}}, frontend_attributes={kernel_metadata={}}')
    assert R.short_name(kernel) == "closed_call.9"
    assert R.op_tag(kernel) == "tpu_custom_call/3in/2out"
    fusion = ("%fusion.616 = bf16[4,2048,14336]{2,1,0:T(8,128)(2,1)} "
              "fusion(bf16[8,4096,14336]{2,1,0} %get-tuple-element.1622), "
              "kind=kOutput, calls=%fused_computation.198")
    assert R.short_name(fusion) == "fusion.616"
    assert R.op_tag(fusion) == "bf16[4,2048,14336]"
    assert R.short_name("dot.3") == "dot.3" and R.op_tag("dot.3") == ""


def test_interval_arithmetic():
    merged = R.union([[5, 9], [0, 3], [2, 4], [9, 10]])
    assert merged == [[0, 4], [5, 10]] and R.length(merged) == 9
    assert R.subtract([[0, 10]], [[2, 3], [5, 7]]) == [[0, 2], [3, 5], [7, 10]]
    assert R.subtract([[0, 4], [6, 8]], [[3, 7]]) == [[0, 3], [7, 8]]
    assert R.op_kind("fusion.123") == "fusion"
    assert R.op_kind("all-reduce-start.2") == "all-reduce-start"


def test_summary_of_a_hand_made_trace():
    s = R.summarize(_trace())
    # busy: per step fusion 4 + kernel 3 + all-reduce 2, and the last fusion
    assert s["busy_s"] == pytest.approx(0.019)
    assert s["window_s"] == pytest.approx(0.036)
    # the while does not count its body twice
    assert s["op_self_s"]["step/while.1"] == pytest.approx(2 * 0.003)
    assert s["op_self_s"]["step/closed_call.9"] == pytest.approx(0.006)
    # outside any program: the bare name
    assert s["op_self_s"]["fusion.9"] == pytest.approx(0.001)
    assert R.kernel_self_s(s, ("tpu_custom_call/3in",)) == pytest.approx(0.006)
    assert R.kernel_self_s(s, ("tpu_custom_call/6in",)) == 0
    assert s["programs"]["step"] == {"count": 2, "total_s": pytest.approx(0.024),
                                         "p50_s": pytest.approx(0.012)}
    # the all-reduce ran while nothing else did: all of it is exposed
    assert s["collective_s"] == pytest.approx(0.004)
    assert s["collective_exposed_s"] == pytest.approx(0.004)
    gaps = dict(s["idle_gaps"])
    # 12->21 ms lies under the data span, 32->35 ms under the wait span, the
    # short gaps inside a step under the outer span
    assert gaps["data"] == pytest.approx(0.009)
    assert gaps["wait"] == pytest.approx(0.003)
    assert gaps["between-steps"] == pytest.approx(0.004)
    assert s["device_ops"][0] == ["step/fusion.7", pytest.approx(0.008)]
    assert ["step/closed_call.9 tpu_custom_call/3in/2out", pytest.approx(0.006)] \
        in s["device_ops"]


def test_hidden_collective_is_not_exposed():
    t = _trace()
    dev = t["devices"]["/device:TPU:0"]
    dev["async"] = [["all-gather.1", 1 * MS, 3 * MS]]  # under fusion.7
    s = R.summarize(t)
    assert s["collective_s"] == pytest.approx(0.004 + 0.003)
    assert s["collective_exposed_s"] == pytest.approx(0.004)


SAMPLE = os.path.join(os.path.dirname(__file__), "data",
                      "train_1chip_trace_sample.json.gz")


@pytest.mark.skipif(not os.path.exists(SAMPLE), reason="no recorded trace")
def test_recorded_chip_trace_reduces_to_its_known_numbers():
    import json

    trace = R.load_sample(SAMPLE)
    want = json.load(open(SAMPLE.replace(".json.gz", ".expected.json")))
    s = R.summarize(trace)
    assert list(trace["devices"]) == ["/device:TPU:0"]
    assert s["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert s["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert 0 < s["busy_s"] <= s["window_s"]
    from benchmarks import readers

    kernels = R.kernel_self_s(s, readers.FLASH_KERNELS)
    assert kernels == pytest.approx(want["flash_kernels_s"], rel=1e-9)
    assert kernels > 0
    assert [n for n, _ in s["device_ops"][:3]] == want["top_ops"]
