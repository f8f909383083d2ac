"""flops.py against counts made by hand for the three configurations."""

import os

import pytest

from benchmarks import flops, harness

CONFIGS = os.path.join(harness.HERE, "configs")

# one Mistral-7B-v0.3 block, by hand:
#   wq 4096*4096 + wk 4096*1024 + wv 4096*1024 + wo 4096*4096 = 41,943,040
#   gate, up, down 3 * 4096*14336                             = 176,160,768
BLOCK = 41_943_040 + 176_160_768
HEAD = 4096 * 32768  # output head; the embedding is a lookup


def _config(name):
    return harness.load_json(os.path.join(CONFIGS, name + ".json"))


@pytest.mark.parametrize("name,layers,total_b", [
    ("mistral7b-v03-lora-d8", 8, 2.013),
    ("mistral7b-v03-serve-d16", 16, 3.758),
    ("mistral7b-v03-lora-full", 32, 7.248),
])
def test_parameter_counts(name, layers, total_b):
    c = _config(name)
    assert flops.block_matmul_params(c) == BLOCK == 218_103_808
    assert flops.matmul_params(c) == layers * BLOCK + HEAD
    # norms: two per block and the final one; embedding and head untied
    assert flops.total_params(c) == layers * (BLOCK + 2 * 4096) \
        + 2 * HEAD + 4096
    assert round(flops.total_params(c) / 1e9, 3) == total_b


def test_total_params_agree_with_the_program():
    c = _config("mistral7b-v03-lora-full")
    assert flops.total_params(c) == harness.model_config(c).num_params()


def test_lora_and_train_flops_by_hand():
    c = _config("mistral7b-v03-lora-d8")
    # rank 16 on wq (4096->4096), wv (4096->1024), gate (4096->14336)
    per_layer = 16 * (4096 + 4096) + 16 * (4096 + 1024) + 16 * (4096 + 14336)
    assert per_layer == 507_904
    assert flops.lora_params(c, 16) == 8 * per_layer
    n = 8 * BLOCK + HEAD
    # causal attention, seq 2048: 2048*2049/2 pairs, 2 matmuls of
    # 2*128 operations per pair and head, 32 heads, 8 layers; x3 with the
    # backward pass; per token
    attn = 3 * (8 * 32 * 2 * 256 * (2048 * 2049 / 2)) / 2048
    want = 4 * n + 6 * 8 * per_layer + attn
    assert flops.train_flops_per_token(c, 2048, 16) == pytest.approx(want)
    # dense training pays dW as well
    assert flops.train_flops_per_token(c, 2048, 0) == pytest.approx(6 * n + attn)
    # the program's own "rough" flops_per_token counts frozen dW as useful
    # and a wrong attention term: the benchmark's figure is lower
    assert want < harness.model_config(c, lora_rank=16).flops_per_token()


def test_flash_kernel_cost_and_peaks():
    from benchmarks import peaks

    c = _config("mistral7b-v03-lora-d8")
    cost = flops.flash_kernel_cost(4, 2048, c)
    pairs = 2048 * 2049 / 2
    assert cost["forward"]["flops"] == pytest.approx(
        2 * 4 * 32 * 256 * pairs * 8)
    assert cost["backward"]["flops"] == 2 * cost["forward"]["flops"]
    tensor = 4 * 2048 * 32 * 128 * 2 * 8
    assert cost["forward"]["bytes"] == 4 * tensor
    shared = flops.flash_kernel_cost(4, 2048, c, chips_sharing=4)
    assert shared["forward"]["flops"] == cost["forward"]["flops"] / 4
    seconds, bound = peaks.roofline_seconds(
        cost["forward"]["flops"], cost["forward"]["bytes"], "TPU v5 lite")
    assert bound == "compute" and seconds == cost["forward"]["flops"] / 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
