"""The benchmark's yardsticks against hand counts."""

import json
import os

import pytest

from benchlib import flops, peaks

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_qwen2_0_5b_params_by_hand():
    cfg = _config("qwen2-0.5b")
    # per layer: q 896*896, k and v 896*128 each, o 896*896, MLP 3*896*4864
    layer = 2 * 896 * 896 + 2 * 896 * 128 + 3 * 896 * 4864
    assert flops.matmul_params(cfg) == {"layers": 24 * layer,
                                        "lm_head": 151936 * 896}
    assert flops.matmul_params(cfg)["lm_head"] == 136_134_656
    # + q/k/v biases (896 + 2 * 128) and two norms per layer, final norm,
    # the tied embedding: the published 494.0M (494.1M with the 128
    # padded vocabulary rows the program holds)
    assert flops.all_params(cfg) == 494_032_768


def test_qwen2_1_5b_7l_params_by_hand():
    cfg = _config("qwen2-1.5b-7l")
    layer = 2 * 1536 * 1536 + 2 * 1536 * 256 + 3 * 1536 * 8960
    assert flops.matmul_params(cfg) == {"layers": 7 * layer,
                                        "lm_head": 151936 * 1536}
    assert flops.all_params(cfg) == 560_960_000


@pytest.mark.parametrize("name, per_token", [
    # 6 * 493,961,216 matmul params + 6 * 2048 * 14 * 64 * 24 attention
    ("qwen2-0.5b", 6 * 493_961_216 + 6 * 2048 * 14 * 64 * 24),
    # 6 * 560,922,624 + 6 * 2048 * 12 * 128 * 7
    ("qwen2-1.5b-7l", 6 * 560_922_624 + 6 * 2048 * 12 * 128 * 7),
])
def test_train_flops_per_token(name, per_token):
    assert flops.train_flops_per_token(_config(name), 2048) == per_token


def test_plan_buckets_greedy_in_tree_order():
    # 4-byte elements, 40-byte buckets: 3+5 fit, 4 opens a new one, a
    # 20-element leaf is a bucket of its own
    assert flops.plan_buckets([3, 5, 4, 20, 1], 40) == [8, 4, 20, 1]
    assert flops.plan_buckets([], 40) == []


def test_int8_wire_bytes_by_hand():
    # one bucket of 1000 elements pads to 1024; two pods
    b = flops.int8_wire_bytes([1000], n_pods=2)
    scales = 4 * 1024 / 256
    assert b["quantize"] == 4 * 1024 + 1024 + scales
    assert b["dequant_aggregate"] == 2 * (1024 + scales) + 4 * 2 + 4 * 1000
    assert b["total"] == b["quantize"] + b["dequant_aggregate"]


def test_peak_table():
    p = peaks.peak("TPU v5 lite")
    assert (p.bf16_flops, p.hbm_bytes) == (197e12, 819e9)
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")
