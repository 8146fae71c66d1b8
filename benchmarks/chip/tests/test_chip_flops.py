"""flops.py against hand counts, and the peaks table."""
import json

import pytest

import flops
import harness


def _run_as(name):
    return json.load(open(harness.HERE / "configs" / f"{name}.json"))[
        "run_as"]


def test_d20_hand_count():
    m = _run_as("nanochat-d20")
    assert flops.param_count(m) == 561_040_640
    assert flops.matmul_params(m) == 477_154_560
    # 6 x 477.15M + 12 * 20 * 10 * 128 * 2048 = 3.49 GFLOP/token
    want = 6 * 477_154_560 + 12 * 20 * 10 * 128 * 2048
    assert flops.train_flops_per_token(m, 2048) == want
    assert round(want / 1e9, 2) == 3.49


# Qwen1.5-0.5B (huggingface.co/Qwen/Qwen1.5-0.5B), for a cell to come
QWEN = {"num_layers": 24, "d_model": 1024, "num_heads": 16,
        "num_kv_heads": 16, "head_dim": 64, "d_ff": 2816,
        "vocab_size": 151936, "mlp_activation": "swiglu", "qkv_bias": True,
        "tie_embeddings": True}


def test_qwen_hand_count():
    m = QWEN
    assert flops.param_count(m) == 463_987_712     # tied: table once
    assert flops.matmul_params(m) == 463_987_712
    want = 6 * 463_987_712 + 12 * 24 * 16 * 64 * 2048
    assert flops.train_flops_per_token(m, 2048) == want
    assert round(want / 1e9, 2) == 3.39


def test_peaks_keyed_by_device_kind():
    assert harness.peak("TPU v5 lite") == 197e12
    assert harness.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(harness.Refused):
        harness.peak("TPU v99")
