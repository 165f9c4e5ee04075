"""The benchmark's FLOP and byte functions against hand counts."""
import json
from pathlib import Path

import pytest

from benchlib import cost

CONFIGS = Path(__file__).resolve().parents[2] / "bench" / "configs"


def _conf(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_mamba2_matmul_params_hand_count():
    c = _conf("mamba2-370m")
    # per layer: 1024 * (2*2048 + 2*128 + 32) in, 2048 * 1024 out
    per_layer = 1024 * (4096 + 256 + 32) + 2048 * 1024
    assert per_layer == 6_586_368
    assert cost.mamba2_matmul_params(c) == 48 * per_layer + 50280 * 1024
    assert cost.mamba2_matmul_params(c) == 367_632_384


def test_mamba2_train_flops_per_token_hand_count():
    c = _conf("mamba2-370m")
    ssd = 2 * 128 * 128 * 1 + 2 * 128 * 64 * 32 + 4 * 128 * 64 * 32
    assert cost.mamba2_ssd_flops_per_token(c) == ssd == 1_605_632
    conv = 2 * 4 * (2048 + 256)
    fwd = 2 * 367_632_384 + 48 * (ssd + conv)
    assert cost.mamba2_train_flops_per_token(c) == 3 * fwd
    # about 2.4 GFLOP a token, 39-40 TFLOP for an 8 x 2048 step
    assert 2.4e9 < 3 * fwd < 2.5e9
    assert 39e12 < 3 * fwd * 8 * 2048 < 41e12


@pytest.mark.parametrize("container,bits,per_token", [
    ("sfp-m2e4", 7, 3616), ("sfp8", 8, 4128), ("sfp16", 16, 8224)])
def test_kv_bytes_per_token(container, bits, per_token):
    c = _conf("mistral-large-123b")
    assert cost.payload_bits(container) == bits
    # 2 layers x (K and V) x (8 heads x 128 values at `bits` + 8 bases)
    assert 2 * cost.kv_bytes_per_token_layer(c, container) == per_token


def test_mistral_decode_step_bytes_and_flops():
    c = _conf("mistral-large-123b")
    per_layer = (2 * 12288 * 12288 + 2 * 12288 * 1024 + 3 * 12288 * 28672)
    params = 2 * per_layer + 12288 * 32768
    assert cost.gqa_matmul_params(c) == params == 3_170_893_824
    flops, byts = cost.decode_step(c, "sfp-m2e4", 64, 1000 * 64)
    assert byts == 2 * params + 2 * 64_000 * 1808
    assert flops == 2 * params * 64 + 2 * 4 * 64_000 * 96 * 128
    # weights alone: 6.34 GB, 7.7 ms at 819 GB/s
    assert abs(2 * params / 819e9 - 7.74e-3) < 1e-4


def test_roofline_time_names_the_bound():
    from benchlib import peaks
    p = peaks.for_kind("TPU v5 lite")
    assert cost.roofline_time(197e12, 1.0, p) == (1.0, "compute")
    t, bound = cost.roofline_time(1.0, 819e9, p)
    assert bound == "memory" and t == pytest.approx(1.0)
    with pytest.raises(KeyError):
        peaks.for_kind("cpu")
