"""The benchmark's operation and byte counts, and its table of peaks."""

import json
import pathlib

import pytest

from bench import flops, harness

ROOT = pathlib.Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def granite():
    with open(ROOT / "bench" / "configs" / "granite-3-2b.json") as f:
        return json.load(f)


def test_granite_matmul_weights(granite):
    # 40 x (2048*64*(64+16) + 3*2048*8192) + 49155*2048 (tied unembedding)
    assert flops.dense_matmul_params(granite) == 2_533_365_760


def test_granite_flops_per_token(granite):
    # 5.07 GFLOP a token before attention; attention adds 4*32*64*40 = 327,680
    # per attended position
    assert flops.serve_token_flops(granite, 0) == 5_066_731_520
    assert flops.attention_flops(granite, 1) == 327_680
    assert (flops.serve_token_flops(granite, 512)
            == 5_066_731_520 + 512 * 327_680)


def test_granite_weights_held(granite):
    ref = harness.load_module(ROOT / "bench" / "configs" / "granite-3-2b.py")
    # matmul weights + 81 norm scales of 2048
    assert ref.param_count(granite) == 2_533_365_760 + 81 * 2048


@pytest.mark.parametrize("real, key, payload, want", [
    (1 << 24, 4, 0, 134_217_728),  # k16-dup: keys only
    (1 << 24, 4, 4, 268_435_456),  # k8-window at full rows: 16 B an element
    (12_582_912, 4, 4, 201_326_592),  # rows three quarters full
])
def test_merge_min_bytes(real, key, payload, want):
    assert flops.merge_min_bytes(real, key, payload) == want


def test_peaks_v5e():
    p = harness.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        harness.peaks("TPU v9 imaginary")
