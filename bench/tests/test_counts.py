"""Per-step operation and byte counts against a hand count of one
qwen3-14b layer, and the peaks table keyed by device_kind."""

from __future__ import annotations

import json

import pytest

from conftest import BENCH
from harness import peaks

import importlib.util


def _counts():
    spec = importlib.util.spec_from_file_location(
        "counts_dense", BENCH / "counts" / "dense_decoder.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


C = _counts()
QWEN = json.loads((BENCH / "configs" / "qwen3-14b-d8.json").read_text())
YI = json.loads((BENCH / "configs" / "yi-34b-d8.json").read_text())


def test_one_qwen3_14b_layer_by_hand():
    # q 5120x40x128, k and v 5120x8x128, o 40x128x5120,
    # gate/up 5120x17408, down 17408x5120
    q = 5120 * 40 * 128
    kv = 5120 * 8 * 128
    ffn = 3 * 5120 * 17408
    assert C.layer_matmul_params(QWEN) == 2 * q + 2 * kv + ffn == 330301440
    # norms: two of 5120, q_norm and k_norm of 128
    assert C.layer_params(QWEN) == 330301440 + 2 * 5120 + 2 * 128
    # K and V, 8 heads of 128, bf16, 8 layers
    assert C.kv_bytes_per_position(QWEN) == 8 * 2 * 8 * 128 * 2 == 32768


def test_step_bytes_and_flops():
    weights = 2 * (8 * (330301440 + 10496) + 5120 + 5120 * 151936)
    assert C.step_weight_bytes(QWEN) == weights
    assert C.bytes_needed(QWEN, 1, 0, 0) == weights
    # two slot-steps attending to 10 and 20 positions
    b = C.bytes_needed(QWEN, 1, 2, 30)
    assert b == weights + 2 * (2 * 5120 + 32768) + 30 * 32768
    per_token = 2 * (8 * 330301440 + 5120 * 151936)
    attn = 8 * 4 * 40 * 128
    assert C.flops_needed(QWEN, 2, 30) == 2 * per_token + 30 * attn


def test_yi_layer_is_heavier_and_head_dim_derived():
    assert C.sizes(YI)["hd"] == 128
    assert C.layer_matmul_params(YI) == (2 * 7168 * 56 * 128
                                         + 2 * 7168 * 8 * 128
                                         + 3 * 7168 * 20480)


def test_peaks_keyed_by_device_kind():
    p = peaks.peak_for("TPU v5 lite")
    assert p.bf16_flops == 197e12 and p.hbm_bytes_per_s == 819e9
    with pytest.raises(KeyError):
        peaks.peak_for("cpu")
