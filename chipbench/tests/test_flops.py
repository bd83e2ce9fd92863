"""The FLOP functions against counts made by hand."""

import pytest

from chipbench import cells, flops, peaks


def config(name):
    return cells.load_json(cells.HERE / "configs" / f"{name}.json")


def test_gpt2_125m_per_token():
    c = config("gpt2-125m")
    d, L, V, T = 768, 12, 50257, 1024
    # per block: qkv d*3d, proj d*d, mlp d*4d + 4d*d = 12 d^2; head V*d once
    n = L * (3 * d * d + d * d + 8 * d * d) + V * d
    assert flops.gpt2_params_matmul(c) == n == 123_532_032
    # 6 N, and attention: QK^T and PV are 2*T*d multiply-adds a token a
    # layer forward = 4*T*d FLOPs, x3 with the backward
    assert flops.gpt2_train_flops_per_token(c, T) == 6 * n + 12 * L * T * d
    assert flops.gpt2_train_flops_per_token(c, T) == pytest.approx(
        854.4e6, rel=1e-3)


def test_gpt2_large_per_token():
    c = config("gpt2-large-774m")
    assert flops.gpt2_params_matmul(c) == 36 * 12 * 1280 ** 2 + 50257 * 1280
    assert flops.gpt2_train_flops_per_token(c, 1024) == pytest.approx(
        5.199e9, rel=1e-3)


def test_resnet50_forward_is_the_published_4_1_g_multiply_adds():
    c = config("resnet50")
    macs = flops.resnet_forward_macs(c["stage_sizes"], 224, 1000)
    assert macs == pytest.approx(4.1e9, rel=0.02)
    assert flops.resnet_train_flops_per_image(c) == 6 * macs
    # the stem by hand: 112*112 outputs x 7*7*3 x 64
    assert flops.resnet_forward_macs([], 224, 0) == 112 * 112 * 147 * 64


def test_peaks_are_the_published_ones_and_unknown_kinds_raise():
    assert peaks.peak_bf16_flops("TPU v5 lite") == 197e12
    with pytest.raises(KeyError):
        peaks.peak_bf16_flops("cpu")
