"""flops.py against counts worked by hand for both configurations."""

import pytest

from chipbench import cells, flops


def cfg(name):
    return cells.build_model(cells.load_config(name)).cfg


def test_gpt2_large_train_flops_per_token():
    c = cfg("gpt2-large")
    # layer: 4 x 1280^2 attention + 2 x 1280 x 5120 feed-forward
    assert flops.layer_matmul_params(c) == 6_553_600 + 13_107_200
    # 6 x (36 x 19,660,800 + 1280 x 50257) + 6 x 36 x 1280 x 1024
    assert flops.train_flops_per_token(c, 1024) == 4_632_706_560 + 283_115_520


def test_opt_1_3b_train_flops_per_token():
    c = cfg("opt-1.3b")
    assert c.ffn_size == 8192 and c.num_params() > 1.3e9
    assert flops.layer_matmul_params(c) == 16_777_216 + 33_554_432
    # 6 x (24 x 50,331,648 + 2048 x 50272) + 6 x 24 x 2048 x 2048
    assert flops.train_flops_per_token(c, 2048) == 7_865_499_648 + 603_979_776


def test_flash_attention_call():
    # gpt2-large train: (4, 20, 1024, 64) bf16, causal
    ops, nbytes = flops.flash_attention_call(4, 20, 20, 1024, 64, 2, backward=False)
    assert ops == 2 * 2 * 4 * 20 * 1024 * 1024 * 64 // 2 == 10_737_418_240
    assert nbytes == 4 * (4 * 20 * 1024 * 64 * 2) == 41_943_040
    ops_b, bytes_b = flops.flash_attention_call(4, 20, 20, 1024, 64, 2, backward=True)
    assert ops_b == 2.5 * ops and bytes_b == 7 * 10_485_760


def test_roofline_names_its_bound():
    peaks = cells.load_peaks()["TPU v5 lite"]
    t, bound = flops.roofline_seconds(197e12, 1.0, peaks)
    assert (t, bound) == (pytest.approx(1.0), "compute")
    t, bound = flops.roofline_seconds(1.0, 819e9, peaks)
    assert (t, bound) == (pytest.approx(1.0), "memory")
