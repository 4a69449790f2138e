"""The operation and byte counters against one layer worked by hand."""
import pytest

import model
import roofline

PK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _cfg(name, **kw):
    conf = model.load_config(name)
    return model.builder(conf).program_config(conf, **kw)


def test_kernel_costs_by_hand():
    gemv = roofline.kernel("quant_gemv").cost
    # 32 rows x up-projection of starcoder2 (18432 x 4608), 4-bit nibbles,
    # per channel, 5 tasks' scales
    f, b = gemv(32, 18432, 4608, 4, "nibble", 1, 5)
    assert f == 2 * 32 * 18432 * 4608
    assert b == 18432 * 4608 // 2 + 5 * 18432 * 8 + 32 * 4608 * 2 \
        + 32 * 18432 * 2
    # 3-bit bit-planes stream 3 bits a weight; nibbles stream 4
    f3, b3 = gemv(1, 4096, 4096, 3, "plane", 32, 1)
    assert b3 == 4096 * 4096 * 3 // 8 + 4096 * 32 * 8 + 4096 * 2 + 4096 * 2
    assert roofline.seconds(f, b, PK) == pytest.approx(b / 819e9)


def test_one_layer_decode_by_hand():
    cfg = _cfg("starcoder2-7b-w4", n_layers=1)
    d, ff, v = 4608, 18432, 49152
    lin = [(4608, d), (512, d), (512, d), (d, 4608), (ff, d), (d, ff)]
    m, live, tasks = 2, 300, 2
    f, b = roofline.decode_step(cfg, m, live, tasks)
    flops = sum(2 * m * n * k for n, k in lin) + 2 * m * v * d \
        + 4 * live * 36 * 128
    codes = sum(n * k // 2 for n, k in lin)
    scales = sum(tasks * n * 8 for n, _ in lin)
    acts = sum(m * k * 2 + m * n * 2 for n, k in lin)
    kv = live * 2 * 4 * 128 * 2
    assert f == flops
    assert b == codes + scales + acts + v * d * 4 + m * d * 4 + kv


def test_train_flops_per_token_by_hand():
    cfg = _cfg("mistral-7b-w3", n_layers=1)
    d, ff, v, s = 4096, 14336, 32768, 1024
    n_lin = d * 4096 + 2 * d * 1024 + 4096 * d + 3 * d * ff
    want = 4 * n_lin + 4 * v * d + 6 * s * 32 * 128
    assert roofline.train_flops_per_token(cfg, s) == want


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")
    assert roofline.peaks("TPU v5 lite")["bf16_flops"] == 197e12
