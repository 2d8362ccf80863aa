"""Operation and byte counts on shapes worked by hand."""

import pytest

from bench import harness, weights
from bench.counts import transformer as C

QWEN3 = weights.model_spec(harness.load_json(
    harness.BENCH / "configs" / "qwen3-0.6b-untied.json"))


def test_matmul_params_qwen3_0_6b():
    # per layer: q,o 2*1024*2048; k,v 2*1024*1024; MLP 3*1024*3072
    per_layer = 4_194_304 + 2_097_152 + 9_437_184
    assert C.matmul_params(QWEN3) == 28 * per_layer + 151_936 * 1024


def test_train_flops_per_token_qwen3_0_6b_at_4096():
    attn = 6 * 16 * 128 * 4097 * 28
    assert C.train_flops_per_token(QWEN3, 4096) == \
        6 * 595_984_384 + attn == 4_985_536_512


def test_fused_ce_work():
    f, b = C.fused_ce_work(8192, 151_936, 1024)
    assert f == 6 * 8192 * 151_936 * 1024
    assert b == 2 * (8192 + 151_936) * 1024 * 2


def test_least_time_names_its_bound():
    peaks = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert C.least_time(1000, 50, peaks) == (10.0, "compute")
    assert C.least_time(100, 50, peaks) == (5.0, "bytes")
    v5e = harness.peaks("TPU v5 lite")
    # one row: 6 V d FLOPs against (1 + V) d bf16 elements read and written
    t, bound = C.least_time(*C.fused_ce_work(1, 151_936, 1024), v5e)
    assert bound == "bytes" and t == pytest.approx(
        2 * (1 + 151_936) * 2048 / 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        harness.peaks("TPU v9 imaginary")
