"""Operation and byte counts at shapes worked out by hand, and the peak
table."""
import pytest

from chipbench import peaks
from chipbench.counts import decode_attention, dequant_matmul, model
from chipbench.reference import rwkv6, transformer


def test_dequant_matmul_cost():
    # M=64, K=768, N=2048, 4-bit codes in blocks of 64
    flops, nbytes = dequant_matmul.cost(64, 768, 2048)
    assert flops == 2 * 64 * 768 * 2048
    codes = 768 * 2048 // 2
    scales = 768 * (2048 // 64) * 2
    acts = 64 * 768 * 2 + 64 * 2048 * 2
    assert nbytes == codes + scales + acts + 16 * 4


def test_decode_attention_cost_counts_live_rows_only():
    # 2 slots holding 100 and 300 rows, one query each, 12/4 heads x 64
    flops, nbytes = decode_attention.cost(400, 400, 2, 1, n_heads=12,
                                          n_kv_heads=4, head_dim=64)
    assert flops == 4 * 12 * 64 * 400
    row = 4 * (64 + 4)               # q8 codes + f32 scale per KV head
    assert nbytes == 2 * 400 * row + 2 * 2 * 12 * 64 * 2
    f4, b4 = decode_attention.cost(400, 400, 2, 1, n_heads=12, n_kv_heads=4,
                                   head_dim=64, code_bits=4)
    assert f4 == flops and b4 < nbytes


def test_model_flops_per_token():
    m = {"n_layers": 12, "d_model": 768, "n_heads": 12, "n_kv_heads": 4,
         "head_dim": 64, "d_ff": 2048, "vocab": 32768}
    per_layer = 768 * 768 + 2 * 768 * 256 + 768 * 768 + 3 * 768 * 2048
    assert model.matmul_flops_per_token(transformer, m) == 2 * (
        12 * per_layer + 768 * 32768)
    step = dict(valid=3, qrows=500)
    assert model.step_flops(transformer, m, step) == (
        3 * model.matmul_flops_per_token(transformer, m)
        + 4 * 12 * 12 * 64 * 500)
    r = {"n_layers": 24, "d_model": 2048, "d_ff": 7168, "vocab": 65536}
    per_layer = 6 * 2048 ** 2 + 2 * 2048 * 64 + 2 * 2048 * 7168
    assert model.matmul_flops_per_token(rwkv6, r) == 2 * (
        24 * per_layer + 2048 * 65536)
    # eleven packed products per step: ten per layer and the unembed
    assert sum(1 if n == 1 else n // 24 for n, _, _ in rwkv6.matmuls(r)) == 11


def test_peaks_refuse_an_unknown_device():
    assert peaks.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
