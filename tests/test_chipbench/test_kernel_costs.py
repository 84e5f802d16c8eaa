"""`kernel_costs` against counts worked by hand, and the table of peaks."""

import pytest

from chipbench import kernel_costs


def test_causal_attention_flops_by_hand():
    # one head, 4 positions, head_dim 8: 4 * 5 / 2 = 10 score entries;
    # forward 2 products of 2 * 8 FLOPs each per entry = 320
    assert kernel_costs.causal_attention_flops(1, 1, 4, 8) == 320
    # backward: four products = 640
    assert kernel_costs.causal_attention_flops(1, 1, 4, 8, True) == 640
    # GPT-2 XL, one row: 25 heads of 64 over 1024 positions
    assert kernel_costs.causal_attention_flops(1, 25, 1024, 64) \
        == 25 * (1024 * 1025 // 2) * 4 * 64


def test_paged_decode_bytes_by_hand():
    # 2 slots holding 100 tokens in all, 32 query heads over 8 KV heads of
    # 128, bf16: K and V 2 * 100 * 8 * 128 * 2 B; q and o 2 * 2 * 32 * 128
    # * 2 B
    assert kernel_costs.paged_decode_bytes(100, 2, 32, 8, 128, 2) \
        == 409_600 + 32_768
    assert kernel_costs.paged_decode_flops(100, 32, 128) == 4 * 100 * 32 * 128


def test_the_decode_kernel_is_bound_by_bytes_on_the_v5e():
    peak = kernel_costs.peaks("TPU v5 lite")
    secs, bound = kernel_costs.roofline_seconds(
        kernel_costs.paged_decode_flops(20_000, 32, 128),
        kernel_costs.paged_decode_bytes(20_000, 32, 32, 8, 128, 2), peak)
    assert bound == "bytes"
    assert secs == pytest.approx((2 * 20_000 * 8 * 128 * 2
                                  + 2 * 32 * 32 * 128 * 2) / 819e9)


def test_gpt2_xl_needs_about_ten_gflop_a_token():
    sizes = {"n_embd": 1600, "n_layer": 48, "n_head": 25,
             "n_positions": 1024, "vocab_size": 50257,
             "padded_vocab_size": 50304}
    matmul = 48 * 12 * 1600 * 1600 + 50304 * 1600
    attn = 48 * 25 * (1024 * 1025 / 2) * 64 * 12 / 1024
    assert kernel_costs.gpt2_train_flops_per_token(sizes) \
        == pytest.approx(6 * matmul + attn)
    assert 9.5e9 < kernel_costs.gpt2_train_flops_per_token(sizes) < 10.5e9


def test_peaks_are_the_published_ones_and_an_unknown_kind_is_an_error():
    peak = kernel_costs.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9 and peak["hbm_bytes"] == 16e9
    with pytest.raises(KeyError, match="TPU v9"):
        kernel_costs.peaks("TPU v9")
    with pytest.raises(KeyError):
        kernel_costs.peaks("_source")
