"""The FLOP and byte functions on shapes worked by hand."""

import pytest

from chipbench import costs, peaks

TINY = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
        "head_dim": 4, "intermediate_size": 16, "vocab_size": 10,
        "num_hidden_layers": 3}


def test_matmul_params_per_layer_by_hand():
    # q 8*2*4=64, k and v 8*1*4=32 each, o 2*4*8=64, SwiGLU 3*8*16=384
    assert costs.matmul_params_per_layer(TINY) == 64 + 64 + 64 + 384


def test_train_flops_per_token_by_hand():
    # weights: 3 layers * 576 + head 8*10 = 1808 -> 6 * 1808 = 10848
    # attention, causal at half: 6 * seq 5 * H 2 * Dh 4 * 3 layers = 720
    assert costs.train_flops_per_token(TINY, 5) == 10848 + 720
    assert costs.head_share_of_train_flops(TINY, 5) == pytest.approx(
        480 / 11568)


def test_flash_train_flops_by_hand():
    # per token 720 (above), 5 tokens a row, 2 rows
    assert costs.flash_train_flops(TINY, 5, 2) == 720 * 5 * 2


def test_paged_decode_bytes_counts_live_tokens_only():
    # one slot with 7 live tokens, bf16: per layer K and V = 2*7*1*4*2 = 112
    # bytes, q and o = 2*2*4*2 = 32; three layers -> 432
    assert costs.paged_decode_bytes(TINY, [7]) == 3 * (112 + 32)
    # a second, empty slot adds only its q/o; max_pages never enters
    assert costs.paged_decode_bytes(TINY, [7, 0]) == 432 + 3 * 32
    # int8 pool with f32 scales: K and V = 2*7*1*(4*1+4) = 112
    assert costs.paged_decode_bytes(TINY, [7], kv_bytes=1,
                                    scale_bytes=4) == 3 * (112 + 32)
    assert costs.paged_decode_flops(TINY, [7]) == 4 * 7 * 2 * 4 * 3


def test_mistral_serve_live_bytes_are_gigabytes_not_tens():
    m = {"hidden_size": 4096, "num_attention_heads": 32,
         "num_key_value_heads": 8, "head_dim": 128,
         "intermediate_size": 14336, "vocab_size": 32768,
         "num_hidden_layers": 16}
    # 65 536 bytes of K and V a token over 16 layers, as the issue reckons
    per_tok = costs.paged_decode_bytes(m, [1]) - costs.paged_decode_bytes(
        m, [0])
    assert per_tok == 65536
    # 32 slots of 500 live tokens: ~1 GB a tick, 1.3 ms at 819 GB/s
    need = costs.paged_decode_bytes(m, [500] * 32)
    t, bound = costs.roofline_seconds(
        costs.paged_decode_flops(m, [500] * 32), need,
        peaks.peaks_for("TPU v5 lite"))
    assert bound == "bytes" and 1.0e9 < need < 1.1e9
    assert t == pytest.approx(need / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(peaks.UnknownDeviceError):
        peaks.peaks_for("TPU v9 imaginary")
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
