import pytest

from benchmarks.roofline import (decoder_step, least_seconds, paged_attention,
                                 prefill_attention)

PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_paged_attention_by_hand():
    # 32 heads of 128, one row attending 1000 positions, one layer:
    # QK^T 2*128*1000 and PV 2*128*1000 per head
    assert paged_attention.flops(32, 128, 1000, 1) == 4 * 32 * 128 * 1000
    # 8 kv heads: K and V of 1000 positions, bf16, read once; q in, o out
    assert paged_attention.nbytes(32, 8, 128, 1000, 1, 1) == \
        2 * 8 * 128 * 1000 * 2 + 2 * 32 * 128 * 1 * 2
    t, bound = least_seconds(paged_attention.flops(32, 128, 1000, 16),
                             paged_attention.nbytes(32, 8, 128, 1000, 1, 16),
                             PEAKS)
    assert bound == "memory"
    assert t == pytest.approx(16 * (4096000 + 16384) / 819e9)


def test_prefill_chunk_by_hand():
    # 4 queries after 8 cached positions attend 9, 10, 11, 12
    assert prefill_attention.attended(8, 4) == 9 + 10 + 11 + 12
    assert prefill_attention.attended(0, 128) == 128 * 129 // 2
    assert prefill_attention.flops(32, 128, [(8, 4)], 2) == \
        4 * 32 * 128 * 2 * 42
    assert prefill_attention.nbytes(32, 8, 128, [(8, 4)], 1) == \
        2 * 8 * 128 * 12 * 2 + 2 * 32 * 128 * 4 * 2


def test_decoder_step_flops_by_hand():
    cfg = {"hidden_size": 4096, "intermediate_size": 14336,
           "num_hidden_layers": 16, "num_attention_heads": 32,
           "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 32768}
    per_layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert per_layer == 218_103_808
    assert decoder_step.layer_params(cfg) == 16 * per_layer
    f = decoder_step.serve_flops(cfg, [], 1, 0, 1)
    assert f == 2 * 16 * per_layer + 2 * 4096 * 32768
    f2 = decoder_step.serve_flops(cfg, [(0, 128)], 0, 0, 0)
    assert f2 == 2 * 16 * per_layer * 128 + 4 * 32 * 128 * 16 * (128 * 129 // 2)


def test_least_seconds_names_the_bound():
    assert least_seconds(197e12, 1.0, PEAKS) == (1.0, "compute")
    assert least_seconds(1.0, 819e9, PEAKS) == (1.0, "memory")
