"""Work counted from shapes, against counts made by hand at small
shapes, and the peaks table."""

import pytest

from chipbench import work

DENSE = {"family": "dense", "num_hidden_layers": 1, "hidden_size": 4,
         "num_attention_heads": 2, "num_key_value_heads": 2, "head_dim": 2,
         "intermediate_size": 8, "vocab_size": 10}
RWKV = {"family": "rwkv6", "num_hidden_layers": 1, "hidden_size": 4,
        "num_attention_heads": 2, "intermediate_size": 8, "vocab_size": 10}


def test_dense_flops_per_token_by_hand():
    # per token: q, k, v, o 4 x 16 = 64 MACs; gate, up, down 3 x 32 = 96;
    # over 3 positions the causal keys are 1 + 2 + 3 = 6 per head, so q.k
    # and p.v take 2 heads x 2 dims x 6 x 2 / 3 tokens = 16; head 4 x 10.
    assert work.train_flops_per_token(DENSE, seq=3) == 6 * (64 + 96 + 16 + 40)


def test_rwkv6_flops_per_token_by_hand():
    # ddlerp low rank 4 x 160 + 5 x 32 x 4 = 1280; decay low rank 2 x 128;
    # r, k, v, g, o 5 x 16; WKV 2 heads x (2 x 2 state + 2 x 2 read) = 16;
    # channel mix 4 x 8 + 8 x 4 + 4 x 4 = 80; head 40.
    assert work.train_flops_per_token(RWKV, seq=3) == \
        6 * (1280 + 256 + 80 + 16 + 80 + 40)


def test_flash_attention_by_hand():
    # 4 positions, causal: 1 + 2 + 3 + 4 = 10 (query, key) pairs, each 2
    # dims x 2 FLOPs for q.k and as many for p.v
    flops, nbytes = work.flash_attention(b=1, s=4, h=1, d=2, itemsize=2)
    assert flops == 10 * 8
    assert nbytes == 4 * (4 * 2) * 2          # q, k, v, o of 4 x 2 bf16


def test_rglru_by_hand():
    flops, nbytes = work.rglru(b=1, s=2, r=3, itemsize=4)
    assert flops == 6 * 6
    assert nbytes == (3 * 6 + 3) * 4


def test_wkv6_by_hand():
    flops, nbytes = work.wkv6(b=1, h=1, s=2, d=2, itemsize=4)
    assert flops == 2 * (5 * 4 + 3 * 2)
    assert nbytes == (5 * 4 + 2 + 4) * 4


def test_peaks_by_device_kind():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("cpu")
