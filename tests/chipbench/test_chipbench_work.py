"""Work counted from shapes, against counts made by hand at small
shapes, the flash kernels' roofline share, and the peaks table."""

import json
from pathlib import Path

import pytest

from chipbench import spec, work

ROOT = Path(__file__).resolve().parents[2]

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


@pytest.mark.parametrize("call, dots, tensors", [
    # dK/dV: q.k^T, dO.v^T, p^T.dO, dS^T.q; reads q, k, v, dO, writes dK, dV
    ("dkv", 4, 6),
    # dQ: q.k^T, dO.v^T, dS.k; reads q, k, v, dO, writes dQ
    ("dq", 3, 5),
])
def test_flash_attention_backward_by_hand(call, dots, tensors):
    # the 10 causal (query, key) pairs of 4 positions, 2 dims, 2 FLOPs each
    flops, nbytes = work.flash_attention(b=1, s=4, h=1, d=2, itemsize=2,
                                         call=call)
    assert flops == dots * 10 * 2 * 2
    assert nbytes == tensors * (4 * 2) * 2


# 3 layers of one head of 2 dims over 4 positions, batch 1: per layer
# bytes bound every call (its FLOPs take under 1% of its bytes' time):
# forward 64 bytes, dK/dV 96, dQ 80
TINY_FLASH = {"config": {"num_attention_heads": 1, "head_dim": 2,
                         "num_hidden_layers": 3,
                         "compute_dtype": "bfloat16"},
              "traffic": {"global_batch": 1, "seq_len": 4},
              "device_kind": "TPU v5 lite"}


@pytest.mark.parametrize("remat, chips, least_bytes", [
    (True, 1, 3 * (2 * 64 + 96 + 80)),       # the forward rematerialised
    (False, 1, 3 * (64 + 96 + 80)),
    (True, 4, 3 * (2 * 64 + 96 + 80) / 4),   # each chip a quarter
])
def test_flash_attention_roofline_by_hand(remat, chips, least_bytes):
    read = spec.metric_reader("flash_attention_roofline.train")
    least_ms = 1e3 * least_bytes / 819e9
    record = {**TINY_FLASH, "chips": chips,
              "traffic": {**TINY_FLASH["traffic"], "remat": remat},
              "trace": {"op_ms_per_step": {"flash_attention": 2 * least_ms,
                                           "fusion": 1.0}}}
    assert read(record) == pytest.approx(50.0)
    record["trace"]["op_ms_per_step"].pop("flash_attention")
    assert read(record) is None


def test_flash_attention_roofline_of_the_cells_at_a_known_time():
    """At 15.88 ms of kernels a step (a traced step on one v5e), the
    one-chip cell's kernels read 48%: eleven dots of 2 x 2 x 16 heads
    x 2048 x 2049 / 2 pairs x 128 per layer, compute bound, 8 layers."""
    cell = spec.resolve("olmo-1b-8l.pretrain-2k")
    record = {"config": cell.config, "traffic": cell.traffic, "chips": 1,
              "device_kind": "TPU v5 lite",
              "trace": {"op_ms_per_step": {"flash_attention": 15.88}}}
    dot = 2 * 2 * 16 * 2048 * 2049 / 2 * 128
    want = 100 * 1e3 * 8 * 11 * dot / 197e12 / 15.88
    read = spec.metric_reader("flash_attention_roofline.train")
    assert read(record) == pytest.approx(want) and 48 < want < 49


# the formulas as ``work.py`` had them before the families took them over
def _dense_before(c, seq):
    d, hd = c["hidden_size"], c["head_dim"]
    hq, hkv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    proj = d * hq * 2 + d * hkv * 2 + 3 * d * c["intermediate_size"]
    attn = 2 * hq * (seq + 1) / 2
    return c["num_hidden_layers"] * (proj + attn) + d * c["vocab_size"]


def _rwkv6_before(c, seq):
    d, ff, lora = c["hidden_size"], c["intermediate_size"], 32
    time_mix = 5 * d * d + 2 * (5 * lora) * d + 2 * lora * d
    wkv = 2 * d * (d // c["num_attention_heads"])
    channel_mix = 2 * d * ff + d * d
    return (c["num_hidden_layers"] * (time_mix + wkv + channel_mix)
            + d * c["vocab_size"])


@pytest.mark.parametrize("name", ["olmo-1b-8l", "olmo-1b-16l",
                                  "rwkv6-1.6b-cut"])
def test_config_files_flops_per_token_as_before(name):
    c = json.loads((ROOT / "chipbench" / "configs" / f"{name}.json")
                   .read_text())
    before = {"dense": _dense_before, "rwkv6": _rwkv6_before}[c["family"]]
    assert work.train_flops_per_token(c, 2048) == 3 * 2 * before(c, 2048)


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
