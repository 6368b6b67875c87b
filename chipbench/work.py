"""Work counted from shapes: model FLOPs per token for training, and the
operations and bytes of one call of each Pallas kernel; and the chip's
peaks by ``device_kind``.

A multiply-add is 2 FLOPs.  Training is forward plus backward, three
times the forward; recomputation is not counted.  Causal attention and
the WKV recurrence count the work they require: position t attends to
t + 1 keys.  Norms, softmax and other elementwise work are left out, as
model FLOPs leave them out.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def _dense_forward_macs(c: dict, seq: int) -> float:
    d, hd = c["hidden_size"], c["head_dim"]
    hq, hkv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    proj = d * hq * 2 + d * hkv * 2 + 3 * d * c["intermediate_size"]
    attn = 2 * hq * (seq + 1) / 2          # q.k and p.v over (t+1) keys
    return c["num_hidden_layers"] * (proj + attn) + d * c["vocab_size"]


def _rwkv6_forward_macs(c: dict, seq: int) -> float:
    d, ff, lora = c["hidden_size"], c["intermediate_size"], 32
    time_mix = 5 * d * d + 2 * (5 * lora) * d + 2 * lora * d
    wkv = 2 * d * (d // c["num_attention_heads"])   # state update + read
    channel_mix = 2 * d * ff + d * d
    return (c["num_hidden_layers"] * (time_mix + wkv + channel_mix)
            + d * c["vocab_size"])


FORWARD_MACS = {"dense": _dense_forward_macs, "rwkv6": _rwkv6_forward_macs}


def train_flops_per_token(c: dict, seq: int) -> float:
    """Forward and backward FLOPs per token of a configuration file."""
    return 3 * 2 * FORWARD_MACS[c["family"]](c, seq)


def flash_attention(b: int, s: int, h: int, d: int, itemsize: int = 2,
                    causal: bool = True) -> tuple[float, float]:
    """(FLOPs, bytes) of one forward call: q.k and p.v over the keys each
    query sees; q, k, v read and o written once."""
    keys = (s + 1) / 2 if causal else s
    return 4.0 * b * h * s * keys * d, 4.0 * b * s * h * d * itemsize


def rglru(b: int, s: int, r: int, itemsize: int = 4) -> tuple[float, float]:
    """(FLOPs, bytes) of one call: per element sqrt(1 - a^2) (3), the
    scaled input (1) and the recurrence's multiply-add (2); a, x read and
    h written, plus the last state."""
    return 6.0 * b * s * r, (3.0 * b * s * r + b * r) * itemsize


def wkv6(b: int, h: int, s: int, d: int, itemsize: int = 4
         ) -> tuple[float, float]:
    """(FLOPs, bytes) of one call: per token and head, the state update
    k v^T and decay (3 d^2) and the read r S plus the u bonus (2 d^2 +
    3 d); r, k, v, w read, o written, u and the final state."""
    flops = b * h * s * (5.0 * d * d + 3.0 * d)
    return flops, (5.0 * b * h * s * d + h * d + b * h * d * d) * itemsize
