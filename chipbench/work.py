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

from chipbench import reference

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def train_flops_per_token(c: dict, seq: int) -> float:
    """Forward and backward FLOPs per token of a configuration file, from
    its family's ``forward_macs``."""
    return 3 * 2 * reference.family(c["family"]).forward_macs(c, seq)


# the flash kernels (``kernels/flash_attention/kernel.py``): per call,
# the dots of each (query, key) pair and the (b, s, h, d) tensors read or
# written once.  forward: q.k^T and p.v; reads q, k, v, writes o.  dkv, the
# dK/dV kernel: q.k^T, dO.v^T, p^T.dO and dS^T.q; reads q, k, v, dO,
# writes dK, dV.  dq: q.k^T, dO.v^T and dS.k; reads q, k, v, dO, writes dQ.
FLASH_CALLS = {"forward": (2, 4), "dkv": (4, 6), "dq": (3, 5)}


def flash_attention(b: int, s: int, h: int, d: int, itemsize: int = 2,
                    causal: bool = True, call: str = "forward"
                    ) -> tuple[float, float]:
    """(FLOPs, bytes) of one call of the flash kernel ``call`` (a key of
    ``FLASH_CALLS``), over the keys each query sees.  The row statistics
    (logsumexp, delta) are left out of the bytes."""
    dots, tensors = FLASH_CALLS[call]
    keys = (s + 1) / 2 if causal else s
    return (2.0 * dots * b * h * s * keys * d,
            float(tensors) * b * s * h * d * itemsize)


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
