"""HLO text analysis: collective-byte accounting and op histograms.

``compiled.cost_analysis()`` reports flops and HBM bytes but NOT collective
traffic, so the collective roofline term is derived here by parsing the
optimized HLO (``compiled.as_text()``) of the per-device SPMD module.

Optimized HLO prints operands without type annotations, so byte counts
come from each collective's RESULT shape (for ``-start`` async forms the
result is a tuple — the largest element is the payload):

    all-gather       result = full gathered tensor
    reduce-scatter   result = one shard (full = result * g)
    all-reduce       result = full tensor
    all-to-all       result = full (same total as operand)
    collective-permute  result = payload

Ring cost model per device (bytes on the wire):
    all-gather / reduce-scatter   (g-1)/g * full
    all-reduce                    2 (g-1)/g * full
    all-to-all                    (g-1)/g * full
    collective-permute            payload

Groups whose members span more than one pod are classified as DCI
(pod-crossing) traffic, the rest ICI.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Iterable

import numpy as np

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1,
    "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

# e.g. "bf16[256,4096]{1,0}" or "f32[]" ; layout braces optional
_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\](?:\{[^}]*\})?")

# one HLO instruction: "%name = <result-type> <opcode>(...), attrs"
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+?)\s+"
    r"(all-gather(?:-start)?|all-reduce(?:-start)?|reduce-scatter"
    r"|all-to-all|collective-permute(?:-start)?)\("
    r"(.*)$"
)

_EXPLICIT_GROUPS_RE = re.compile(r"replica_groups=\{(\{[^=]*?\})\}")
_IOTA_GROUPS_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")


def shape_bytes(shape_str: str) -> int:
    """Bytes of one HLO shape string like ``bf16[8,128]{1,0}``."""
    m = _SHAPE_RE.match(shape_str.strip())
    if not m:
        return 0
    dtype, dims = m.group(1), m.group(2)
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _result_bytes(kind: str, result_str: str) -> int:
    """Payload bytes of a collective's (possibly tuple) result type.  An
    async ``-start`` of a gather or permute returns (operand, result,
    context...): its largest element is the payload.  Otherwise a tuple is
    a variadic collective, and every element is payload."""
    sizes = [shape_bytes(m.group(0))
             for m in _SHAPE_RE.finditer(result_str)]
    if kind.endswith("-start") and not kind.startswith("all-reduce"):
        return max(sizes, default=0)
    return sum(sizes)


def _parse_groups(attrs: str) -> list[list[int]]:
    m = _IOTA_GROUPS_RE.search(attrs)
    if m:
        n_groups, group_size = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        perm = ([int(x) for x in m.group(4).split(",")]
                if m.group(4) else list(range(len(dims))))
        ids = np.arange(int(np.prod(dims))).reshape(dims).transpose(perm).ravel()
        return ids.reshape(n_groups, group_size).tolist()
    m = _EXPLICIT_GROUPS_RE.search(attrs)
    if m:
        body = m.group(1)
        groups = []
        for grp in re.findall(r"\{([0-9,\s]*)\}", body):
            members = [int(x) for x in grp.replace(" ", "").split(",") if x]
            if members:
                groups.append(members)
        return groups
    return []


def _full_and_ring(kind: str, result_bytes: int, g: int
                   ) -> tuple[float, float]:
    """(full tensor bytes, per-device ring link bytes)."""
    g = max(g, 1)
    if kind.startswith("all-gather"):
        full = float(result_bytes)
        return full, full * (g - 1) / g
    if kind.startswith("reduce-scatter"):
        full = float(result_bytes) * g
        return full, full * (g - 1) / g
    if kind.startswith("all-reduce"):
        full = float(result_bytes)
        return full, 2.0 * full * (g - 1) / g
    if kind.startswith("all-to-all"):
        full = float(result_bytes)
        return full, full * (g - 1) / g
    if kind.startswith("collective-permute"):
        full = float(result_bytes)
        return full, full
    return float(result_bytes), float(result_bytes)


@dataclasses.dataclass
class CollectiveOp:
    kind: str
    full_bytes: float          # logical tensor size moved
    group_size: int
    crosses_pod: bool
    link_bytes: float          # ring-model per-device bytes on the wire
    name: str = ""             # the HLO instruction
    channel: int | None = None  # channel_id, shared by an async op's parts

    @property
    def base_kind(self) -> str:
        return self.kind.replace("-start", "")


@dataclasses.dataclass
class CollectiveStats:
    ops: list[CollectiveOp]

    @property
    def raw_operand_bytes(self) -> float:
        return sum(op.full_bytes for op in self.ops)

    @property
    def ici_link_bytes(self) -> float:
        return sum(op.link_bytes for op in self.ops if not op.crosses_pod)

    @property
    def dci_link_bytes(self) -> float:
        return sum(op.link_bytes for op in self.ops if op.crosses_pod)

    def by_kind(self) -> dict[str, tuple[int, float]]:
        """kind -> (count, link_bytes)."""
        out: dict[str, tuple[int, float]] = defaultdict(lambda: (0, 0.0))
        for op in self.ops:
            c, b = out[op.base_kind]
            out[op.base_kind] = (c + 1, b + op.link_bytes)
        return dict(out)

    def summary(self) -> str:
        parts = [f"{k}:n={c},linkB={b:.3e}" for k, (c, b) in
                 sorted(self.by_kind().items())]
        return (f"ici={self.ici_link_bytes:.3e}B dci={self.dci_link_bytes:.3e}B "
                + " ".join(parts))


_CHANNEL_RE = re.compile(r"channel_id=(\d+)")


def parse_collective(line: str, pod_size: int | None = None
                     ) -> CollectiveOp | None:
    """The collective one HLO instruction line issues, or None (not a
    collective, or the ``-done`` half of an async pair).

    ``pod_size``: number of devices per pod; a replica group containing
    members from different ``device // pod_size`` blocks is classified as
    pod-crossing (DCI)."""
    m = _INSTR_RE.match(line)
    if not m:
        return None
    name, result_str, kind, attrs = m.groups()
    if kind.endswith("-done"):
        return None
    n_bytes = _result_bytes(kind, result_str)
    groups = _parse_groups(attrs)
    g = len(groups[0]) if groups else 1
    crosses = False
    if pod_size and groups:
        for grp in groups:
            pods = {d // pod_size for d in grp}
            if len(pods) > 1:
                crosses = True
                break
    full, ring = _full_and_ring(kind, n_bytes, g)
    channel = _CHANNEL_RE.search(attrs)
    return CollectiveOp(
        kind=kind, full_bytes=full, group_size=g,
        crosses_pod=crosses, link_bytes=ring, name=name,
        channel=int(channel.group(1)) if channel else None)


def parse_collectives(hlo_text: str, pod_size: int | None = None
                      ) -> CollectiveStats:
    """Extract every collective op with its ring-model link bytes (see
    ``parse_collective``)."""
    ops = [op for op in (parse_collective(line, pod_size)
                         for line in hlo_text.splitlines()) if op]
    return CollectiveStats(ops=ops)


def op_histogram(hlo_text: str, opcodes: Iterable[str] | None = None
                 ) -> dict[str, int]:
    """Count instructions by opcode (for redundancy / remat analysis)."""
    counts: dict[str, int] = defaultdict(int)
    instr = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]*\s*=\s*\S+\s+([a-z][\w\-]*)\(")
    for line in hlo_text.splitlines():
        m = instr.match(line)
        if m:
            op = m.group(1)
            if opcodes is None or op in opcodes:
                counts[op] += 1
    return dict(counts)
