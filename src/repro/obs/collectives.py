"""The collectives of a compiled step, by kind, counted from its HLO text.

``count_collectives`` reads ``compiled.as_text()`` of a jitted step (the
per-device SPMD module) and gives, for each kind of collective, the ops
one execution of the step issues and the bytes each device sends for
them over the interconnect, by ``repro.hw.hlo``'s ring model (all-gather
and reduce-scatter (g-1)/g of the full tensor, all-reduce twice that,
all-to-all (g-1)/g, collective-permute its payload; g the group size).

Per step means:

* an op inside a loop body counts once per trip: the trip count is the
  while's ``known_trip_count``, else the bound its condition compares the
  counter with (a scan's ``i < n``), else 1;
* an async collective counts once: its ``-done`` half is not counted, and
  the TPU compiler's async collective fusions (a start, continuations
  that overlap the transfer with compute, a done, each a fused
  computation holding a copy of the op under one ``channel_id``) count
  as the one op they split; ops outside fused computations count one
  each, whatever their ``channel_id`` (every ``collective-permute`` of a
  ``ppermute`` carries channel 1);
* the TPU compiler's all-reduce-scatter fusion (an ``all-reduce`` inside a
  computation named ``all-reduce-scatter...``) counts as the
  reduce-scatter it implements.

``collectives()`` is the process-wide registry that the training launcher
fills from its compiled step (``record_collectives``); nothing on the
step's own path reads the HLO.
"""

from __future__ import annotations

import re

from repro.hw.hlo import parse_collective
from repro.obs.metrics import MetricsRegistry

KINDS = ("all-gather", "reduce-scatter", "all-reduce", "all-to-all",
         "collective-permute")

_HEADER = re.compile(r"^(ENTRY\s+)?%([\w.\-]+)\s+\(")
_CALLEE = re.compile(
    r"\b(calls|body|condition|to_apply|branch_computations|"
    r"true_computation|false_computation)=(\{[^}]*\}|%[\w.\-]+)")
_NAME = re.compile(r"%([\w.\-]+)")
_KNOWN_TRIPS = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_INT_CONSTANT = re.compile(r"=\s*s32\[\]\S*\s+constant\((\d+)\)")
_FUSED_CALL = re.compile(r"\bcalls=%([\w.\-]+)")


def _computations(text: str) -> tuple[str | None, dict[str, list[str]]]:
    """(the entry computation's name, each computation's lines)."""
    entry, bodies, current = None, {}, None
    for line in text.splitlines():
        if current is None:
            m = _HEADER.match(line)
            if m and line.rstrip().endswith("{"):
                current = m.group(2)
                bodies[current] = []
                if m.group(1):
                    entry = current
        elif line.startswith("}"):
            current = None
        else:
            bodies[current].append(line)
    return entry, bodies


def _trips(while_line: str, condition: list[str]) -> int:
    m = _KNOWN_TRIPS.search(while_line)
    if m:
        return int(m.group(1))
    bounds = [int(c.group(1)) for c in map(_INT_CONSTANT.search, condition)
              if c]
    lt = any("compare(" in line and "direction=LT" in line
             for line in condition)
    return bounds[0] if lt and len(bounds) == 1 else 1


def _runs_per_step(entry: str, bodies: dict[str, list[str]]
                   ) -> dict[str, int]:
    """How many times each computation runs in one run of ``entry``.
    Reducers (``to_apply``) and loop conditions hold no collective and
    are left out; an async op's computation counts at its start."""
    calls: dict[str, list[tuple[str, int]]] = {}
    for name, lines in bodies.items():
        out = calls.setdefault(name, [])
        for line in lines:
            if re.search(r"\basync-(?:update|done)\(", line):
                continue
            callees = dict(_CALLEE.findall(line))
            trips = (_trips(line, bodies.get(
                _NAME.search(callees["condition"]).group(1), []))
                     if "body" in callees and "condition" in callees else 1)
            for attr, target in callees.items():
                if attr in ("to_apply", "condition"):
                    continue
                factor = trips if attr == "body" else 1
                out.extend((c, factor) for c in _NAME.findall(target))
    runs: dict[str, int] = {}

    def visit(name: str, n: int) -> None:
        runs[name] = runs.get(name, 0) + n
        for callee, factor in calls.get(name, ()):
            visit(callee, n * factor)

    visit(entry, 1)
    return runs


def _fused(bodies: dict[str, list[str]]) -> set[str]:
    """The computations a ``fusion`` instruction calls."""
    return {callee for lines in bodies.values() for line in lines
            if " fusion(" in line for callee in _FUSED_CALL.findall(line)}


def count_collectives(hlo_text: str) -> dict[str, dict[str, float]]:
    """kind -> {"ops": ops per step, "bytes": bytes each device sends per
    step}, for the kinds the step issues, in ``KINDS`` order."""
    entry, bodies = _computations(hlo_text)
    if entry is None:
        return {}
    fused = _fused(bodies)
    seen: dict[tuple, tuple[int, float]] = {}
    for name, n in _runs_per_step(entry, bodies).items():
        for line in bodies.get(name, ()):
            op = parse_collective(line)
            if op is None:
                continue
            kind, sent = op.base_kind, op.link_bytes
            if kind == "all-reduce" and name.startswith("all-reduce-scatter"):
                g = op.group_size
                kind, sent = "reduce-scatter", op.full_bytes * (g - 1) / g
            key = ((kind, op.channel)
                   if op.channel is not None and name in fused
                   else (kind, name, op.name))
            runs, _ = seen.get(key, (0, 0.0))
            seen[key] = (max(runs, n), sent)
    out: dict[str, dict[str, float]] = {}
    for (kind, *_), (runs, sent) in seen.items():
        c = out.setdefault(kind, {"ops": 0, "bytes": 0.0})
        c["ops"] += runs
        c["bytes"] += runs * sent
    return {k: out[k] for k in KINDS if k in out}


_COLLECTIVES = MetricsRegistry()


def collectives() -> MetricsRegistry:
    """The collectives of the step the launcher compiled last: counters
    ``<kind>.ops`` and ``<kind>.bytes`` per step (``count_collectives``);
    empty before any, and on one device."""
    return _COLLECTIVES


def record_collectives(hlo_text: str) -> MetricsRegistry:
    """Put the counts of ``hlo_text`` in ``collectives()``, in place of
    what it held."""
    reg = collectives()
    reg.counters.clear()
    for kind, c in count_collectives(hlo_text).items():
        reg.counter(f"{kind}.ops").inc(c["ops"])
        reg.counter(f"{kind}.bytes").inc(c["bytes"])
    return reg
