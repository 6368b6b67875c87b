"""From a profiler trace (``.xplane.pb``) to device busy and idle time, the
top device operations, collective time and idle gaps labelled by the
host span that covers them.

The window is the stretch from the first to the last host step span
(``train``).  A device is busy where one of its operations (the ``XLA
Ops`` line of each ``/device:`` plane) runs; busy time is the union of
those intervals inside the window, averaged over the devices.  Each idle
gap of a device inside the window is labelled by the host span among
``data``, ``dispatch`` and ``wait`` that overlaps it most, or ``host``
where none does.  Collective time is the summed duration of the
operations whose name says all-reduce, all-gather, reduce-scatter,
all-to-all or collective-permute: the time the core spends in them, so
an asynchronous collective counts its start and its wait at its done,
not what its transfer overlaps.  An operation's time is its self time:
the ``XLA Ops`` line nests a loop's body inside the loop, and the body's
operations are taken off the loop's.  An operation is named by its HLO
instruction (``fusion.12``), which repeats in every trip of a loop.

Importing this module touches no accelerator.
"""

from __future__ import annotations

import collections
import dataclasses
import re

STEP_SPAN = "train"
HOST_SPANS = ("data", "dispatch", "wait")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"allreduce|allgather|reducescatter|alltoall", re.IGNORECASE)

Interval = tuple[float, float]          # (start, end) in seconds


@dataclasses.dataclass
class Trace:
    device_ops: dict[str, list[tuple[str, float, float]]]   # device -> ops
    host_spans: list[tuple[str, float, float]]              # (name, s, e)


def load(path: str) -> Trace:
    """Read the ops of every device and the benchmark's host spans."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops: dict[str, list] = {}
    spans: list = []
    wanted = set(HOST_SPANS) | {STEP_SPAN}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(plane.name, []).extend(
                        (e.name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns * 1e-9,
                              (e.start_ns + e.duration_ns) * 1e-9)
                             for e in line.events if e.name in wanted)
    return Trace(device_ops=ops, host_spans=spans)


def union(intervals: list[Interval]) -> list[Interval]:
    """Merge overlapping intervals; sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: list[Interval], lo: float, hi: float) -> list[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    head = text.split(" = ", 1)[0] if " = " in text else text
    return head.lstrip("%")[:80]


def self_times(ops: list[tuple[str, float, float]]
               ) -> list[tuple[str, float]]:
    """(name, self seconds) of nested operations on one line."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    own = [e - s for _, s, e in ops]
    stack: list[int] = []
    for i, (_, s, e) in enumerate(ops):
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e, ops[stack[-1]][2]) - s
        stack.append(i)
    return [(op_name(n), t) for (n, _, _), t in zip(ops, own)]


def _overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def label(gap: Interval, spans: list[tuple[str, float, float]]) -> str:
    best, most = "host", 0.0
    for name, s, e in spans:
        if name in HOST_SPANS:
            o = _overlap(gap, (s, e))
            if o > most:
                best, most = name, o
    return best


def reduce(trace: Trace, top: int = 10) -> dict | None:
    """Busy, idle and collective seconds averaged over devices, the top
    operations, and the longest labelled gaps; None without a step span
    or a device operation."""
    steps = [(s, e) for n, s, e in trace.host_spans if n == STEP_SPAN]
    devices = {d: v for d, v in trace.device_ops.items() if v}
    if not steps or not devices:
        return None
    lo, hi = min(s for s, _ in steps), max(e for _, e in steps)
    busy_s = collective_s = 0.0
    per_op: collections.Counter = collections.Counter()
    all_gaps: list[tuple[str, float]] = []
    for ops in devices.values():
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
                  if min(e, hi) > max(s, lo)]
        busy = union([(s, e) for _, s, e in inside])
        busy_s += sum(e - s for s, e in busy)
        for n, dt in self_times(inside):
            per_op[n] += dt
            if COLLECTIVE.search(n):
                collective_s += dt
        all_gaps.extend((label(g, trace.host_spans), g[1] - g[0])
                        for g in gaps(busy, lo, hi))
    n_dev = len(devices)
    all_gaps.sort(key=lambda g: -g[1])
    return {
        "window_s": hi - lo,
        "busy_s": busy_s / n_dev,
        "collective_s": collective_s / n_dev,
        "steps": len(steps),
        "devices": n_dev,
        "device_ops": [[n, t / n_dev] for n, t in per_op.most_common(top)],
        "idle_gaps": [[n, t] for n, t in all_gaps[:top]],
    }
