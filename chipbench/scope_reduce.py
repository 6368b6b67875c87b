"""From a profiler trace of the program's train step and the step's
compiled HLO text to device time per step under each named scope of the
program, and the device idle time under the program's data fetch.

The window is ``trace_reduce``'s: the stretch from the first to the last
host step span (``train``).  Each device operation's self time inside it
counts under every named scope in its ``op_name``: every component of
the path but the last (the primitive), with transform wrappers stripped
(``transpose(jvp(attention))`` is ``attention``, ``jit(train_step)`` is
``train_step``).  Nested scopes each count it, so ``flash`` inside
``attention`` counts in both; scopes that do not nest split the time.
Operations are named by their HLO instruction, so the ``op_name``s come
from the compiled step's HLO text: a fusion takes the ``op_name`` of its
``dot`` or ``convolution`` if it holds one, else that of its root, so a
weight-gradient matmul fused into a loop's ``dynamic-update-slice`` is
charged to its layer.  The program's own host spans, one ``repro.step``
per iteration of its loop holding one ``repro.data`` around its
``batch_at``, give the device idle time under the data fetch.

The HLO text has to come from a compile that did not load the step from
the persistent compile cache: the cache keys a program without its op
names, so a loaded executable names its operations as whichever source
of the same program wrote the entry.  The trace's events carry no
``op_name`` of their own that ``ProfileData`` reads.

Importing this module touches no accelerator.
"""

from __future__ import annotations

import collections
import dataclasses
import re

from chipbench import trace_reduce
from chipbench.trace_reduce import STEP_SPAN, Trace

PROGRAM_STEP = "repro.step"
PROGRAM_DATA = "repro.data"

_INSTR = re.compile(r"^\s*(ROOT\s+)?%([\w.\-]+) = (.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+) .*\{\s*$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"(?:\w+\()*([^()]*)\)*")   # jvp(attention) -> attention


def load(path: str) -> Trace:
    """``trace_reduce.load``'s device ops and host spans, with the
    program's ``repro.step`` and ``repro.data`` spans besides."""
    from jax.profiler import ProfileData
    trace = trace_reduce.load(path)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                trace.host_spans.extend(
                    (e.name, e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events
                    if e.name in (PROGRAM_STEP, PROGRAM_DATA))
    return trace


@dataclasses.dataclass
class _Instr:
    opcode: str
    op_name: str
    calls: str | None
    root: bool


def hlo_op_names(text: str) -> dict[str, str]:
    """Instruction name -> the ``op_name`` its device time is charged to,
    from a compiled module's HLO text: a fusion's ``dot`` or
    ``convolution``'s, else its root's; any other instruction's own."""
    comps: dict[str, list[_Instr]] = {}
    named: dict[str, _Instr] = {}
    body: list[_Instr] = []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c:
                body = comps.setdefault(c.group(1), [])
            continue
        root, name, rest = m.groups()
        op = _OPCODE.search(" " + rest)
        calls = _CALLS.search(rest)
        op_name = _OP_NAME.search(rest)
        ins = _Instr(op.group(1) if op else "",
                     op_name.group(1) if op_name else "",
                     calls.group(1) if calls else None, bool(root))
        body.append(ins)
        named[name] = ins

    def dot_in(comp: str, depth: int = 0) -> str | None:
        """op_name of the first dot or convolution in ``comp`` or in a
        fusion nested in it."""
        for i in comps.get(comp, ()):
            if i.opcode in ("dot", "convolution") and i.op_name:
                return i.op_name
            if i.opcode == "fusion" and i.calls and depth < 4:
                found = dot_in(i.calls, depth + 1)
                if found:
                    return found
        return None

    def charged(ins: _Instr) -> str:
        if ins.opcode != "fusion" or ins.calls not in comps:
            return ins.op_name
        root = next((i for i in comps[ins.calls] if i.root), None)
        return (dot_in(ins.calls) or (root.op_name if root else "")
                or ins.op_name)

    return {name: charged(ins) for name, ins in named.items()}


def scope_names(op_name: str) -> set[str]:
    """Every named scope in ``op_name``: each component of the path but
    the last, with transform wrappers stripped; a component that strips
    to nothing (``transpose(jvp())``) names none."""
    return {m.group(1) for part in op_name.split("/")[:-1]
            if (m := _SCOPE.fullmatch(part)) and m.group(1)}


def _overlap(a: tuple[float, float], b: tuple[float, float]) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def reduce(trace: Trace, hlo: str) -> dict | None:
    """Averaged over devices, per step of the window: ``self_ms_per_step``,
    all operations' self time; ``scope_ms_per_step``, the self time under
    each scope the HLO text ``hlo`` names (empty where it names none; an
    operation it does not name counts in the total alone).  Also
    ``data_idle_s``, idle seconds under the program's ``repro.data`` spans
    (None where the trace has none), ``steps``, the window's step spans,
    and ``program_steps``, the ``repro.step`` spans that start inside the
    window (each holds one ``repro.data``).  None without a step span or
    a device operation."""
    steps = [(s, e) for n, s, e in trace.host_spans if n == STEP_SPAN]
    devices = {d: v for d, v in trace.device_ops.items() if v}
    if not steps or not devices:
        return None
    lo, hi = min(s for s, _ in steps), max(e for _, e in steps)
    fetches = [(s, e) for n, s, e in trace.host_spans if n == PROGRAM_DATA]
    data_idle_s = 0.0
    per_op: collections.Counter = collections.Counter()
    for ops in devices.values():
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
                  if min(e, hi) > max(s, lo)]
        for n, dt in trace_reduce.self_times(inside):
            per_op[n] += dt
        busy = trace_reduce.union([(s, e) for _, s, e in inside])
        idle = trace_reduce.gaps(busy, lo, hi)
        data_idle_s += sum(_overlap(g, f) for g in idle for f in fetches)
    names = hlo_op_names(hlo)
    scoped: collections.Counter = collections.Counter()
    for n, t in per_op.items():
        for scope in scope_names(names.get(n, "")):
            scoped[scope] += t
    ms = 1e3 / (len(devices) * len(steps))
    return {
        "steps": len(steps),
        "self_ms_per_step": sum(per_op.values()) * ms,
        "scope_ms_per_step": {k: t * ms for k, t in scoped.items()},
        "data_idle_s": (data_idle_s / len(devices) if fetches else None),
        "program_steps": sum(1 for n, s, _ in trace.host_spans
                             if n == PROGRAM_STEP and lo <= s < hi),
    }


def trace_record(path: str, hlo: str) -> dict | None:
    """What a traced run's record keeps of the trace file at ``path``:
    ``trace_reduce.reduce``'s fields and ``reduce``'s, from one reading;
    None without a step span or a device operation."""
    trace = load(path)
    whole = trace_reduce.reduce(trace)
    return None if whole is None else {**whole, **reduce(trace, hlo)}
