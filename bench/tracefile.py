"""Read a JAX profiler trace (``.xplane.pb``) into plain intervals.

Device planes (``/device:TPU:<n>``) give the operations that ran on
each chip (line ``XLA Ops``) and the programs they belonged to (line
``XLA Modules``).  An operation's event is named by its HLO text
(``%copy.82 = bf16[...] copy(...)``); it is kept as its instruction
name (``copy.82``) and opcode (``copy``).  Loops (``while``) and other
containers are events too, spanning the operations inside them.  Host planes carry the benchmark's clock marker: a
``TraceAnnotation`` named :data:`CLOCK_MARK` opened at a known
``time.perf_counter`` reading, which puts every event on that clock.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

CLOCK_MARK = "bench.clock"
CONTAINERS = ("while", "conditional", "call")
_DEVICE_RE = re.compile(r"^/device:TPU:(\d+)$")
_OPCODE_RE = re.compile(r"(?<![\w.-])([a-z][a-z0-9-]*)\(")


@dataclass
class Trace:
    # per device index, on the perf_counter clock:
    # ops [(name, opcode, t0, t1)], modules [(name, t0, t1)]
    ops: Dict[int, List[Tuple[str, str, float, float]]] = field(
        default_factory=dict)
    modules: Dict[int, List[Tuple[str, float, float]]] = field(
        default_factory=dict)


def split_hlo(text: str) -> Tuple[str, str]:
    """``%name = shape opcode(...)`` -> (name, opcode)."""
    if " = " not in text:
        return text, ""
    name, rest = text.split(" = ", 1)
    m = _OPCODE_RE.search(rest)
    return name.lstrip("%"), (m.group(1) if m else "")


def union_length(intervals, a: float, b: float) -> float:
    """Length of the union of ``(t0, t1)`` intervals clipped to [a, b]."""
    total, end = 0.0, a
    for t0, t1 in sorted((max(s, a), min(e, b)) for s, e in intervals):
        if t1 <= end:
            continue
        total += t1 - max(t0, end)
        end = t1
    return total


def gaps(intervals, a: float, b: float) -> List[Tuple[float, float]]:
    """Maximal sub-intervals of [a, b] that no interval covers."""
    out, end = [], a
    for t0, t1 in sorted(intervals):
        if t0 > end:
            out.append((end, min(t0, b)))
        end = max(end, t1)
        if end >= b:
            break
    if end < b:
        out.append((end, b))
    return [(s, e) for s, e in out if e > s]


def load(src, clock_pc: float) -> Trace:
    """``src``: a trace file's path, or its bytes.  ``clock_pc``: the
    perf_counter reading taken inside the :data:`CLOCK_MARK`
    annotation."""
    from jax.profiler import ProfileData
    pd = (ProfileData.from_serialized_xspace(src)
          if isinstance(src, bytes) else ProfileData.from_file(src))
    mark_ns = None
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == CLOCK_MARK:
                    mark_ns = ev.start_ns
                    break
    if mark_ns is None:
        raise ValueError(f"no {CLOCK_MARK} event in the trace")
    off = clock_pc - mark_ns * 1e-9
    tr = Trace()
    for plane in pd.planes:
        m = _DEVICE_RE.match(plane.name)
        if not m:
            continue
        dev = int(m.group(1))
        for line in plane.lines:
            if line.name == "XLA Ops":
                evs = tr.ops.setdefault(dev, [])
                for ev in line.events:
                    t0 = ev.start_ns * 1e-9 + off
                    evs.append((*split_hlo(ev.name), t0,
                                t0 + ev.duration_ns * 1e-9))
            elif line.name == "XLA Modules":
                evs = tr.modules.setdefault(dev, [])
                for ev in line.events:
                    t0 = ev.start_ns * 1e-9 + off
                    evs.append((ev.name, t0, t0 + ev.duration_ns * 1e-9))
    for v in tr.ops.values():
        v.sort(key=lambda e: e[2])
    for v in tr.modules.values():
        v.sort(key=lambda e: e[1])
    return tr
