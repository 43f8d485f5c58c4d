"""Reduce a profiler trace (``.xplane.pb``) to the intervals the per-layer
readers need, with nothing but ``jax.profiler.ProfileData``.

Device planes are ``/device:TPU:<n>``: their ``XLA Ops`` line holds one
event per operation executed (the event's name is the HLO instruction's
text), ``XLA Modules`` one per program executed.  Host spans are the
benchmark's own ``TraceAnnotation`` events on the ``/host:CPU`` plane.  Both
are on the trace's one clock; the device's events are placed on it by the
profiler, which agrees with the host's to about a millisecond.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # seconds on the trace's clock

# a control-flow op's event spans the events of the ops its body runs
CONTAINERS = ("while", "conditional", "call")
_OPCODE = re.compile(r" ([a-z][a-z\-]*)\(")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_MODULE = re.compile(r"\(\d+\)$")


@dataclasses.dataclass(frozen=True)
class Op:
    start: float
    end: float
    name: str          # the instruction's name, e.g. "fusion.12"
    opcode: str        # e.g. "fusion", "while"

    @property
    def container(self) -> bool:
        return self.opcode in CONTAINERS


@dataclasses.dataclass
class Device:
    id: int
    ops: List[Op]
    modules: List[Tuple[float, float, str]]


@dataclasses.dataclass
class Trace:
    devices: Dict[int, Device]
    spans: List[Tuple[float, float, str]]      # host annotations

    def window(self) -> Optional[Interval]:
        """From the first annotation's start to the last one's end."""
        if not self.spans:
            return None
        return min(s for s, _, _ in self.spans), max(e for _, e, _ in self.spans)


def parse_op(text: str) -> Tuple[str, str]:
    """(name, opcode) of an HLO instruction's text."""
    name = text[1:].split(" ", 1)[0] if text.startswith("%") else text.split(" ", 1)[0]
    rhs = text.split(" = ", 1)[1] if " = " in text else text
    m = _OPCODE.search(" " + rhs)
    return name, (m.group(1) if m else name)


def load(path: str, annotations: Sequence[str]) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[int, Device] = {}
    spans: List[Tuple[float, float, str]] = []
    wanted = set(annotations)
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if m:
            dev = Device(int(m.group(1)), [], [])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        name, opcode = parse_op(e.name)
                        s = e.start_ns * 1e-9
                        dev.ops.append(Op(s, s + e.duration_ns * 1e-9, name, opcode))
                elif line.name == "XLA Modules":
                    for e in line.events:
                        s = e.start_ns * 1e-9
                        dev.modules.append((s, s + e.duration_ns * 1e-9, _MODULE.sub("", e.name)))
            dev.ops.sort(key=lambda o: o.start)
            dev.modules.sort()
            devices[dev.id] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        s = e.start_ns * 1e-9
                        spans.append((s, s + e.duration_ns * 1e-9, e.name))
    spans.sort()
    return Trace(devices, spans)


# -- interval arithmetic ------------------------------------------------------


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the disjoint sorted ``a`` not covered by the disjoint sorted ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def intersect_total(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    return total(a) - total(subtract(a, b))


# -- readings -----------------------------------------------------------------


def busy(dev: Device, lo: float, hi: float) -> List[Interval]:
    """Where some operation runs on the device, inside [lo, hi]."""
    return clip(union((o.start, o.end) for o in dev.ops), lo, hi)


def executions(dev: Device, module: str, lo: float, hi: float) -> List[Interval]:
    """Executions of the program named ``module`` (e.g. ``jit__decode``)
    that start inside [lo, hi]."""
    return [(s, e) for s, e, n in dev.modules if n == module and lo <= s < hi]


def top_ops(trace: Trace, lo: float, hi: float, n: int = 10) -> List[List]:
    """The device operations that took most time, summed over devices and
    executions, named ``<program>/<instruction>``; a loop counts through the
    operations of its body, not as one."""
    acc: Dict[str, float] = {}
    for dev in trace.devices.values():
        mods = dev.modules
        j = 0
        for o in dev.ops:
            if o.end <= lo or o.start >= hi or o.container:
                continue
            while j + 1 < len(mods) and mods[j + 1][0] <= o.start:
                j += 1
            prog = mods[j][2] if mods and mods[j][0] <= o.start <= mods[j][1] else "?"
            key = f"{prog}/{o.name}"
            acc[key] = acc.get(key, 0.0) + (min(o.end, hi) - max(o.start, lo))
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_by_host(trace: Trace, lo: float, hi: float, n: int = 10) -> List[List]:
    """Idle device time in [lo, hi], mean over devices, split by the
    innermost host annotation open at the middle of each gap ("none" where
    the host was inside none)."""
    acc: Dict[str, float] = {}
    for dev in trace.devices.values():
        for s, e in subtract([(lo, hi)], busy(dev, lo, hi)):
            mid = 0.5 * (s + e)
            label = "none"
            for a, b, name in trace.spans:
                if a <= mid < b:
                    label = name
                elif a > mid:
                    break
            acc[label] = acc.get(label, 0.0) + (e - s) / len(trace.devices)
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]
