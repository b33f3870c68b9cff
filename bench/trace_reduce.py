"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics read.

On the TPU each device plane (``/device:TPU:<n>``) has a line ``XLA Ops``
whose events are named by their HLO instruction text
(``%fusion.12 = bf16[...] fusion(...)``), with a ``while`` loop's event
covering the events of its body, and a line ``Async XLA Ops`` whose events
run from an async ``-start`` to its ``-done``.  The operations become
intervals, classed by opcode as collective (collective-permute, all-reduce,
all-gather, reduce-scatter, all-to-all, send, recv, with their ``-start``
and ``-done``; an async collective counts from start to done) or compute
(every other leaf operation; ``while``, ``conditional`` and ``call`` are
containers and count only through their bodies).  Within the traced
window, given by the benchmark's own host span ``bench.window``, the
reduction gives per device:

* busy time: the union of all operation intervals;
* compute and collective time: the union of each class;
* exposed collective time: the part of the collective union that no
  compute operation covers;
* idle gaps: the holes in the busy union, each labelled with the innermost
  ``bench.*`` host span that covers its midpoint.  The profiler puts the
  device's clock on the host's to about a millisecond, so a label is
  coarse for gaps that short.
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
WINDOW_SPAN = "bench.window"
CONTAINERS = {"while", "conditional", "call"}
_HLO = re.compile(r"^%?([^\s=]+) = .*? ([a-z][a-z0-9-]*)\(")
_COLLECTIVE = re.compile(
    r"^(collective-permute|all-reduce|all-gather|reduce-scatter|all-to-all|send|recv)"
    r"(-start|-done)?(\.\d+)?$"
)


def parse_op(event_name: str) -> Tuple[str, str]:
    """``(instruction name, opcode)`` of an ``XLA Ops`` event.  A bare name
    (no HLO text) is its own opcode, less a numeric suffix."""
    m = _HLO.match(event_name)
    if m:
        return m.group(1), m.group(2)
    return event_name, re.sub(r"\.\d+$", "", event_name)


def op_class(name: str, opcode: str = "") -> str:
    """A collective by its opcode, or by its name where the opcode is a
    generic ``async-start``/``async-done`` wrapper."""
    if _COLLECTIVE.match(opcode or name):
        return "collective"
    if opcode in ("async-start", "async-done") and _COLLECTIVE.match(name):
        return "collective"
    return "compute"


def union(intervals: List[Interval]) -> List[Interval]:
    merged: List[Interval] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def total(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """``a`` minus ``b``; both are sorted, disjoint unions."""
    out: List[Interval] = []
    j = 0
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


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


@dataclass
class DeviceSummary:
    busy_ns: float
    compute_ns: float
    collective_ns: float
    exposed_collective_ns: float
    gaps: List[Interval]
    op_ns: Dict[str, float]


@dataclass
class TraceSummary:
    window: Interval
    devices: Dict[int, DeviceSummary]
    host_spans: List[Tuple[float, float, str]] = field(default_factory=list)

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def mean(self, attr: str) -> float:
        return sum(getattr(d, attr) for d in self.devices.values()) / len(self.devices)

    def label(self, t: float) -> str:
        """The innermost ``bench.*`` host span that covers time ``t``."""
        best: Optional[Tuple[float, float, str]] = None
        for s, e, name in self.host_spans:
            if s <= t < e and name != WINDOW_SPAN and (best is None or s >= best[0]):
                best = (s, e, name)
        return best[2] if best else WINDOW_SPAN

    def idle_gaps(self, top: int = 10) -> List[Tuple[str, float]]:
        """The longest device idle gaps of the window, over all devices, in seconds."""
        gaps = [(e - s, s, e) for d in self.devices.values() for s, e in d.gaps]
        gaps.sort(reverse=True)
        return [[self.label((s + e) / 2), dur * 1e-9] for dur, s, e in gaps[:top]]

    def device_ops(self, top: int = 10) -> List[Tuple[str, float]]:
        """Operations that took most device time in the window, in seconds
        averaged over devices."""
        agg: Dict[str, float] = defaultdict(float)
        for d in self.devices.values():
            for name, ns in d.op_ns.items():
                agg[name] += ns / len(self.devices)
        ranked = sorted(agg.items(), key=lambda kv: -kv[1])[:top]
        return [[name, ns * 1e-9] for name, ns in ranked]


Event = Tuple[str, float, float]


def device_ops(ops_line: List[Event], async_line: List[Event]) -> List[Tuple[str, str, float, float]]:
    """``(name, class, start, end)`` of each leaf operation of one device,
    and of each async collective from its start to its done."""
    out = []
    for text, s, e in ops_line:
        name, opcode = parse_op(text)
        if opcode not in CONTAINERS:
            out.append((name, op_class(name, opcode), s, e))
    for text, s, e in async_line:
        name, opcode = parse_op(text)
        if op_class(name, opcode) == "collective":
            out.append((f"{name} start-to-done", "collective", s, e))
    return out


def summarize(device_events: Dict[int, List[Tuple[str, str, float, float]]],
              host_spans: List[Tuple[float, float, str]]) -> TraceSummary:
    windows = [(s, e) for s, e, name in host_spans if name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} host span")
    lo, hi = windows[0]
    devices = {}
    for dev, ops in sorted(device_events.items()):
        comp, coll = [], []
        op_ns: Dict[str, float] = defaultdict(float)
        for name, cls, s, e in ops:
            for cs, ce in clip([(s, e)], lo, hi):
                (coll if cls == "collective" else comp).append((cs, ce))
                op_ns[name] += ce - cs
        comp_u, coll_u = union(comp), union(coll)
        busy = union(comp_u + coll_u)
        devices[dev] = DeviceSummary(
            busy_ns=total(busy),
            compute_ns=total(comp_u),
            collective_ns=total(coll_u),
            exposed_collective_ns=total(subtract(coll_u, comp_u)),
            gaps=subtract([(lo, hi)], busy),
            op_ns=dict(op_ns),
        )
    if not devices:
        raise ValueError("trace has no device plane with operations")
    return TraceSummary((lo, hi), devices, sorted(host_spans))


def read_trace(path: str) -> TraceSummary:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_events: Dict[int, List[Tuple[str, str, float, float]]] = {}
    host_spans: List[Tuple[float, float, str]] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {OPS_LINE: [], ASYNC_LINE: []}
            for line in plane.lines:
                if line.name in lines:
                    lines[line.name] = [(e.name, e.start_ns, e.end_ns) for e in line.events]
            device_events[int(m.group(1))] = device_ops(lines[OPS_LINE], lines[ASYNC_LINE])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_spans.extend((e.start_ns, e.end_ns, e.name) for e in line.events
                                  if e.name.startswith("bench."))
    return summarize(device_events, host_spans)
