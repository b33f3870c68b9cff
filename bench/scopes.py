"""Device time under the program's named scopes, from a profiler trace.

The program names its phases with ``jax.named_scope``, which sets each HLO
instruction's ``op_name`` metadata: ``train_forward`` (the loss), AD's
``transpose(jvp(train_forward))`` (the backward pass, remat recompute
included), ``train_optimizer`` (the AdamW update) and
``pccl_<collective>_<algorithm>`` (every op the PCCL engine adds to a
step: chunking, padding, and per round group ``group<i>`` the gathers,
``ppermute`` and adds).  The profiler carries an op's ``op_name`` as a stat
of the op's event metadata on the device plane.  This module reads it, with
the rules below written here as literal strings: it imports nothing from
the program, so a renamed scope makes a metric go missing rather than
silently change.

Per device, within the traced window (``bench.window``), as unions of the
intervals of the ops that :mod:`bench.trace_reduce` counts (leaf ``XLA Ops``
events, and async collectives from start to done):

* ``forward``, ``backward``, ``optimizer``: ops whose ``op_name`` matches
  :func:`phase`;
* ``engine``: ops under a ``pccl_*`` scope;
* ``engine_exposed``: the part of ``engine`` that no op outside ``pccl_*``
  covers.

``bench/run.py`` hands a per-layer reader the :class:`TraceSummary` and
not the file, so :func:`for_window` finds the file through the frame of
``run_cell`` that is reducing that summary.  A trace with no ``op_name``
stat, as from a program without scopes, gives no scope time, and the
readers report nothing.
"""

from __future__ import annotations

import re
import sys
from collections import defaultdict
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Tuple

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace_reduce as tr  # noqa: E402

# the stat of an XLA op's event metadata that holds the HLO op_name
OP_NAME_STATS = ("tf_op",)

BACKWARD = "transpose(jvp(train_forward))"
FORWARD = "train_forward"
OPTIMIZER = "train_optimizer"
ENGINE = re.compile(r"(^|/)pccl_")
GROUP = re.compile(r"(?:^|/)(pccl_\w+/group\d+)")
SCOPES = ("forward", "backward", "optimizer", "engine", "engine_exposed")


def phase(op_name: str) -> Optional[str]:
    """``forward``, ``backward`` or ``optimizer`` for an op of the training
    step, else None.  AD names the backward pass ``transpose(jvp(<scope>))``
    of the forward scope, and its remat recompute sits under it."""
    if BACKWARD in op_name:
        return "backward"
    if FORWARD in op_name:
        return "forward"
    if OPTIMIZER in op_name:
        return "optimizer"
    return None


def in_engine(op_name: str) -> bool:
    return bool(ENGINE.search(op_name))


# ----------------------------------------------------------- xplane reading
#
# jax.profiler.ProfileData gives an event's own stats but not those of its
# metadata, where the profiler keeps an op's op_name; so the few fields
# needed are read from the protobuf wire format (tsl/profiler/protobuf/
# xplane.proto: XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4,
# .stat_metadata = 5; XEventMetadata.name = 2, .display_name = 4,
# .stats = 5; XStatMetadata.name = 2; XStat.metadata_id = 1,
# .str_value = 5, .ref_value = 7).  Lines, the bulk of a trace, are skipped
# by their length.


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        c = b[i]
        i += 1
        value |= (c & 0x7F) << shift
        if c < 0x80:
            return value, i
        shift += 7


def _fields(b: bytes, lo: int, hi: int) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of a message in ``b[lo:hi]``; a
    length-delimited value is its ``(start, end)``."""
    i = lo
    while i < hi:
        key, i = _varint(b, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            value, i = (i, i + n), i + n
        elif wire == 1:
            value, i = None, i + 8
        elif wire == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {i}")
        yield num, value


def _text(b: bytes, span) -> str:
    return b[span[0]:span[1]].decode("utf-8", "replace")


def _map_entry(b: bytes, span) -> Tuple[int, Tuple[int, int]]:
    key, value = 0, (span[0], span[0])
    for num, v in _fields(b, *span):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def _plane_op_names(b: bytes, span) -> Tuple[str, Dict[str, str]]:
    """A plane's name and, for each event metadata name that carries an
    ``op_name`` stat, that op_name."""
    name, events, stat_names = "", [], {}
    for num, v in _fields(b, *span):
        if num == 2:
            name = _text(b, v)
        elif num == 4:
            events.append(_map_entry(b, v)[1])
        elif num == 5:
            key, meta = _map_entry(b, v)
            for n, sv in _fields(b, *meta):
                if n == 2:
                    stat_names[key] = _text(b, sv)
    wanted = {k for k, n in stat_names.items() if n in OP_NAME_STATS}
    out: Dict[str, str] = {}
    if not wanted or not name.startswith("/device:"):
        return name, out
    for meta in events:
        names, op_name = [], None
        for num, v in _fields(b, *meta):
            if num in (2, 4):
                names.append(_text(b, v))
            elif num == 5:
                stat_id, value = None, None
                for n, sv in _fields(b, *v):
                    if n == 1:
                        stat_id = sv
                    elif n == 5:
                        value = _text(b, sv)
                    elif n == 7:
                        value = stat_names.get(sv)
                if stat_id in wanted and value:
                    op_name = value
        if op_name:
            out.update((n, op_name) for n in names if n)
    return name, out


def read_op_names(path: str) -> Dict[int, Dict[str, str]]:
    """Per TPU device, event name -> HLO op_name."""
    b = Path(path).read_bytes()
    out = {}
    for num, v in _fields(b, 0, len(b)):
        if num == 1:
            name, names = _plane_op_names(b, v)
            m = tr.DEVICE_PLANE.match(name)
            if m:
                out[int(m.group(1))] = names
    return out


# ---------------------------------------------------------------- reduction


@dataclass
class ScopeSummary:
    scope_ns: Dict[int, Dict[str, float]]  # device -> scope -> ns in the window

    def mean(self, scope: str) -> float:
        return sum(d[scope] for d in self.scope_ns.values()) / len(self.scope_ns)


def scoped_ops(ops_line: List[tr.Event], async_line: List[tr.Event],
               op_names: Dict[str, str]) -> List[Tuple[str, float, float]]:
    """``(op_name, start, end)`` of each op that :func:`bench.trace_reduce.device_ops`
    counts, ``""`` where the trace names no op_name."""
    out = []
    for text, s, e in ops_line:
        if tr.parse_op(text)[1] not in tr.CONTAINERS:
            out.append((op_names.get(text, ""), s, e))
    for text, s, e in async_line:
        name, opcode = tr.parse_op(text)
        if tr.op_class(name, opcode) == "collective":
            out.append((op_names.get(text, ""), s, e))
    return out


def summarize(device_ops: Dict[int, List[Tuple[str, float, float]]],
              window: tr.Interval) -> ScopeSummary:
    lo, hi = window
    scope_ns = {}
    for dev, ops in sorted(device_ops.items()):
        parts: Dict[str, List[tr.Interval]] = defaultdict(list)
        for op_name, s, e in ops:
            for iv in tr.clip([(s, e)], lo, hi):
                if in_engine(op_name):
                    parts["engine"].append(iv)
                else:
                    parts["other"].append(iv)
                    p = phase(op_name)
                    if p:
                        parts[p].append(iv)
        unions = {k: tr.union(v) for k, v in parts.items()}
        d = {k: tr.total(unions.get(k, [])) for k in SCOPES if k != "engine_exposed"}
        d["engine_exposed"] = tr.total(tr.subtract(unions.get("engine", []),
                                                   unions.get("other", [])))
        scope_ns[dev] = d
    return ScopeSummary(scope_ns)


def read_device_ops(path: str) -> Dict[int, List[Tuple[str, float, float]]]:
    """Per TPU device, :func:`scoped_ops` of the trace at ``path``."""
    from jax.profiler import ProfileData

    op_names = read_op_names(path)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: [(e.name, e.start_ns, e.end_ns) for e in ln.events]
                     for ln in plane.lines if ln.name in (tr.OPS_LINE, tr.ASYNC_LINE)}
            dev = int(m.group(1))
            out[dev] = scoped_ops(lines.get(tr.OPS_LINE, []), lines.get(tr.ASYNC_LINE, []),
                                  op_names.get(dev, {}))
    return out


def read_scopes(path: str, window: tr.Interval) -> Optional[ScopeSummary]:
    """The scope times of the trace at ``path`` within ``window``, or None
    where no device op carries an ``op_name``."""
    device_ops = read_device_ops(path)
    if not any(op_name for ops in device_ops.values() for op_name, _, _ in ops):
        return None
    return summarize(device_ops, window)


def group_ns(path: str, window: tr.Interval) -> Dict[int, Dict[str, float]]:
    """Per TPU device, the union of the op intervals within ``window`` under
    each engine round group, keyed ``pccl_<collective>_<algorithm>/group<i>``
    (an async collective from start to done, as in ``engine``)."""
    out = {}
    for dev, ops in read_device_ops(path).items():
        groups: Dict[str, List[tr.Interval]] = defaultdict(list)
        for op_name, s, e in ops:
            g = GROUP.search(op_name)
            if g:
                groups[g.group(1)].extend(tr.clip([(s, e)], *window))
        out[dev] = {k: tr.total(tr.union(v)) for k, v in groups.items()}
    return out


_CACHE: Dict[Tuple[str, tr.Interval], Optional[ScopeSummary]] = {}


def run_cell_trace(summary) -> Optional[str]:
    """The ``.xplane.pb`` behind ``summary``: the ``path`` of the
    ``bench/run.py`` ``run_cell`` frame that reduced it, if any."""
    f = sys._getframe(1)
    while f is not None:
        if (f.f_code.co_name == "run_cell" and f.f_locals.get("summary") is summary
                and Path(f.f_code.co_filename).name == "run.py"):
            return f.f_locals.get("path")
        f = f.f_back
    return None


def for_window(summary) -> Optional[ScopeSummary]:
    """The scope times of the traced window that ``summary`` reduces."""
    path = run_cell_trace(summary)
    if path is None:
        return None
    key = (path, tuple(summary.window))
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = read_scopes(path, summary.window)
    return _CACHE[key]


def ms_per_step(summary, run, scope: str) -> Optional[float]:
    """Device ms a step under ``scope``, averaged over devices; None where
    the trace names no op under it (for ``engine_exposed``: under
    ``engine``)."""
    scopes = for_window(summary)
    held = "engine" if scope == "engine_exposed" else scope
    if scopes is None or run["steps"] == 0 or scopes.mean(held) == 0:
        return None
    return scopes.mean(scope) * 1e-6 / run["steps"]


def main(argv=None) -> int:
    """``python3 bench/scopes.py <window.xplane.pb>``: per device, the ms
    under each scope and each engine round group in the traced window."""
    import json

    (path,) = argv if argv is not None else sys.argv[1:]
    window = tr.read_trace(path).window
    scopes = read_scopes(path, window)
    groups = group_ns(path, window)
    for dev in sorted(groups):
        row = {"device": dev, "window_ms": (window[1] - window[0]) * 1e-6,
               "scope_ms": {k: v * 1e-6 for k, v in (scopes.scope_ns[dev] if scopes else {}).items()},
               "group_ms": {k: v * 1e-6 for k, v in sorted(groups[dev].items())}}
        print(json.dumps(row))
    return 0


if __name__ == "__main__":
    sys.exit(main())
