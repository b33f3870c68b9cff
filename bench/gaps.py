"""Explain the device's idle gaps in a traced window.

    python3 bench/gaps.py <window.xplane.pb> [--top N] [--min-ms M]

``bench/run.py --trace 1 --keep-trace <path>`` keeps the window's trace.
For each of the longest idle gaps of :mod:`bench.trace_reduce` (holes in
a device's busy union), this prints one JSON line: the device, where the
gap starts in the window and how long it is, the host events of any name
that cover it (innermost last, after the ``bench.*`` span), and how much
of it an ``XLA Modules`` event covers.  A gap inside a running module has
no op events in that time: the trace dropped them.  A gap outside every
module is time in which the host had not yet dispatched the next step;
the covering host events say what the host was doing.

The device clock sits about a millisecond off the host's.  Before
labelling, each device's offset is measured: every ``XLA Modules`` event
in the window is paired with the ``bench.step`` host span that dispatched
it, and the median of module start minus span start is taken.
It moves labels only, never the intervals the metrics sum.  The offsets
go to standard error.
"""

from __future__ import annotations

import argparse
import bisect
import json
import statistics
import sys
from pathlib import Path
from typing import Dict, List, Tuple

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace_reduce as tr  # noqa: E402

MODULES_LINE = "XLA Modules"
STEP_SPAN = "bench.step"

Span = Tuple[float, float, str]


def read_spans(path: str) -> Tuple[List[Span], Dict[int, List[Span]]]:
    """Every host event, and each device's ``XLA Modules`` events."""
    from jax.profiler import ProfileData

    host: List[Span] = []
    modules: Dict[int, List[Span]] = {}
    for plane in ProfileData.from_file(path).planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if m:
            modules[int(m.group(1))] = sorted(
                (e.start_ns, e.end_ns, e.name) for line in plane.lines
                if line.name == MODULES_LINE for e in line.events)
        elif plane.name.startswith("/host:"):
            host.extend((e.start_ns, e.end_ns, e.name) for line in plane.lines
                        for e in line.events)
    return sorted(host), modules


def clock_offset(modules: List[Span], host: List[Span], window: tr.Interval) -> float:
    """Median of (module start - start of the ``bench.step`` span that
    dispatched it) over the window's modules; 0 where there is none.  The
    dispatching span is the one that starts nearest the module: steps are
    far longer than the clocks' offset."""
    lo, hi = window
    steps = [s for s, e, n in host if n == STEP_SPAN]
    lags = []
    for s, e, n in modules:
        i = bisect.bisect_left(steps, s)
        near = steps[max(i - 1, 0):i + 1]
        if lo <= s < hi and near:
            lags.append(s - min(near, key=lambda t: abs(s - t)))
    return statistics.median(lags) if lags else 0.0


def covering(host: List[Span], lo: float, hi: float) -> List[Span]:
    """Host events that cover the midpoint of ``[lo, hi)``, outermost first."""
    mid = (lo + hi) / 2
    return sorted(((s, e, n) for s, e, n in host if s <= mid < e),
                  key=lambda x: (x[0], -x[1]))


def label(spans: List[Span]) -> str:
    """The innermost ``bench.*`` span, then the innermost event of any
    other name inside it: ``bench.block/<runtime event>``."""
    bench = [n for s, e, n in spans if n.startswith("bench.") and n != tr.WINDOW_SPAN]
    other = [n for s, e, n in spans if not n.startswith("bench.")]
    parts = ([bench[-1]] if bench else [tr.WINDOW_SPAN]) + ([other[-1]] if other else [])
    return "/".join(parts)


def explain(path: str, top: int = 10, min_ms: float = 0.0) -> List[dict]:
    summary = tr.read_trace(path)
    host, modules = read_spans(path)
    lo, hi = summary.window
    offsets = {dev: clock_offset(modules.get(dev, []), host, summary.window)
               for dev in summary.devices}
    for dev, off in sorted(offsets.items()):
        print(f"[gaps] device {dev}: device clock - host clock ~ {off * 1e-6:.3f} ms "
              f"(module start after its bench.step start, median)", file=sys.stderr)
    gaps = sorted(((e - s, dev, s, e) for dev, d in summary.devices.items() for s, e in d.gaps),
                  reverse=True)
    out = []
    for dur, dev, s, e in gaps[:top]:
        if dur < min_ms * 1e6:
            break
        off = offsets[dev]
        spans = covering(host, s - off, e - off)
        in_module = tr.total(tr.union([(max(ms, s), min(me, e)) for ms, me, _ in modules.get(dev, [])
                                       if me > s and ms < e]))
        out.append({"device": dev, "start_ms": (s - lo) * 1e-6, "ms": dur * 1e-6,
                    "label": label(spans), "host_events": [n for _, _, n in spans],
                    "in_module_ms": in_module * 1e-6})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace")
    ap.add_argument("--top", type=int, default=10)
    ap.add_argument("--min-ms", type=float, default=0.0)
    args = ap.parse_args(argv)
    for row in explain(args.trace, args.top, args.min_ms):
        print(json.dumps(row))
    return 0


if __name__ == "__main__":
    sys.exit(main())
