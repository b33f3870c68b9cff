"""Device time under the encoder-decoder's named scopes, from a profiler trace.

The program's encoder-decoder runs its encoder (frames and positions
through the encoder layers and their final norm) under
``encdec_encoder``, and each decoder layer's cross-attention (its norm,
the K/V projection of the frames, the attention, its residual add) under
``encdec_cross_attention``; the PCCL engine's ops lie under ``pccl_*``.
AD keeps a scope's name in the ops it derives, so the remat recompute and
the backward pass fall under the same scope as the forward.  The rules
are literal strings of this module; like :mod:`bench.scopes`, whose
readers it calls, it imports nothing from the program, so a renamed scope
makes a metric go missing rather than silently change.

Per device, within the traced window, as unions of the intervals of the
ops that :func:`bench.scopes.read_device_ops` gives (leaf ``XLA Ops``
events, async collectives from start to done):

* ``encoder``: ops whose ``op_name`` holds ``encdec_encoder``;
* ``cross_attention``: ops whose ``op_name`` holds ``encdec_cross_attention``;
* ``engine``: ops under a ``pccl_*`` scope.

A trace in which no op carries one of these names gives nothing for that
part, and its reader reports nothing.
"""

from __future__ import annotations

import re
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import scopes  # noqa: E402
from bench import trace_reduce as tr  # noqa: E402

ENCODER = "encdec_encoder"
CROSS_ATTENTION = "encdec_cross_attention"
ENGINE = re.compile(r"(^|/)pccl_")
PARTS = ("encoder", "cross_attention", "engine")


def part(op_name: str) -> Optional[str]:
    """The part of the step ``op_name`` belongs to, or None."""
    if ENGINE.search(op_name):
        return "engine"
    if ENCODER in op_name:
        return "encoder"
    if CROSS_ATTENTION in op_name:
        return "cross_attention"
    return None


def summarize(device_ops: Dict[int, List[Tuple[str, float, float]]],
              window: tr.Interval) -> Dict[int, Dict[str, float]]:
    """Per device, ns of the window under each of :data:`PARTS`."""
    out = {}
    for dev, ops in sorted(device_ops.items()):
        parts: Dict[str, List[tr.Interval]] = defaultdict(list)
        for op_name, s, e in ops:
            p = part(op_name)
            if p:
                parts[p].extend(tr.clip([(s, e)], *window))
        out[dev] = {p: tr.total(tr.union(parts[p])) for p in PARTS}
    return out


_CACHE: Dict[Tuple[str, tr.Interval], Dict[int, Dict[str, float]]] = {}


def for_window(summary) -> Optional[Dict[int, Dict[str, float]]]:
    """The part times of the traced window that ``summary`` reduces, or
    None outside ``bench/run.py``'s ``run_cell``."""
    path = scopes.run_cell_trace(summary)
    if path is None:
        return None
    key = (path, tuple(summary.window))
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = summarize(scopes.read_device_ops(path), summary.window)
    return _CACHE[key]


def ms_per_step(summary, run, name: str) -> Optional[float]:
    """Device ms a step under part ``name``, averaged over devices; None
    where the trace names no op under it."""
    parts = for_window(summary)
    if not parts or run["steps"] == 0:
        return None
    mean = sum(d[name] for d in parts.values()) / len(parts)
    return mean * 1e-6 / run["steps"] if mean else None
