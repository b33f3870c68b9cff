"""Find a cell's files by the names in ``BENCHMARK.json``.

A configuration is ``bench/configs/<config>.json``, a traffic mix
``bench/traffic/<traffic>.json``, the way of driving the program
``bench/modes/<mode>.py`` (named by the mix), a per-layer metric
``bench/metrics/<metric>.py`` and a cell's correctness limits
``bench/limits/<workload>.json``.  A new cell or metric adds files and
entries; nothing here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class HarnessError(Exception):
    """A cell, file or device that the benchmark cannot run with."""


def load_json(path: Path) -> Dict[str, Any]:
    if not path.is_file():
        raise HarnessError(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def benchmark() -> Dict[str, Any]:
    return load_json(ROOT / "BENCHMARK.json")


def workload(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise HarnessError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> Dict[str, Any]:
    return load_json(BENCH / "configs" / f"{name}.json")


def traffic(name: str) -> Dict[str, Any]:
    return load_json(BENCH / "traffic" / f"{name}.json")


def limits(workload_name: str) -> Dict[str, float]:
    return load_json(BENCH / "limits" / f"{workload_name}.json")["limits"]


def peaks(device_kind: str) -> Dict[str, float]:
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise HarnessError(f"no peaks for device kind {device_kind!r} in bench/peaks.json")
    return table[device_kind]


def _load_module(kind: str, name: str) -> ModuleType:
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise HarnessError(f"missing {path.relative_to(ROOT)}")
    mod_name = f"bench.{kind}.{name.replace('.', '_')}"
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def mode(name: str) -> ModuleType:
    return _load_module("modes", name)


def metric_reader(name: str) -> ModuleType:
    return _load_module("metrics", name)


def metrics_for(entries: List[Dict[str, Any]], workload_name: str) -> List[Dict[str, Any]]:
    """The metrics of ``entries`` that this cell reports."""
    return [m for m in entries if workload_name in m.get("workloads", [workload_name])]


def model_config(cfg: Dict[str, Any]):
    """The program's registry configuration, checked against the file.

    Every key of the file's ``config`` must equal the registry's value, so
    that the cell runs the configuration its file states.
    """
    from repro.configs import get_config

    mc = get_config(cfg["registry"])
    have = dataclasses.asdict(mc)
    wrong = {k: (v, have.get(k)) for k, v in cfg["config"].items() if have.get(k) != v}
    if wrong:
        raise HarnessError(f"{cfg['name']}: registry differs from its file "
                           f"(file, registry): {wrong}")
    return mc
