"""Each cell, driven at a small size on CPU devices with its own limits:
the sound run is correct, and the run with each fault planted under the
timed path, and the float8 control, are not."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in harness.benchmark()["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_faults_and_control_come_out_not_correct(name):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    proc = subprocess.run([sys.executable, str(ROOT / "bench" / "tests" / "cells_check.py"), name],
                          cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    cases = {c["case"]: c for c in map(json.loads, proc.stdout.strip().splitlines())}
    expected = {"sound", "unchanged", "half_batch", "control"}
    if harness.traffic(harness.workload(harness.benchmark(), name)["traffic"])["chips"] > 1:
        expected.add("no_exchange")
    assert set(cases) == expected
    assert cases["sound"]["correct"], cases["sound"]
    for case in expected - {"sound"}:
        assert not cases[case]["correct"], cases[case]
