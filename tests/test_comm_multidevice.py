"""Runs the 8-device collective equivalence suite in a subprocess.

XLA locks the host device count at first jax init, so multi-device checks
must not share a process with the single-device smoke tests (only
dedicated subprocesses force device_count)."""

import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.mark.slow
@pytest.mark.multidevice
def test_multidevice_collectives():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tests" / "multidevice_check.py")],
        capture_output=True,
        text=True,
        env=env,
        timeout=600,
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "ALL-MULTIDEVICE-OK" in proc.stdout
