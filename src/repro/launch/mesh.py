"""Production mesh builders (assignment MULTI-POD DRY-RUN step 1).

Functions, not module-level constants, so importing this module never touches
jax device state.  Single pod = 16×16 = 256 chips (data × model); multi-pod
adds a leading "pod" axis: 2×16×16 = 512 chips.
"""

from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()[:n]  # dry-run forces 512 host devices; 1 pod uses 256
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices, have {len(devices)} — run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=512 (dryrun.py sets this)"
        )
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes), devices=devices)
