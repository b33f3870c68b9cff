"""train_single: the program's one-chip training step.

``make_train_step`` jitted with the parameters and optimizer state donated,
as ``Trainer._build`` does; the batch already on the chip.
"""

from __future__ import annotations

import jax
from jax.sharding import SingleDeviceSharding

from bench.training import Built, TrainingRun


def build_step(model, opt_cfg, devices, traffic) -> Built:
    from repro.train.train_step import make_train_step

    jitted = jax.jit(make_train_step(model, opt_cfg), donate_argnums=(0, 1))

    def step(params, opt_state, batch):
        params, opt_state, metrics = jitted(params, opt_state, batch)
        return params, opt_state, metrics["loss"]

    one = SingleDeviceSharding(devices[0])
    return Built(step, one, one, {"jitted": jitted})


def setup(cfg, traffic, seed, devices, wrap=None, **kw) -> TrainingRun:
    """``wrap``, where given, wraps :func:`build_step` (the tests break the
    timed path with it)."""
    build = build_step if wrap is None else wrap(build_step)
    return TrainingRun(cfg, traffic, seed, devices, build, **kw)
