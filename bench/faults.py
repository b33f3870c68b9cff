"""Faults planted under the timed path, to show that the check catches them.

Each wraps a mode's ``build_step`` and breaks the step it returns:

* ``unchanged``: the step returns the state it was given (and the loss);
* ``half_batch``: the step sees only the first half of the batch's rows,
  so the mean is taken over those;
* ``no_exchange``: the data-parallel step's gradient all-reduce leaves out
  the exchange, each chip keeping its own gradient.

``bench/calibrate.py`` reads them on the chip; the tests read them on the
CPU at a small size.
"""

from __future__ import annotations

import jax


def unchanged(build_step):
    def build(model, opt_cfg, devices, traffic, **kw):
        built = build_step(model, opt_cfg, devices, traffic, **kw)
        loss = jax.jit(lambda p, batch: model.loss(p, batch)[0])
        return built._replace(step=lambda p, o, batch: (p, o, loss(p, batch)))
    return build


def half_batch(build_step):
    def build(model, opt_cfg, devices, traffic, **kw):
        built = build_step(model, opt_cfg, devices, traffic, **kw)

        def step(p, o, batch):
            return built.step(p, o, {k: v[: v.shape[0] // 2] for k, v in batch.items()})
        return built._replace(step=step)
    return build


class LocalOnly:
    """A communicator whose all-reduce exchanges nothing: each rank's own
    value, scaled as if all ranks had sent the same."""

    def __init__(self, axis_name: str, n: int):
        self.axis_name, self.n = axis_name, n

    def all_reduce(self, x):
        return x * self.n


def no_exchange(build_step):
    def build(model, opt_cfg, devices, traffic, **kw):
        comm = LocalOnly("data", traffic["chips"])
        return build_step(model, opt_cfg, devices, traffic, comm=comm, **kw)
    return build


FAULTS = {"unchanged": unchanged, "half_batch": half_batch, "no_exchange": no_exchange}


def faults_for(traffic) -> list:
    """The faults a cell driven by this mix can have."""
    names = ["unchanged", "half_batch"]
    if traffic["chips"] > 1:
        names.append("no_exchange")
    return names
