"""train_dp: the program's pure data-parallel step over the cell's chips.

``make_dp_train_step`` with the gradient of every leaf all-reduced through
a PCCL communicator (``PcclSession(TPU_V5E_PHOTONIC).communicator("data",
n, backend=..., algorithm=...)`` from the traffic mix); parameters and
optimizer state replicated, the global batch split over the chips.
"""

from __future__ import annotations

import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from bench.training import Built, TrainingRun

AXIS = "data"


def build_step(model, opt_cfg, devices, traffic, comm=None) -> Built:
    from repro.api import PcclSession
    from repro.core import cost_model as cm
    from repro.train.train_step import make_dp_train_step

    n = traffic["chips"]
    mesh = Mesh(np.array(devices[:n]), (AXIS,))
    session = None
    if comm is None:
        session = PcclSession(cm.TPU_V5E_PHOTONIC)
        comm = session.communicator(AXIS, n, backend=traffic["backend"],
                                    algorithm=traffic["algorithm"])
    step = make_dp_train_step(model, opt_cfg, comm, mesh)
    return Built(step, NamedSharding(mesh, P()), NamedSharding(mesh, P(AXIS)),
                 {"jitted": step, "session": session})


def setup(cfg, traffic, seed, devices, wrap=None, **kw) -> TrainingRun:
    """``wrap``, where given, wraps :func:`build_step` (the tests break the
    timed path with it)."""
    build = build_step if wrap is None else wrap(build_step)
    return TrainingRun(cfg, traffic, seed, devices, build, **kw)
