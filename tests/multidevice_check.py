"""Multi-device equivalence checks for executable PCCL collectives.

Run as a subprocess by test_comm_multidevice.py with 8 host devices (this
must set XLA_FLAGS before importing jax, which pytest's process cannot do
without polluting single-device tests).  Asserts every schedule-driven
collective matches the XLA reference collective bit-for-bit in fp32.
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

import warnings

import jax
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.api import PcclSession
from repro.comm import primitives as prim
from repro.comm.pccl_collectives import (
    ErrorFeedbackState,
    PcclComm,
    compressed_all_reduce,
    compressed_all_reduce_ef,
)
from repro.core import cost_model as cm
from repro.core import schedules as S

warnings.simplefilter("ignore", DeprecationWarning)  # PcclComm shim coverage

N = 8


def _mesh():
    return Mesh(jax.devices()[:N], ("x",))


def _smap(f, mesh, in_specs, out_specs):
    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False))


def check_reduce_scatter():
    mesh = _mesh()
    rng = np.random.default_rng(0)
    X = rng.normal(size=(N, N * 6)).astype(np.float32)  # rank-major addends

    for algo, sched in [
        ("ring", S.ring_reduce_scatter(N, X.nbytes / N)),
        ("rhd", S.rhd_reduce_scatter(N, X.nbytes / N)),
    ]:
        def f(x):
            return prim.reduce_scatter(x[0], sched, "x")[None]

        out = _smap(f, mesh, P("x", None), P("x", None))(X)
        want = X.sum(axis=0).reshape(N, 6)  # chunk c belongs to rank c
        np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-6)
        print(f"reduce_scatter/{algo} OK")


def check_all_gather():
    mesh = _mesh()
    rng = np.random.default_rng(1)
    X = rng.normal(size=(N * 5,)).astype(np.float32)

    for algo, sched in [
        ("ring", S.ring_all_gather(N, X.nbytes)),
        ("rhd", S.rhd_all_gather(N, X.nbytes)),
    ]:
        def f(x):
            return prim.all_gather(x, sched, "x")

        out = _smap(f, mesh, P("x"), P(None))(X)
        np.testing.assert_allclose(np.asarray(out), X, rtol=0)
        print(f"all_gather/{algo} OK")


def check_all_reduce():
    mesh = _mesh()
    rng = np.random.default_rng(2)
    X = rng.normal(size=(N, 40)).astype(np.float32)

    for algo, sched in [
        ("ring", S.ring_all_reduce(N, X.nbytes / N)),
        ("rhd", S.rhd_all_reduce(N, X.nbytes / N)),
        ("bucket2d", S.bucket_all_reduce((2, 4), X.nbytes / N)),
    ]:
        def f(x):
            return prim.all_reduce(x[0], sched, "x")

        out = _smap(f, mesh, P("x", None), P(None))(X)
        np.testing.assert_allclose(np.asarray(out), X.sum(axis=0), rtol=1e-5, atol=1e-6)
        print(f"all_reduce/{algo} OK")


def check_all_to_all():
    mesh = _mesh()
    rng = np.random.default_rng(3)
    B = 3
    X = rng.normal(size=(N, N * B)).astype(np.float32)  # [rank, dest-major]

    for algo, sched in [
        ("dex", S.dex_all_to_all(N, X.nbytes / N)),
        ("direct", S.direct_all_to_all(N, X.nbytes / N)),
        ("ring", S.ring_all_to_all(N, X.nbytes / N)),
    ]:
        def f(x):
            return prim.all_to_all(x[0], sched, "x")[None]

        out = np.asarray(_smap(f, mesh, P("x", None), P("x", None))(X))
        want = (
            X.reshape(N, N, B).transpose(1, 0, 2).reshape(N, N * B)
        )  # block (s -> t) lands at rank t, origin-major
        np.testing.assert_allclose(out, want, rtol=0)
        print(f"all_to_all/{algo} OK")


def check_pccl_comm_api():
    mesh = _mesh()
    rng = np.random.default_rng(4)
    X = rng.normal(size=(N, 64)).astype(np.float32)
    comm = PcclComm(axis_name="x", n=N)
    assert comm.chosen_algorithm("all_reduce", 64 * 4) in ("rhd", "ring", "bucket2d", "bucket3d")

    def f(x):
        return comm.all_reduce(x[0])

    out = _smap(f, mesh, P("x", None), P(None))(X)
    np.testing.assert_allclose(np.asarray(out), X.sum(axis=0), rtol=1e-5, atol=1e-6)

    comm_xla = PcclComm(axis_name="x", n=N, algorithm="xla")

    def g(x):
        return comm_xla.all_reduce(x[0])

    out2 = _smap(g, mesh, P("x", None), P(None))(X)
    np.testing.assert_allclose(np.asarray(out2), X.sum(axis=0), rtol=1e-5, atol=1e-6)
    print("PcclComm API OK")


def check_compressed_all_reduce():
    mesh = _mesh()
    rng = np.random.default_rng(5)
    X = rng.normal(size=(N, N * 16)).astype(np.float32)

    def f(x):
        return compressed_all_reduce(x[0], "x", N)

    out = np.asarray(_smap(f, mesh, P("x", None), P(None))(X))
    want = X.sum(axis=0)
    rel = np.abs(out - want) / (np.abs(want) + 1e-6)
    assert np.median(rel) < 0.05, f"median rel err {np.median(rel)}"

    # error feedback: mean residual-compensated error over repeated reduces of
    # the SAME gradient should shrink vs no-EF (bias correction property)
    def g(x, r):
        red, ef = compressed_all_reduce_ef(x[0], ErrorFeedbackState(r[0]), "x", N)
        return red, ef.residual[None]

    r = np.zeros_like(X)
    accum_ef = np.zeros_like(want)
    accum_raw = np.zeros_like(want)
    steps = 8
    for _ in range(steps):
        red, r = _smap(g, mesh, (P("x", None), P("x", None)), (P(None), P("x", None)))(X, r)
        accum_ef += np.asarray(red)
        accum_raw += out
    err_ef = np.abs(accum_ef / steps - want).mean()
    err_raw = np.abs(accum_raw / steps - want).mean()
    assert err_ef <= err_raw * 1.05, (err_ef, err_raw)
    print("compressed_all_reduce OK")


def check_session_backend_parity():
    """interp and xla backends of the same Communicator agree numerically."""
    mesh = _mesh()
    rng = np.random.default_rng(6)
    session = PcclSession(cm.TPU_V5E_PHOTONIC)
    interp = session.communicator("x", N, backend="interp")
    xla = session.communicator("x", N, backend="xla")

    # all_reduce
    X = rng.normal(size=(N, 48)).astype(np.float32)
    oi = _smap(lambda x: interp.all_reduce(x[0]), mesh, P("x", None), P(None))(X)
    ox = _smap(lambda x: xla.all_reduce(x[0]), mesh, P("x", None), P(None))(X)
    np.testing.assert_allclose(np.asarray(oi), np.asarray(ox), rtol=1e-5, atol=1e-6)

    # reduce_scatter
    Y = rng.normal(size=(N, N * 4)).astype(np.float32)
    ri = _smap(lambda x: interp.reduce_scatter(x[0])[None], mesh, P("x", None), P("x", None))(Y)
    rx = _smap(lambda x: xla.reduce_scatter(x[0])[None], mesh, P("x", None), P("x", None))(Y)
    np.testing.assert_allclose(np.asarray(ri), np.asarray(rx), rtol=1e-5, atol=1e-6)

    # all_to_all
    Z = rng.normal(size=(N, N * 2)).astype(np.float32)
    ai = _smap(lambda x: interp.all_to_all(x[0])[None], mesh, P("x", None), P("x", None))(Z)
    ax = _smap(lambda x: xla.all_to_all(x[0])[None], mesh, P("x", None), P("x", None))(Z)
    np.testing.assert_allclose(np.asarray(ai), np.asarray(ax), rtol=0)

    # xla never plans; interp planned each collective exactly once
    assert session.stats.misses == 3 and session.stats.size == 3, session.stats
    print("session backend parity OK")


def check_communicator_split():
    """split(color) sub-groups reduce within each group only, on both
    backends (hierarchical DP×TP pattern)."""
    mesh = _mesh()
    rng = np.random.default_rng(7)
    session = PcclSession(cm.TPU_V5E_PHOTONIC)
    root = session.communicator("x", N, backend="interp")
    colors = [r % 2 for r in range(N)]  # two interleaved groups of 4

    X = rng.normal(size=(N, 24)).astype(np.float32)
    want = np.empty_like(X)
    for g in ((0, 2, 4, 6), (1, 3, 5, 7)):
        s = X[list(g)].sum(axis=0)
        for r in g:
            want[r] = s

    for backend in ("interp", "xla"):
        sub = root.split(colors, backend=backend)
        assert sub.n == 4 and sub.groups == ((0, 2, 4, 6), (1, 3, 5, 7))
        out = _smap(lambda x: sub.all_reduce(x[0])[None], mesh, P("x", None), P("x", None))(X)
        np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-6)

        # group-local all_gather: each rank gathers its group's shards
        Y = rng.normal(size=(N, 3)).astype(np.float32)
        wg = np.empty((N, 12), np.float32)
        for g in sub.groups:
            cat = np.concatenate([Y[r] for r in g])
            for r in g:
                wg[r] = cat
        og = _smap(lambda y: sub.all_gather(y[0])[None], mesh, P("x", None), P("x", None))(Y)
        np.testing.assert_allclose(np.asarray(og), wg, rtol=0)
        print(f"communicator split/{backend} OK")


def main():
    assert jax.device_count() == N, jax.devices()
    check_reduce_scatter()
    check_all_gather()
    check_all_reduce()
    check_all_to_all()
    check_pccl_comm_api()
    check_compressed_all_reduce()
    check_session_backend_parity()
    check_communicator_split()
    print("ALL-MULTIDEVICE-OK")


if __name__ == "__main__":
    main()
