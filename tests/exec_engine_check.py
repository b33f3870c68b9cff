"""Device-level checks for the compiled schedule execution engine.

Run as a subprocess by test_exec_engine.py with 8 host devices (XLA locks
the device count at first jax init, so this cannot share a process with the
single-device suite).  Asserts:

* engine output **bit-identical** to the pre-PR per-round interpreter
  (``execute_schedule_reference`` + dense all-to-all state) for all four
  collectives × their {ring, rhd, dex, direct} algorithms × n ∈ {4, 8},
  on the full axis and on split (two-group) communicators;
* the O(n·blk) slot-addressed all-to-all cross-checks against the dense
  O(n²·blk) path;
* the eager jitted-executable cache: second identical call is a cache hit
  with zero new traces; reductions stay correct through donation.
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.api import PcclSession, subgroup_schedule
from repro.comm import exec_engine
from repro.comm import primitives as prim
from repro.core import cost_model as cm
from repro.core import schedules as S

ALGOS = {
    "reduce_scatter": ("ring", "rhd"),
    "all_gather": ("ring", "rhd"),
    "all_reduce": ("ring", "rhd"),
    "all_to_all": ("dex", "direct", "ring"),
}


def mesh_of(n):
    return Mesh(jax.devices()[:n], ("x",))


def smap(f, mesh, in_specs, out_specs):
    return jax.jit(
        jax.shard_map(
            f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
        )
    )


# ----------------------------------------------------- pre-PR interpreter
# Full-axis oracle: the shared ``primitives.run_reference`` (the original
# wrappers verbatim over the per-round reference executor); the grouped
# variant below exists only here.
ref_collective = prim.run_reference


def ref_grouped(collective, x, sched, axis, me_local, m):
    """Pre-PR grouped path: group-local buffers, dense a2a state."""
    if collective == "reduce_scatter":
        chunks = x.reshape((m, x.shape[0] // m) + x.shape[1:])
        chunks = prim.execute_schedule_reference(chunks, sched, axis)
        return jnp.take(chunks, me_local, axis=0)
    if collective == "all_reduce":
        chunks = x.reshape((m, x.shape[0] // m) + x.shape[1:])
        chunks = prim.execute_schedule_reference(chunks, sched, axis)
        return chunks.reshape(x.shape)
    if collective == "all_gather":
        chunks = jnp.zeros((m,) + x.shape, x.dtype).at[me_local].set(x)
        chunks = prim.execute_schedule_reference(chunks, sched, axis)
        return chunks.reshape((m * x.shape[0],) + x.shape[1:])
    if collective == "all_to_all":
        blocks = x.reshape((m, x.shape[0] // m) + x.shape[1:])
        state = jnp.zeros((m, m) + blocks.shape[1:], blocks.dtype)
        state = state.at[me_local].set(blocks)
        flat = state.reshape((m * m,) + blocks.shape[1:])
        flat = prim.execute_schedule_reference(flat, sched, axis)
        state = flat.reshape((m, m) + blocks.shape[1:])
        return jnp.take(state, me_local, axis=1).reshape(x.shape)
    raise AssertionError(collective)


def engine_collective(collective, x, sched, axis):
    return getattr(prim, collective)(x, sched, axis)


def make_schedule(collective, algo, n, d):
    return S.get_schedule(collective, algo, n, d)


def local_input(collective, n, rng):
    """Per-rank local operand (stacked rank-major into the global array)."""
    if collective == "reduce_scatter":
        return rng.normal(size=(n, n * 3)).astype(np.float32)
    if collective == "all_gather":
        return rng.normal(size=(n, 5)).astype(np.float32)
    if collective == "all_reduce":
        return rng.normal(size=(n, 2 * n)).astype(np.float32)
    return rng.normal(size=(n, n * 2)).astype(np.float32)  # all_to_all


def check_full_axis_bit_identity():
    rng = np.random.default_rng(0)
    for n in (4, 8):
        mesh = mesh_of(n)
        for collective, algos in ALGOS.items():
            X = local_input(collective, n, rng)
            d = float(X.nbytes / n)
            for algo in algos:
                sched = make_schedule(collective, algo, n, d)

                def fe(x):
                    return engine_collective(collective, x[0], sched, "x")[None]

                def fr(x):
                    return ref_collective(collective, x[0], sched, "x")[None]

                oe = np.asarray(smap(fe, mesh, P("x", None), P("x", None))(X))
                orf = np.asarray(smap(fr, mesh, P("x", None), P("x", None))(X))
                np.testing.assert_array_equal(
                    oe, orf, err_msg=f"{collective}/{algo} n={n}"
                )
            print(f"full-axis bit-identity {collective} n={n} OK")


def check_split_bit_identity():
    """Engine grouped path (Communicator.split) vs the pre-PR grouped
    interpreter, on two interleaved groups of 4 over an 8-rank axis."""
    n_axis, m = 8, 4
    mesh = mesh_of(n_axis)
    colors = [r % 2 for r in range(n_axis)]
    groups = ((0, 2, 4, 6), (1, 3, 5, 7))
    local_of = np.zeros(n_axis, np.int32)
    for g in groups:
        for i, r in enumerate(g):
            local_of[r] = i
    local_dev = jnp.asarray(local_of)
    rng = np.random.default_rng(1)
    session = PcclSession(cm.TPU_V5E_PHOTONIC, thread_fabric=False)
    root = session.communicator("x", n_axis, backend="interp")

    for collective, algos in ALGOS.items():
        X = local_input(collective, m, rng)
        X = np.concatenate([X, X[::-1] * 0.5], axis=0)[:n_axis]  # 8 rows
        d = float(X[0].nbytes)
        for algo in algos:
            sub = root.split(colors, algorithm=algo)
            sched = subgroup_schedule(make_schedule(collective, algo, m, d), groups, n_axis)

            def fe(x):
                return getattr(sub, collective)(x[0])[None]

            def fr(x):
                me_local = jnp.take(local_dev, lax.axis_index("x"))
                return ref_grouped(collective, x[0], sched, "x", me_local, m)[None]

            oe = np.asarray(smap(fe, mesh, P("x", None), P("x", None))(X))
            orf = np.asarray(smap(fr, mesh, P("x", None), P("x", None))(X))
            np.testing.assert_array_equal(
                oe, orf, err_msg=f"split {collective}/{algo}"
            )
        print(f"split bit-identity {collective} OK")


def check_compact_vs_dense_all_to_all():
    rng = np.random.default_rng(2)
    for n in (4, 8):
        mesh = mesh_of(n)
        X = rng.normal(size=(n, n * 3)).astype(np.float32)
        d = float(X.nbytes / n)
        for algo in ("dex", "direct", "ring"):
            sched = make_schedule("all_to_all", algo, n, d)
            # the compact compile must actually engage for generated schedules
            assert exec_engine.compile_all_to_all(sched, n, tuple(range(n))) is not None

            def fc(x):
                return prim.all_to_all(x[0], sched, "x")[None]

            def fd(x):
                return prim.all_to_all_dense(x[0], sched, "x")[None]

            oc = np.asarray(smap(fc, mesh, P("x", None), P("x", None))(X))
            od = np.asarray(smap(fd, mesh, P("x", None), P("x", None))(X))
            np.testing.assert_array_equal(oc, od, err_msg=f"a2a {algo} n={n}")
            # and both must satisfy the all-to-all post-condition
            want = X.reshape(n, n, 3).transpose(1, 0, 2).reshape(n, n * 3)
            np.testing.assert_array_equal(oc, want)
        print(f"compact-vs-dense all_to_all n={n} OK")


def check_executable_cache_accounting():
    """Second identical eager call = executable-cache hit, zero retraces."""
    exec_engine.clear_exec_caches()
    n = 8
    # thread_fabric=False keeps the planned schedule deterministic across
    # calls; the executable cache is keyed by fingerprint either way
    session = PcclSession(cm.TPU_V5E_PHOTONIC, thread_fabric=False)
    comm = session.communicator("x", n, backend="interp")
    rng = np.random.default_rng(3)

    X = rng.normal(size=(n, 24)).astype(np.float32)
    out1 = np.asarray(comm.all_reduce(X))
    s1 = exec_engine.exec_stats()
    assert s1.executable_misses == 1 and s1.executable_hits == 0, s1
    assert s1.traces >= 1, s1

    out2 = np.asarray(comm.all_reduce(X))
    s2 = exec_engine.exec_stats()
    assert s2.executable_hits == 1 and s2.executable_misses == 1, s2
    assert s2.traces == s1.traces, (s2, s1)  # 0 retraces on the second call

    want = np.broadcast_to(X.sum(axis=0), X.shape)
    np.testing.assert_allclose(out1, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(out1, out2)

    # a different shape is a genuine miss (and one new trace)
    Y = rng.normal(size=(n, 48)).astype(np.float32)
    comm.all_reduce(Y)
    s3 = exec_engine.exec_stats()
    assert s3.executable_misses == 2 and s3.traces == s2.traces + 1, s3

    # every eager collective round-trips through the cache
    for collective, make in (
        ("reduce_scatter", lambda: rng.normal(size=(n, n * 2)).astype(np.float32)),
        ("all_gather", lambda: rng.normal(size=(n, 3)).astype(np.float32)),
        ("all_to_all", lambda: rng.normal(size=(n, n * 2)).astype(np.float32)),
    ):
        Z = make()
        before = exec_engine.exec_stats()
        o1 = np.asarray(getattr(comm, collective)(Z))
        o2 = np.asarray(getattr(comm, collective)(Z))
        after = exec_engine.exec_stats()
        assert after.executable_hits == before.executable_hits + 1, collective
        assert after.traces == before.traces + 1, collective
        np.testing.assert_array_equal(o1, o2)
    print("executable cache accounting OK")


def check_eager_matches_shard_map():
    """The eager global-operand convention agrees with in-shard_map use."""
    n = 8
    mesh = mesh_of(n)
    session = PcclSession(cm.TPU_V5E_PHOTONIC, thread_fabric=False)
    comm = session.communicator("x", n, backend="interp")
    rng = np.random.default_rng(4)

    X = rng.normal(size=(n, n * 2)).astype(np.float32)
    eager = np.asarray(comm.all_to_all(X))
    traced = np.asarray(
        smap(lambda x: comm.all_to_all(x[0])[None], mesh, P("x", None), P("x", None))(X)
    )
    np.testing.assert_array_equal(eager, traced)

    # a concrete constant used *inside* a shard_map body is mid-trace state,
    # not an eager call — it must route through the trace path (regression:
    # tracer-only dispatch misrouted it to the eager executable builder)
    C = np.arange(n * 2, dtype=np.float32)
    outc = np.asarray(
        smap(
            lambda x: comm.all_reduce(jnp.asarray(C))[None],
            mesh, P("x", None), P("x", None),
        )(X)
    )
    np.testing.assert_allclose(outc[0], C * n, rtol=1e-6)

    # split communicator, eager: per-group reduction
    colors = [r // 4 for r in range(n)]
    sub = comm.split(colors)
    Y = rng.normal(size=(n, 12)).astype(np.float32)
    got = np.asarray(sub.all_reduce(Y))
    want = np.empty_like(Y)
    for g in ((0, 1, 2, 3), (4, 5, 6, 7)):
        s = Y[list(g)].sum(axis=0)
        for r in g:
            want[r] = s
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    print("eager/shard_map parity OK")


def main():
    assert jax.device_count() == 8, jax.devices()
    check_full_axis_bit_identity()
    check_split_bit_identity()
    check_compact_vs_dense_all_to_all()
    check_executable_cache_accounting()
    check_eager_matches_shard_map()
    print("ALL-EXEC-ENGINE-OK")


if __name__ == "__main__":
    main()
