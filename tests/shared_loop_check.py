"""Device-level checks of the engine's shared round loop, on 4 CPU devices.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python tests/shared_loop_check.py

Run as a subprocess by ``test_exec_engine.py`` (XLA locks the device count
at first init).  Prints one JSON line per check, ``{"check", "ok",
"detail"}``:

* ``tree_matches_per_leaf``: a pytree all-reduce through the shared loop
  (leaves that need padding, a single element, bf16, two communicators
  with different algorithms so that two loops run) is bit-identical to
  ``comm.all_reduce`` of each leaf;
* ``tree_matches_reference``: ... and to ``primitives.run_reference``;
* ``tree_mixed_paths``: a tree whose leaves plan two algorithms, one of
  them the per-leaf ``ring_ef8``, still matches leaf by leaf;
* ``tree_eager``: a concrete tree outside any trace runs leaf by leaf;
* ``xla_and_sim_trees``: the ``xla`` and ``sim`` backends give the same
  tree shapes, and ``sim`` charges the per-leaf sum;
* ``dp_step_bitwise``: one ``make_dp_train_step`` step with the tree call
  gives the parameters, optimizer state and loss of the per-leaf call;
* ``dp_step_counter``: ``shared_loop_buffers`` counts the step's leaves,
  and ``PcclSession.exec_stats()`` shows it;
* ``dp_step_bitwise_encdec``, ``dp_step_counter_encdec``: the same two for
  a small whisper-small, whose 32 leaves all share the loop;
* ``one_buffer_hlo_<case>``: a one-buffer ``execute_compiled`` compiles to
  the optimised HLO of the engine before the shared loop existed (kept
  below as ``single_buffer_engine``), instruction names and metadata
  aside.
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=4 " + os.environ.get("XLA_FLAGS", "")
)

import json  # noqa: E402
import re  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402
from jax.sharding import Mesh  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.api import PcclSession  # noqa: E402
from repro.comm import exec_engine  # noqa: E402
from repro.comm import primitives as prim  # noqa: E402
from repro.core import cost_model as cm  # noqa: E402
from repro.core import schedules as S  # noqa: E402

from check_helpers import canonical_hlo, small_model  # noqa: E402

N = 4
AXIS = "x"
CHECKS = []


def check(fn):
    CHECKS.append(fn)
    return fn


def smap(f, in_specs, out_specs):
    mesh = Mesh(np.array(jax.devices()[:N]), (AXIS,))
    return jax.jit(
        jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                      check_vma=False)
    )


def comm_of(algorithm="auto", hw=cm.TPU_V5E_PHOTONIC, **kw):
    return PcclSession(hw, thread_fabric=False).communicator(
        AXIS, N, backend="interp", algorithm=algorithm, **kw)


def global_tree(rng):
    """Per-rank leaves, stacked rank-major: (N, *local)."""
    return {
        "padded": rng.normal(size=(N, 37, 5)).astype(np.float32),  # 185 % 4 == 1
        "single": rng.normal(size=(N,)).astype(np.float32),  # local shape ()
        "lanes": [rng.normal(size=(N, 16, 512)).astype(np.float32),
                  rng.normal(size=(N, 8, 96)).astype(jnp.bfloat16)],
    }


def run_tree(fn, tree):
    """``fn(local tree)`` on every rank; returns the global result tree."""
    return smap(lambda t: jax.tree.map(lambda y: y[None], fn(jax.tree.map(lambda x: x[0], t))),
                (P(AXIS),), P(AXIS))(tree)


def assert_trees_equal(a, b):
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb, (ta, tb)
    for x, y in zip(la, lb):
        x, y = np.atleast_1d(np.asarray(x)), np.atleast_1d(np.asarray(y))
        assert x.shape == y.shape and x.dtype == y.dtype, (x.shape, y.shape)
        assert np.array_equal(x.view(np.uint8), y.view(np.uint8)), "not bit-identical"


def reference_leaf(comm, x):
    """The pre-engine interpreter on one leaf, padded as the backend pads."""
    flat = x.reshape(-1)
    pad = (-flat.size) % comm.n
    flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)]) if pad else flat
    sched = comm.axis_schedule("all_reduce", flat.size * flat.dtype.itemsize)
    out = prim.run_reference("all_reduce", flat, sched, AXIS)
    return out[: x.size].reshape(x.shape)


# ------------------------------------------------------------ tree checks
TWO_LOOPS = {"ring": comm_of("auto"), "rhd": comm_of("rhd")}


@check
def tree_matches_per_leaf():
    tree = global_tree(np.random.default_rng(0))
    before = exec_engine.exec_stats().shared_loop_buffers
    got = run_tree(lambda t: {k: c.all_reduce(t) for k, c in TWO_LOOPS.items()}, tree)
    counted = exec_engine.exec_stats().shared_loop_buffers - before
    want = run_tree(lambda t: {k: jax.tree.map(c.all_reduce, t)
                               for k, c in TWO_LOOPS.items()}, tree)
    assert_trees_equal(got, want)
    leaves = len(jax.tree.leaves(tree))
    assert counted == 2 * leaves, (counted, leaves)  # two loops, every leaf shared
    return {"leaves": leaves, "shared_loop_buffers": counted}


@check
def tree_matches_reference():
    tree = global_tree(np.random.default_rng(1))
    got = run_tree(lambda t: {k: c.all_reduce(t) for k, c in TWO_LOOPS.items()}, tree)
    want = run_tree(lambda t: {k: jax.tree.map(lambda x, c=c: reference_leaf(c, x), t)
                               for k, c in TWO_LOOPS.items()}, tree)
    assert_trees_equal(got, want)
    # and it is the sum over ranks
    np.testing.assert_allclose(np.asarray(got["ring"]["padded"][0]),
                               tree["padded"].sum(axis=0), rtol=1e-5, atol=1e-5)


@check
def tree_mixed_paths():
    # H100_DGX at 4 ranks with an error tolerance plans bucket2d below
    # 1 MiB and the lossy ring_ef8 (the one-array path) above
    comm = comm_of("auto", cm.H100_DGX, rel_error_tol=0.05)
    rng = np.random.default_rng(2)
    tree = [rng.normal(size=(N, 300)).astype(np.float32),
            rng.normal(size=(N, 512, 1024)).astype(np.float32),
            rng.normal(size=(N, 7)).astype(np.float32)]
    algos = [comm.axis_schedule("all_reduce", x[0].size * 4 + 4).algorithm for x in tree]
    assert algos == ["bucket2d", "ring_ef8", "bucket2d"], algos
    before = exec_engine.exec_stats().shared_loop_buffers
    got = run_tree(comm.all_reduce, tree)
    counted = exec_engine.exec_stats().shared_loop_buffers - before
    assert_trees_equal(got, run_tree(lambda t: [comm.all_reduce(x) for x in t], tree))
    assert counted == 2, counted
    return {"algorithms": algos}


@check
def tree_eager():
    comm = comm_of("auto")
    rng = np.random.default_rng(3)
    tree = {"a": rng.normal(size=(N, 6)).astype(np.float32),
            "b": rng.normal(size=(N, 3, 4)).astype(np.float32)}
    got = comm.all_reduce(tree)
    for k, x in tree.items():
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(comm.all_reduce(x)))


@check
def xla_and_sim_trees():
    tree = global_tree(np.random.default_rng(4))
    session = PcclSession(cm.TPU_V5E_PHOTONIC, thread_fabric=False)
    interp = run_tree(session.communicator(AXIS, N, backend="interp").all_reduce, tree)
    xla = run_tree(session.communicator(AXIS, N, backend="xla").all_reduce, tree)
    shapes = jax.tree.map(lambda x: (x.shape, str(x.dtype)), interp)
    assert jax.tree.map(lambda x: (x.shape, str(x.dtype)), xla) == shapes
    local = jax.tree.map(lambda x: x[0], tree)
    sim = session.communicator(AXIS, N, backend="sim")
    out = sim.all_reduce(local)
    assert jax.tree.map(lambda x: (x.shape, str(x.dtype)), out) == \
        jax.tree.map(lambda x: (x.shape, str(x.dtype)), local)
    per_leaf = session.communicator(AXIS, N, backend="sim")
    for leaf in jax.tree.leaves(local):
        per_leaf.all_reduce(leaf)
    assert sim.sim_elapsed_s == per_leaf.sim_elapsed_s > 0, (
        sim.sim_elapsed_s, per_leaf.sim_elapsed_s)
    assert len(sim.backend.events) == len(jax.tree.leaves(local))
    return {"sim_elapsed_s": sim.sim_elapsed_s}


# ---------------------------------------------------------------- DP step
class PerLeaf:
    """The communicator called once per gradient leaf, as the DP step did
    before it handed the engine the whole tree."""

    def __init__(self, comm):
        self.comm, self.axis_name, self.n = comm, comm.axis_name, comm.n

    def all_reduce(self, tree):
        return jax.tree.map(self.comm.all_reduce, tree)


def dp_steps(name):
    """One step of ``make_dp_train_step`` for a small ``name``, with the
    tree call and with :class:`PerLeaf`; each result with the
    ``shared_loop_buffers`` its trace added."""
    from repro.models.module import unbox
    from repro.train.optimizer import OptimizerConfig, init_opt_state
    from repro.train.train_step import make_dp_train_step

    model = small_model(name)
    params = unbox(model.init(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(5)
    batch = {k: jnp.asarray(rng.integers(0, 128, size=(2 * N, 8)), jnp.int32)
             for k in ("tokens", "targets")}
    if model.cfg.enc_dec is not None:
        batch["enc_frames"] = jnp.asarray(rng.standard_normal((2 * N, 12, 32)), jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:N]), ("data",))
    session = PcclSession(cm.TPU_V5E_PHOTONIC, thread_fabric=False)
    comm = session.communicator("data", N, backend="interp")
    opt_cfg = OptimizerConfig()
    out = {}
    for kind, c in (("tree", comm), ("per_leaf", PerLeaf(comm))):
        step = make_dp_train_step(model, opt_cfg, c, mesh)
        before = session.exec_stats().shared_loop_buffers
        p = jax.tree.map(jnp.copy, params)
        res = step(p, init_opt_state(p), batch)
        out[kind] = (res, session.exec_stats().shared_loop_buffers - before)
    return out, len(jax.tree.leaves(params))


_DP = {}


def dp(name="bert-base-paper"):
    if name not in _DP:
        _DP[name] = dp_steps(name)
    return _DP[name]


@check
def dp_step_bitwise():
    out, _ = dp()
    assert_trees_equal(out["tree"][0], out["per_leaf"][0])
    return {"loss": float(out["tree"][0][2])}


@check
def dp_step_counter():
    out, leaves = dp()
    assert out["tree"][1] == leaves, (out["tree"][1], leaves)
    assert out["per_leaf"][1] == 0, out["per_leaf"][1]
    return {"leaves": leaves}


@check
def dp_step_bitwise_encdec():
    out, _ = dp("whisper-small")
    assert_trees_equal(out["tree"][0], out["per_leaf"][0])
    return {"loss": float(out["tree"][0][2])}


@check
def dp_step_counter_encdec():
    out, leaves = dp("whisper-small")
    assert leaves == 32 and out["tree"][1] == leaves, (out["tree"][1], leaves)
    assert out["per_leaf"][1] == 0, out["per_leaf"][1]
    return {"leaves": leaves}


# ------------------------------------------------------ one-buffer HLO
def single_buffer_engine(chunks, compiled, axis_name):
    """The engine's ``execute_compiled`` as it was before buffers could
    share a round loop, verbatim but for ``me``: the one-buffer baseline."""
    me = lax.axis_index(axis_name)
    shape = chunks.shape

    def apply_round(buf, send, recv, grp):
        payload = jnp.take(buf, send, axis=0)
        got = lax.ppermute(payload, axis_name, grp.perm)
        return buf.at[recv].add(got) if grp.reduce else buf.at[recv].set(got)

    with exec_engine.collective_scope(compiled.collective, compiled.algorithm):
        chunks = exec_engine._lane_view(chunks)
        for i, grp in enumerate(compiled.groups):
            with jax.named_scope(f"group{i}"):
                send = jnp.take(jnp.asarray(grp.send_ids), me, axis=1)  # (rounds, k)
                recv = jnp.take(jnp.asarray(grp.recv_ids), me, axis=1)
                if grp.rounds == 1:
                    chunks = apply_round(chunks, send[0], recv[0], grp)
                else:

                    def body(buf, sr, _grp=grp):
                        return apply_round(buf, sr[0], sr[1], _grp), None

                    chunks, _ = lax.scan(body, chunks, (send, recv))
        return chunks.reshape(shape)


_NAME = re.compile(r"%?[A-Za-z_][\w.\-]*(?=[ ,)(=\]}])")


def compiled_hlo(engine, sched, local_shape):
    compiled = exec_engine.compile_schedule(sched)
    f = smap(lambda x: engine(x[0], compiled, AXIS)[None], (P(AXIS),), P(AXIS))
    shape = jax.ShapeDtypeStruct((N,) + local_shape, jnp.float32)
    return canonical_hlo(f.lower(shape).compile().as_text())


ONE_BUFFER_CASES = {
    "ring_all_reduce": (S.get_schedule("all_reduce", "ring", N, 4096.0), (N, 256)),
    "rhd_all_reduce": (S.get_schedule("all_reduce", "rhd", N, 4096.0), (N, 6)),
    "ring_all_gather": (S.get_schedule("all_gather", "ring", N, 4096.0), (N, 128, 3)),
}


def one_buffer_check(case):
    sched, shape = ONE_BUFFER_CASES[case]

    def run():
        new = compiled_hlo(exec_engine.execute_compiled, sched, shape)
        old = compiled_hlo(single_buffer_engine, sched, shape)
        assert new == old, "\n".join(
            f"new: {a}\nold: {b}" for a, b in zip(new.splitlines(), old.splitlines())
            if a != b)
        return {"lines": len(new.splitlines())}

    run.__name__ = f"one_buffer_hlo_{case}"
    return run


for _case in ONE_BUFFER_CASES:
    check(one_buffer_check(_case))


def main():
    assert jax.device_count() == N, jax.devices()
    for fn in CHECKS:
        try:
            detail, ok = fn(), True
        except Exception:  # report every check, not only the first failure
            detail, ok = traceback.format_exc()[-3000:], False
        print(json.dumps({"check": fn.__name__, "ok": ok, "detail": detail}), flush=True)


if __name__ == "__main__":
    main()
