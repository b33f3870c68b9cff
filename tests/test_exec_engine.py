"""Compiled execution engine: table-level and cache-level unit tests.

Device-level bit-identity against the pre-PR interpreter (4 collectives ×
{ring, rhd, dex, direct} × n ∈ {4, 8}, full-axis and split) runs in
exec_engine_check.py under 8 host devices in a subprocess — XLA locks the
device count at first init, so it cannot share this process.  Everything
here is device-free: fingerprints, compiled tables vs the per-round
reference, round-group folding, the slot-addressed all-to-all compile
(checked by a pure-numpy emulation of the executor), LRU accounting, and
the attributable trace-time errors.  The shared round loop's device checks
(a gradient tree against per-leaf all-reduces, the DP step, one-buffer HLO)
run in shared_loop_check.py under 4 host devices, also in a subprocess.
"""

import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from repro.comm import exec_engine
from repro.comm.errors import ScheduleExecutionError
from repro.core import schedules as S
from repro.core.schedules import Round, Schedule, Transfer

ROOT = pathlib.Path(__file__).resolve().parent.parent


# ------------------------------------------------------------ fingerprint
def test_fingerprint_stable_across_reconstruction():
    a = S.ring_reduce_scatter(8, 4096.0)
    b = S.ring_reduce_scatter(8, 4096.0)
    assert a is not b and a.fingerprint() == b.fingerprint()
    assert a.fingerprint() == a.fingerprint()  # memoized path


def test_fingerprint_ignores_byte_sizes():
    # a buffer-size sweep rescales one template; execution is unchanged, so
    # every size shares one compiled executable
    assert (
        S.ring_reduce_scatter(8, 1024.0).fingerprint()
        == S.ring_reduce_scatter(8, 1 << 30).fingerprint()
    )


def test_fingerprint_distinguishes_structure():
    fps = {
        S.ring_reduce_scatter(8, 1024.0).fingerprint(),
        S.rhd_reduce_scatter(8, 1024.0).fingerprint(),
        S.ring_all_gather(8, 1024.0).fingerprint(),
        S.ring_reduce_scatter(4, 1024.0).fingerprint(),
        S.dex_all_to_all(8, 1024.0).fingerprint(),
        S.direct_all_to_all(8, 1024.0).fingerprint(),
    }
    assert len(fps) == 6


def test_fingerprint_collision_regression():
    """Fingerprints are injective on schedule structure (the docstring of
    Schedule.fingerprint points here).  Sweep the generator zoo plus a
    batch of structurally-adjacent hand variants — every distinct
    (perm, chunk, reduce, round-boundary) table must hash distinctly."""
    zoo = []
    for n in (2, 3, 4, 6, 8, 16):
        zoo += [S.ring_reduce_scatter(n, 1.0), S.ring_all_gather(n, 1.0),
                S.ring_all_reduce(n, 1.0), S.direct_all_to_all(n, 1.0),
                S.ring_all_to_all(n, 1.0)]
    for n in (2, 4, 8, 16):
        zoo += [S.rhd_reduce_scatter(n, 1.0), S.rhd_all_gather(n, 1.0),
                S.rhd_all_reduce(n, 1.0), S.dex_all_to_all(n, 1.0)]
    for dims in ((2, 2), (2, 4), (3, 3), (2, 2, 2)):
        zoo += [S.bucket_reduce_scatter(dims, 1.0),
                S.bucket_all_gather(dims, 1.0)]

    # adjacent variants that a sloppy (non-delimited) encoding would merge:
    base = S.ring_reduce_scatter(4, 1.0)
    flat = Schedule(base.collective, base.algorithm, base.n, 1.0,
                    (Round(tuple(t for r in base.rounds
                                 for t in r.transfers), 1.0),))
    zoo.append(flat)  # same transfers, different round boundaries
    t = base.rounds[0].transfers[0]
    one = Schedule("p2p", "direct", 4, 1.0,
                   (Round((Transfer(t.src, t.dst, t.chunks, t.reduce),), 1.0),))
    two = Schedule("p2p", "direct", 4, 1.0,
                   (Round((Transfer(t.src, t.dst, (1, 2), t.reduce),), 1.0),))
    twelve = Schedule("p2p", "direct", 4, 1.0,
                      (Round((Transfer(t.src, t.dst, (12,), t.reduce),), 1.0),))
    zoo += [one, two, twelve]  # chunks (1,2) vs (12) must not collide

    fps = [s.fingerprint() for s in zoo]
    assert len(set(fps)) == len(fps), "fingerprint collision in sweep"


# ------------------------------------------------------------ PCCL_VERIFY
def _corrupt(sched):
    """Relabel one chunk: the rounds stay valid permutations (so the
    executable compiles), but the dataflow postcondition fails — exactly
    the class of bug only the static verifier catches."""
    rounds = list(sched.rounds)
    tf = list(rounds[0].transfers)
    t = tf[0]
    bad_chunk = (t.chunks[0] + 1) % sched.n
    tf[0] = Transfer(t.src, t.dst, (bad_chunk,) + t.chunks[1:], t.reduce)
    rounds[0] = Round(tuple(tf), rounds[0].size)
    return Schedule(sched.collective, sched.algorithm, sched.n,
                    sched.buffer_bytes, tuple(rounds))


def test_pccl_verify_disabled_compiles_corrupt(monkeypatch):
    monkeypatch.delenv("PCCL_VERIFY", raising=False)
    exec_engine.clear_exec_caches()
    compiled = exec_engine.compile_schedule(_corrupt(S.ring_reduce_scatter(8, 64.0)))
    assert compiled is not None  # off by default: zero-overhead path


def test_pccl_verify_enabled_rejects_corrupt(monkeypatch):
    from repro.analysis.verify import ScheduleVerificationError

    monkeypatch.setenv("PCCL_VERIFY", "1")
    exec_engine.clear_exec_caches()
    with pytest.raises(ScheduleVerificationError):
        exec_engine.compile_schedule(_corrupt(S.ring_reduce_scatter(8, 64.0)))
    # correct schedules still compile with verification on
    assert exec_engine.compile_schedule(S.ring_reduce_scatter(8, 64.0))


def test_pccl_verify_cache_hits_skip_verification(monkeypatch):
    monkeypatch.delenv("PCCL_VERIFY", raising=False)
    exec_engine.clear_exec_caches()
    bad = _corrupt(S.ring_reduce_scatter(8, 64.0))
    exec_engine.compile_schedule(bad)  # populate cache while disabled
    monkeypatch.setenv("PCCL_VERIFY", "1")
    # hit: env is only consulted on compile-cache miss
    assert exec_engine.compile_schedule(bad) is not None
    exec_engine.clear_exec_caches()


# -------------------------------------------------------- compiled tables
def _flat_tables(compiled):
    """(perm, send_row, recv_row, reduce) per round, unstacked."""
    out = []
    for grp in compiled.groups:
        for g in range(grp.rounds):
            out.append((list(grp.perm), grp.send_ids[g], grp.recv_ids[g], grp.reduce))
    return out


@pytest.mark.parametrize(
    "sched",
    [
        S.ring_reduce_scatter(8, 4096.0),
        S.rhd_all_gather(8, 4096.0),
        S.ring_all_reduce(8, 4096.0),
        S.bucket_all_reduce((2, 4), 4096.0),
        S.dex_all_to_all(8, 4096.0),
        S.direct_all_to_all(8, 4096.0),
        S.ring_all_to_all(4, 4096.0),
    ],
    ids=lambda s: f"{s.collective}-{s.algorithm}",
)
def test_compiled_tables_match_reference(sched):
    compiled = exec_engine.compile_schedule(sched)
    assert compiled.num_rounds == sched.num_rounds
    flat = _flat_tables(compiled)
    assert len(flat) == sched.num_rounds
    for i, rnd in enumerate(sched.rounds):
        perm, send, recv, reduce = exec_engine.round_tables(rnd, sched.n)
        cperm, csend, crecv, creduce = flat[i]
        assert cperm == perm and creduce == reduce
        np.testing.assert_array_equal(csend, send)
        np.testing.assert_array_equal(crecv, recv)


def test_round_group_folding():
    # ring RS: n-1 rounds, one perm, one reduce flag -> a single scan group
    rs = exec_engine.compile_schedule(S.ring_reduce_scatter(8, 1.0))
    assert [g.rounds for g in rs.groups] == [7]
    # ring all-reduce: RS phase + AG phase -> exactly two groups
    ar = exec_engine.compile_schedule(S.ring_all_reduce(8, 1.0))
    assert [g.rounds for g in ar.groups] == [7, 7]
    assert [g.reduce for g in ar.groups] == [True, False]
    # RHD pairs a different bit each round -> per-round fallback groups
    rhd = exec_engine.compile_schedule(S.rhd_reduce_scatter(8, 1.0))
    assert [g.rounds for g in rhd.groups] == [1, 1, 1]
    # bucket: every torus-axis phase folds into one group
    b = exec_engine.compile_schedule(S.bucket_reduce_scatter((2, 4), 1.0))
    assert sum(g.rounds for g in b.groups) == b.num_rounds
    assert len(b.groups) < b.num_rounds
    # ring all-to-all shares the perm but k shrinks per round -> no folding
    ra = exec_engine.compile_schedule(S.ring_all_to_all(4, 1.0))
    assert [g.rounds for g in ra.groups] == [1] * ra.num_rounds


def test_compiled_cache_accounting():
    exec_engine.clear_exec_caches()
    sched = S.ring_reduce_scatter(16, 512.0)
    c1 = exec_engine.compile_schedule(sched)
    s = exec_engine.exec_stats()
    assert s.compiled_misses == 1 and s.compiled_hits == 0
    c2 = exec_engine.compile_schedule(S.ring_reduce_scatter(16, 512.0))
    s = exec_engine.exec_stats()
    assert s.compiled_hits == 1 and c2 is c1  # the cached object, same id
    # a rescaled sweep template is the same executable (size-free fingerprint)
    c3 = exec_engine.compile_schedule(S.ring_reduce_scatter(16, 2048.0))
    assert c3 is c1


def test_lru_bound_and_eviction():
    lru = exec_engine._LruCache(max_entries=2)
    lru.put("a", 1)
    lru.put("b", 2)
    assert lru.get("a") == 1  # refreshes a
    lru.put("c", 3)  # evicts b (LRU)
    assert lru.get("b") is None and lru.get("a") == 1 and lru.get("c") == 3
    assert lru.evictions == 1 and len(lru) == 2


# ----------------------------------------------- slot-addressed all-to-all
def _emulate_compiled(compiled, m, local_of):
    """Pure-numpy replay of execute_compiled over integer chunk ids."""
    n_rows = compiled.n
    buf = np.array(
        [[local_of[r] * m + t for t in range(m)] for r in range(n_rows)],
        dtype=np.int64,
    )
    for grp in compiled.groups:
        dst_of = dict(grp.perm)
        for g in range(grp.rounds):
            payload = {r: buf[r, grp.send_ids[g, r]].copy() for r in range(n_rows)}
            for r in range(n_rows):
                d = dst_of[r]
                buf[d, grp.recv_ids[g, d]] = payload[r]
    return buf


@pytest.mark.parametrize("n", [4, 8])
@pytest.mark.parametrize("algo", ["dex", "direct", "ring"])
def test_slot_compile_satisfies_post_condition(n, algo):
    sched = S.get_schedule("all_to_all", algo, n, 4096.0)
    local_of = tuple(range(n))
    compiled = exec_engine.compile_all_to_all(sched, n, local_of)
    assert compiled is not None, f"{algo} n={n} must be slot-addressable"
    assert compiled.final_slots.shape == (n, n)
    buf = _emulate_compiled(compiled, n, local_of)
    # rank r ends holding block (o -> r) at final_slots[r, o], for every o
    for r in range(n):
        for o in range(n):
            assert buf[r, compiled.final_slots[r, o]] == o * n + r


def test_slot_compile_grouped_local_ids():
    """Composed split schedule: group-local chunk ids, global ranks."""
    from repro.api import subgroup_schedule

    m, n_axis = 4, 8
    groups = ((0, 2, 4, 6), (1, 3, 5, 7))
    local_of = [0] * n_axis
    for g in groups:
        for i, r in enumerate(g):
            local_of[r] = i
    sched = subgroup_schedule(S.direct_all_to_all(m, 1024.0), groups, n_axis)
    compiled = exec_engine.compile_all_to_all(sched, m, tuple(local_of))
    assert compiled is not None and compiled.final_slots.shape == (n_axis, m)
    buf = _emulate_compiled(compiled, m, tuple(local_of))
    for r in range(n_axis):
        for o in range(m):
            assert buf[r, compiled.final_slots[r, o]] == o * m + local_of[r]


def test_slot_compile_rejects_unheld_chunk():
    # rank 0 claims to send block (1 -> 1), which it never held -> dense path
    n = 2
    rounds = (
        Round(
            (
                Transfer(0, 1, chunks=(3,), reduce=False),
                Transfer(1, 0, chunks=(2,), reduce=False),
            ),
            1.0,
        ),
    )
    bad = Schedule("all_to_all", "bad", n, 4.0, rounds)
    assert exec_engine.compile_all_to_all(bad, n, (0, 1)) is None
    # the verdict (and the sentinel) is memoized
    assert exec_engine.compile_all_to_all(bad, n, (0, 1)) is None


def test_slot_compile_rejects_reduce_rounds():
    bad = Schedule(
        "all_to_all",
        "bad",
        2,
        4.0,
        (
            Round(
                (
                    Transfer(0, 1, chunks=(1,), reduce=True),
                    Transfer(1, 0, chunks=(2,), reduce=True),
                ),
                1.0,
            ),
        ),
    )
    assert exec_engine.compile_all_to_all(bad, 2, (0, 1)) is None


# ------------------------------------------------------ attributable errors
def test_round_table_errors_name_round_and_schedule():
    good = S.ring_all_gather(4, 1024.0)
    # break round 1: rank 0 sends twice (not a permutation)
    r1 = good.rounds[1]
    broken = Round(r1.transfers + (Transfer(0, 2, chunks=(0,)),), r1.size)
    bad = Schedule(
        good.collective, good.algorithm, good.n, good.buffer_bytes,
        (good.rounds[0], broken, good.rounds[2]),
    )
    with pytest.raises(ScheduleExecutionError, match=r"all_gather/ring round 1/3"):
        exec_engine.compile_schedule(bad)

    # chunkless schedules stay attributable too
    swing = S.swing_reduce_scatter(8, 1024.0)
    with pytest.raises(
        ScheduleExecutionError, match=r"reduce_scatter/swing round 0/3.*chunk"
    ):
        exec_engine.compile_schedule(swing)


def test_legacy_round_tables_signature():
    from repro.comm import primitives as prim

    rnd = S.ring_all_gather(4, 64.0).rounds[0]
    perm, send, recv, reduce = prim._round_tables(rnd, 4)
    assert len(perm) == 4 and send.shape == (4, 1) and reduce is False


# ------------------------------------------------------ communicator bits
def test_local_index_table_cached_and_correct():
    from repro.api import PcclSession
    from repro.core import cost_model as cm

    session = PcclSession(cm.H100_DGX, thread_fabric=False)
    root = session.communicator("x", 8, backend="sim")
    sub = root.split([r % 2 for r in range(8)])
    t1 = sub.local_index_table()
    np.testing.assert_array_equal(t1, [0, 0, 1, 1, 2, 2, 3, 3])
    assert sub.local_index_table() is t1  # built once, cached
    assert not t1.flags.writeable
    np.testing.assert_array_equal(root.local_index_table(), np.arange(8))
    assert root.group_fingerprint() == ("full", 8)
    assert sub.group_fingerprint() == ("split", ((0, 2, 4, 6), (1, 3, 5, 7)))


def test_sim_all_gather_preserves_array_namespace():
    from repro.api import PcclSession
    from repro.core import cost_model as cm

    session = PcclSession(cm.H100_DGX, thread_fabric=False)
    comm = session.communicator("x", 4, backend="sim")
    xnp = np.ones((2, 3), np.float16)
    out = comm.all_gather(xnp)
    assert isinstance(out, np.ndarray) and out.dtype == np.float16
    assert out.shape == (8, 3)

    jnp = pytest.importorskip("jax.numpy")
    xj = jnp.ones((2, 3), jnp.bfloat16)
    outj = comm.all_gather(xj)
    assert not isinstance(outj, np.ndarray)  # stayed a jax array
    assert outj.dtype == jnp.bfloat16 and outj.shape == (8, 3)


def test_session_exec_stats_surface():
    from repro.api import PcclSession
    from repro.core import cost_model as cm

    exec_engine.clear_exec_caches()
    s = PcclSession(cm.H100_DGX, thread_fabric=False)
    stats = s.exec_stats()
    assert stats.executable_hits == 0 and stats.traces == 0
    exec_engine.compile_schedule(S.ring_all_gather(4, 64.0))
    assert s.exec_stats().compiled_misses == 1


# ------------------------------------------------------- device subprocess
@pytest.mark.slow
@pytest.mark.multidevice
def test_exec_engine_device_checks():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tests" / "exec_engine_check.py")],
        capture_output=True,
        text=True,
        env=env,
        timeout=600,
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "ALL-EXEC-ENGINE-OK" in proc.stdout


SHARED_LOOP_CHECKS = [
    "tree_matches_per_leaf",
    "tree_matches_reference",
    "tree_mixed_paths",
    "tree_eager",
    "xla_and_sim_trees",
    "dp_step_bitwise",
    "dp_step_counter",
    "dp_step_bitwise_encdec",
    "dp_step_counter_encdec",
    "one_buffer_hlo_ring_all_reduce",
    "one_buffer_hlo_rhd_all_reduce",
    "one_buffer_hlo_ring_all_gather",
]


@pytest.fixture(scope="module")
def shared_loop_results():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tests" / "shared_loop_check.py")],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    return {r["check"]: r for r in map(json.loads, proc.stdout.strip().splitlines())}


@pytest.mark.multidevice
@pytest.mark.parametrize("check", SHARED_LOOP_CHECKS)
def test_shared_round_loop_on_devices(shared_loop_results, check):
    assert set(shared_loop_results) == set(SHARED_LOOP_CHECKS)
    r = shared_loop_results[check]
    assert r["ok"], r["detail"]


def test_shared_loop_counter_surface():
    exec_engine.clear_exec_caches()
    assert exec_engine.exec_stats().shared_loop_buffers == 0
    exec_engine.note_shared_loop(3)
    assert exec_engine.exec_stats().shared_loop_buffers == 3
    exec_engine.clear_exec_caches()
    assert exec_engine.exec_stats().shared_loop_buffers == 0
