"""Comm/compute fusion layer: stream programs, counters, ring_ef8 gating.

Single-device tests for :mod:`repro.comm.fusion` and its planner/engine
integration; the multi-device bit-identity checks (fused matmul+RS and
AR+rmsnorm vs the unfused oracle, quantized execution) run in
``fusion_check.py`` under 8 host devices in a subprocess — XLA locks the
device count at first jax init, so they cannot share this process.
"""

import os
import subprocess
import sys
import types
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))  # benchmarks/ is a root-level namespace pkg

from repro.comm import exec_engine
from repro.comm.fusion import _stream_program, stream_program
from repro.core import cost_model as cm
from repro.core import schedules as S
from repro.core.cost_model import compressed_ef_error_bound
from repro.core.pccl import candidate_algorithms

_D = float(1 << 20)


# ------------------------------------------------------- stream programs
class TestStreamProgram:
    @pytest.mark.parametrize("n", [2, 4, 8, 16])
    def test_ring_reduce_scatter_is_streamable(self, n):
        compiled = exec_engine.compile_schedule(S.ring_reduce_scatter(n, _D))
        prog = stream_program(compiled)
        assert prog is not None
        assert prog.rounds == n - 1
        assert prog.order.shape == (n, n)
        for r in range(n):
            # each rank's order is a permutation of the chunk ids …
            assert sorted(prog.order[r]) == list(range(n))
            # … in which every chunk a round touches is already computed:
            # round t runs at scan step t+1, after tiles order[: t+2]
            for t in range(prog.rounds):
                avail = set(prog.order[r][: t + 2].tolist())
                assert prog.send[t, r] in avail
                assert prog.recv[t, r] in avail

    def test_memoized_by_fingerprint(self):
        c1 = exec_engine.compile_schedule(S.ring_reduce_scatter(8, _D))
        c2 = exec_engine.compile_schedule(S.ring_reduce_scatter(8, 2 * _D))
        assert stream_program(c1) is stream_program(c2)  # same fingerprint

    @pytest.mark.parametrize(
        "sched",
        [
            S.ring_all_gather(8, _D),      # no reduction
            S.ring_all_reduce(8, _D),      # two phases -> two round groups
            S.rhd_reduce_scatter(8, _D),   # log-n rounds != n_chunks - 1
        ],
        ids=["all_gather", "all_reduce", "rhd"],
    )
    def test_non_streamable_schedules(self, sched):
        assert stream_program(exec_engine.compile_schedule(sched)) is None

    def test_infeasible_deadlines_rejected(self):
        # n=4, k=1, 3 rounds: every rank touches chunks {0,1} in round 0 and
        # {2,3} in round 1 -> 4 distinct chunks due by end of round 1, but
        # the scan has only produced 3 tiles by then (prologue + 2 steps)
        n, rounds = 4, 3
        send = np.array([[0] * n, [2] * n, [1] * n], dtype=np.int32)
        recv = np.array([[1] * n, [3] * n, [2] * n], dtype=np.int32)
        grp = types.SimpleNamespace(
            perm=tuple((i, (i + 1) % n) for i in range(n)),
            reduce=True,
            send_ids=send[:, :, None],
            recv_ids=recv[:, :, None],
        )
        fake = types.SimpleNamespace(groups=(grp,))
        assert _stream_program(fake) is None


# ---------------------------------------------------- overlap accounting
class TestExecStatsOverlap:
    def test_counters_accumulate_and_reset(self):
        exec_engine.clear_exec_caches()
        s0 = exec_engine.exec_stats()
        assert (s0.fused_dispatches, s0.fallback_dispatches) == (0, 0)
        exec_engine.note_fused_dispatch()
        exec_engine.note_fused_dispatch()
        exec_engine.note_fallback_dispatch()
        s1 = exec_engine.exec_stats()
        assert (s1.fused_dispatches, s1.fallback_dispatches) == (2, 1)
        exec_engine.clear_exec_caches()
        s2 = exec_engine.exec_stats()
        assert (s2.fused_dispatches, s2.fallback_dispatches) == (0, 0)

    def test_clear_exec_caches_drops_kernel_verify_memo(self):
        # regression (PR 9): PCCL_VERIFY's kernel-analysis memo survived
        # clear_exec_caches(), so a kernel edited mid-process kept its
        # stale clean verdict
        import jax
        import jax.numpy as jnp

        from repro.analysis import kernel_lint
        from repro.kernels.matmul.kernel import matmul_pallas

        kernel_lint.clear_verified_cache()
        sds = jax.ShapeDtypeStruct((64, 128), jnp.float32)
        wds = jax.ShapeDtypeStruct((128, 128), jnp.float32)
        kernel_lint.verify_entry_point(
            "matmul", matmul_pallas, (sds, wds), {"block_m": 64}
        )
        assert kernel_lint._VERIFIED
        exec_engine.clear_exec_caches()
        assert not kernel_lint._VERIFIED


# --------------------------------------------------- ring_ef8 in the core
class TestRingEf8Schedule:
    def test_same_transfers_quarter_wire(self):
        exact = S.ring_all_reduce(8, _D)
        ef8 = S.ring_ef8_all_reduce(8, _D)
        assert ef8.collective == "all_reduce"
        assert ef8.algorithm == "ring_ef8"
        assert len(ef8.rounds) == len(exact.rounds)
        for re_, rx in zip(ef8.rounds, exact.rounds):
            assert re_.transfers == rx.transfers
            assert re_.size == pytest.approx(0.25 * rx.size)
        assert ef8.fingerprint() != exact.fingerprint()

    def test_registered_generator(self):
        built = S.get_schedule("all_reduce", "ring_ef8", 8, _D)
        assert built.algorithm == "ring_ef8"
        assert built.fingerprint() == S.ring_ef8_all_reduce(8, _D).fingerprint()

    def test_error_bound_values(self):
        assert compressed_ef_error_bound(2) == pytest.approx(1 / 127.0)
        assert compressed_ef_error_bound(8) == pytest.approx(7 / 127.0)
        # monotone in n: more quantizing hops, looser bound
        bounds = [compressed_ef_error_bound(n) for n in range(2, 32)]
        assert bounds == sorted(bounds)
        with pytest.raises(ValueError):
            compressed_ef_error_bound(1)


class TestRingEf8Arbitration:
    def test_candidates_gated_by_tolerance(self):
        base = candidate_algorithms("all_reduce", 8, "auto")
        assert "ring_ef8" not in base
        loose = candidate_algorithms("all_reduce", 8, "auto", 1.0)
        assert "ring_ef8" in loose
        assert set(loose) >= set(base)
        # tolerance below the n=8 bound (7/127 ~ 0.055) keeps the sum exact
        tight = candidate_algorithms("all_reduce", 8, "auto", 0.01)
        assert "ring_ef8" not in tight
        # boundary: exactly the bound is acceptable
        at = candidate_algorithms("all_reduce", 8, "auto", 7 / 127.0)
        assert "ring_ef8" in at

    def test_only_all_reduce_and_auto(self):
        assert "ring_ef8" not in candidate_algorithms(
            "reduce_scatter", 8, "auto", 1.0
        )
        assert candidate_algorithms("all_reduce", 8, "ring", 1.0) == ["ring"]
        assert candidate_algorithms("all_reduce", 8, "ring_ef8") == ["ring_ef8"]

    def test_session_plans_ef8_only_within_tolerance(self):
        from repro.api import PcclSession

        nbytes = 1e9
        s = PcclSession(cm.TPU_V5E_PHOTONIC, thread_fabric=False)
        exact = s.plan("all_reduce", nbytes, n=8, algorithm="auto")
        lossy = s.plan("all_reduce", nbytes, n=8, algorithm="auto",
                       rel_error_tol=1.0)
        tight = s.plan("all_reduce", nbytes, n=8, algorithm="auto",
                       rel_error_tol=1e-3)
        assert exact.algorithm != "ring_ef8"
        assert lossy.algorithm == "ring_ef8"
        assert lossy.cost < exact.cost  # the 4x wire discount must show up
        assert tight.algorithm == exact.algorithm
        assert tight.cost == exact.cost


# ------------------------------------------------------ matmul kernel ops
class TestMatmulKernel:
    @pytest.mark.parametrize(
        "dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)]
    )
    def test_matches_reference(self, dtype, tol):
        import jax.numpy as jnp

        from repro.kernels.matmul import matmul, matmul_reference

        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(256, 256)), dtype=dtype)
        w = jnp.asarray(rng.normal(size=(256, 128)), dtype=dtype)
        got = matmul(x, w, block_m=64, block_n=128, block_k=128,
                     use_pallas=True, interpret=True)
        want = matmul_reference(x, w)
        assert got.dtype == want.dtype
        np.testing.assert_allclose(
            np.asarray(got, dtype=np.float32),
            np.asarray(want, dtype=np.float32),
            rtol=tol, atol=tol,
        )

    def test_chunked_calls_bit_identical_to_whole(self):
        # the fused path's correctness keystone: per-chunk kernel calls at
        # the same block sizes reproduce the whole-M call bit-for-bit
        import jax.numpy as jnp

        from repro.kernels.matmul import matmul

        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(256, 128)), dtype=jnp.float32)
        w = jnp.asarray(rng.normal(size=(128, 128)), dtype=jnp.float32)
        whole = matmul(x, w, block_m=32, use_pallas=True, interpret=True)
        parts = [
            matmul(x[i: i + 32], w, block_m=32, use_pallas=True,
                   interpret=True)
            for i in range(0, 256, 32)
        ]
        np.testing.assert_array_equal(
            np.asarray(whole), np.concatenate([np.asarray(p) for p in parts])
        )

    def test_tiles_exactly_and_fallback(self):
        import jax.numpy as jnp

        from repro.kernels.matmul import matmul, matmul_reference, tiles_exactly

        assert tiles_exactly(256, 128, 128, block_m=64)
        assert not tiles_exactly(250, 128, 128, block_m=64)
        # K=100 clips block_k to 100 (tiles); an explicit smaller block
        # that does not divide K does not
        assert tiles_exactly(256, 100, 128)
        assert not tiles_exactly(256, 100, 128, block_k=64)
        # non-tiling shapes silently dispatch to the reference (no padding)
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.normal(size=(250, 100)), dtype=jnp.float32)
        w = jnp.asarray(rng.normal(size=(100, 64)), dtype=jnp.float32)
        got = matmul(x, w, use_pallas=True, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(matmul_reference(x, w))
        )

    def test_shape_validation(self):
        import jax.numpy as jnp

        from repro.kernels.matmul.kernel import matmul_pallas

        x = jnp.zeros((64, 128), jnp.float32)
        with pytest.raises(ValueError):
            matmul_pallas(x, jnp.zeros((64, 64), jnp.float32))  # K mismatch
        with pytest.raises(ValueError):
            matmul_pallas(x, jnp.zeros((128, 100), jnp.float32),
                          block_n=64)  # N=100 not tiled


# --------------------------------------------- taskgraph overlap modeling
class TestTaskgraphOverlap:
    def _sim(self, **kw):
        from benchmarks.taskgraph import CommScheme, Workload, simulate_training
        from repro.core import topology as T

        return simulate_training(
            Workload(), CommScheme("pccl", "pccl"), T.ring(8),
            cm.TPU_V5E_PHOTONIC, **kw,
        )

    def test_default_and_zero_fraction_unchanged(self):
        base = self._sim()
        zero = self._sim(overlap_fraction=0.0)
        assert zero.iteration_s == base.iteration_s
        assert zero.comm_s == base.comm_s

    def test_overlap_hides_comm_not_compute(self):
        base = self._sim()
        ov = self._sim(overlap_fraction=0.43)
        full = self._sim(overlap_fraction=1.0)
        assert ov.comm_s < base.comm_s
        assert full.comm_s <= ov.comm_s
        assert ov.compute_s == base.compute_s
        assert ov.iteration_s == pytest.approx(ov.comm_s + ov.compute_s)
        # the cold layer-1 AllReduce and one warm AllReduce never hide
        assert full.comm_s > 0

    def test_fraction_validated(self):
        with pytest.raises(ValueError):
            self._sim(overlap_fraction=1.5)

    def test_measured_overlap_fraction(self, tmp_path):
        import json

        from benchmarks.taskgraph import measured_overlap_fraction

        p = tmp_path / "BENCH_exec.json"
        p.write_text(json.dumps({"points": [
            {"collective": "fused_matmul_reduce_scatter",
             "seq_warm_s": 10.0, "fused_warm_s": 6.0},
            {"collective": "fused_matmul_reduce_scatter",
             "seq_warm_s": 10.0, "fused_warm_s": 4.0},
            {"collective": "reduce_scatter", "speedup": 100.0},
        ]}))
        assert measured_overlap_fraction(p) == pytest.approx(0.6)
        p.write_text(json.dumps({"points": [{"collective": "all_gather"}]}))
        assert measured_overlap_fraction(p) is None

    def test_committed_bench_has_fused_rows(self):
        # the committed baseline must keep feeding the overlap model
        from benchmarks.taskgraph import measured_overlap_fraction

        frac = measured_overlap_fraction(ROOT / "BENCH_exec.json")
        assert frac is not None and 0.0 < frac < 1.0


# --------------------------------------------------- bench gate schema
def test_bench_gate_identifies_fused_rows():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_gate", ROOT / "scripts" / "bench_gate.py"
    )
    gate = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gate)
    assert "shape" in gate.ID_KEYS and "mode" in gate.ID_KEYS
    a = {"n": 8, "collective": "fused_matmul_reduce_scatter",
         "shape": "256x128x128", "mode": "fused", "speedup": 1.4}
    b = dict(a, shape="512x128x128")
    assert gate.point_id(a) != gate.point_id(b)
    assert gate.point_id(a) == gate.point_id(dict(a, speedup=9.9))


# ------------------------------------------------------- device subprocess
@pytest.mark.slow
@pytest.mark.multidevice
def test_fusion_device_checks():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tests" / "fusion_check.py")],
        capture_output=True,
        text=True,
        env=env,
        timeout=600,
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "ALL-FUSION-OK" in proc.stdout
