"""Device-level checks for the comm/compute fusion layer.

Run as a subprocess by test_fusion.py with 8 host devices (XLA locks the
device count at first jax init, so this cannot share a process with the
single-device suite).  Asserts:

* :func:`repro.comm.fusion.fused_matmul_reduce_scatter` is **bit-identical**
  to the unfused kernel-then-collective composition across n ∈ {4, 8} and
  dtypes {float32, bfloat16}, and every fallback trigger (blocks that do
  not tile the chunk, a grouped communicator) still returns the identical
  result while counting a fallback dispatch;
* :func:`fused_all_reduce_rmsnorm` is bit-identical to
  ``all_reduce`` → rmsnorm, with the size-indivisible fallback counted;
* ``ring_ef8`` execution (``all_reduce_quantized`` through the interp
  backend, full-axis and split) stays within the documented accuracy
  bound of the exact ring all-reduce and runs the same number of rounds.
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import PcclSession
from repro.comm import exec_engine
from repro.comm.fusion import fused_all_reduce_rmsnorm, fused_matmul_reduce_scatter
from repro.core import cost_model as cm
from repro.core.cost_model import compressed_ef_error_bound
from repro.kernels.matmul.ops import matmul
from repro.kernels.rmsnorm.ops import rmsnorm

HW = cm.TPU_V5E_PHOTONIC
INTERPRET = jax.default_backend() == "cpu"


def fresh_comm(n, algorithm="ring"):
    session = PcclSession(HW, thread_fabric=False)
    return session.communicator("x", n, backend="interp", algorithm=algorithm)


def unfused_mm_rs(comm, x, w, **blocks):
    """The sequential oracle: whole-M kernel dispatch, then the collective."""
    S, M, K = x.shape
    y = matmul(
        x.reshape(S * M, K), w, use_pallas=True, interpret=INTERPRET, **blocks
    ).reshape(S, M, w.shape[1])
    return comm.reduce_scatter(y)


def check_fused_mm_rs_bit_identity():
    for n, M, K, N, dtype in [
        (8, 256, 128, 128, np.float32),
        (8, 64, 128, 256, np.float32),
        (4, 128, 64, 128, np.float32),
        (8, 256, 128, 128, jnp.bfloat16),
    ]:
        rng = np.random.default_rng(M + N)
        x = jnp.asarray(rng.normal(size=(n, M, K)), dtype=dtype)
        w = jnp.asarray(rng.normal(size=(K, N)), dtype=dtype)
        comm = fresh_comm(n)
        blocks = dict(block_m=M // n, block_n=N, block_k=K)
        s0 = exec_engine.exec_stats()
        got = fused_matmul_reduce_scatter(comm, x, w, **blocks)
        s1 = exec_engine.exec_stats()
        assert s1.fused_dispatches == s0.fused_dispatches + 1, (s0, s1)
        want = unfused_mm_rs(comm, x, w, **blocks)
        assert got.shape == (n, M // n, N)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    print("fused mm+RS bit-identity OK")


def check_fused_mm_rs_fallbacks():
    n, M, K, N = 8, 256, 128, 128
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(n, M, K)), dtype=jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, N)), dtype=jnp.float32)

    # blocks that do not tile the (Mc=32, K, N) chunk -> unfused path
    comm = fresh_comm(n)
    s0 = exec_engine.exec_stats()
    got = fused_matmul_reduce_scatter(comm, x, w, block_m=24)
    s1 = exec_engine.exec_stats()
    assert s1.fallback_dispatches == s0.fallback_dispatches + 1
    assert s1.fused_dispatches == s0.fused_dispatches
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(unfused_mm_rs(comm, x, w, block_m=24))
    )

    # grouped communicator -> unfused path (two groups of 4)
    split = fresh_comm(8).split([0, 0, 0, 0, 1, 1, 1, 1])
    s0 = exec_engine.exec_stats()
    got = fused_matmul_reduce_scatter(split, x, w, block_m=32, block_n=N,
                                      block_k=K)
    s1 = exec_engine.exec_stats()
    assert s1.fallback_dispatches == s0.fallback_dispatches + 1
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(unfused_mm_rs(split, x, w, block_m=32, block_n=N,
                                 block_k=K)),
    )
    print("fused mm+RS fallbacks OK")


def check_fused_ar_rmsnorm():
    n, rows, d = 8, 64, 256
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(n, rows, d)), dtype=jnp.float32)
    g = jnp.asarray(rng.normal(size=(d,)), dtype=jnp.float32)
    comm = fresh_comm(n)
    s0 = exec_engine.exec_stats()
    got = fused_all_reduce_rmsnorm(comm, x, g)
    s1 = exec_engine.exec_stats()
    assert s1.fused_dispatches == s0.fused_dispatches + 1
    want = rmsnorm(comm.all_reduce(x), g, use_pallas=True, interpret=INTERPRET)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    # local size not divisible by n (5*254 % 8 != 0) -> sequential
    # fallback, same result
    x_odd = jnp.asarray(rng.normal(size=(n, 5, 254)), dtype=jnp.float32)
    g_odd = jnp.asarray(rng.normal(size=(254,)), dtype=jnp.float32)
    s0 = exec_engine.exec_stats()
    got = fused_all_reduce_rmsnorm(comm, x_odd, g_odd)
    s1 = exec_engine.exec_stats()
    assert s1.fallback_dispatches == s0.fallback_dispatches + 1
    want = rmsnorm(comm.all_reduce(x_odd), g_odd, use_pallas=True,
                   interpret=INTERPRET)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    print("fused AR+rmsnorm OK")


def check_ring_ef8_execution():
    n, d = 8, 512
    rng = np.random.default_rng(13)
    x = jnp.asarray(rng.normal(size=(n, d)), dtype=jnp.float32)

    exact = np.asarray(fresh_comm(n, "ring").all_reduce(x))
    lossy = np.asarray(fresh_comm(n, "ring_ef8").all_reduce(x))
    assert lossy.shape == exact.shape
    assert not np.array_equal(lossy, exact)  # it really quantized the wire
    # documented first-order bound, in absolute form: the relative bound
    # (n-1)/127 is w.r.t. n*A where A = max per-rank magnitude
    A = float(np.abs(np.asarray(x)).max())
    bound = compressed_ef_error_bound(n) * n * A
    err = float(np.abs(lossy - exact).max())
    assert err <= bound, (err, bound)

    # grouped routing: two independent groups of 4, each within its bound
    lossy_g = np.asarray(
        fresh_comm(8, "ring_ef8").split([0, 0, 0, 0, 1, 1, 1, 1]).all_reduce(x)
    )
    exact_g = np.asarray(
        fresh_comm(8, "ring").split([0, 0, 0, 0, 1, 1, 1, 1]).all_reduce(x)
    )
    bound_g = compressed_ef_error_bound(4) * 4 * A
    err_g = float(np.abs(lossy_g - exact_g).max())
    assert err_g <= bound_g, (err_g, bound_g)
    print(f"ring_ef8 execution OK (err {err:.4f} <= bound {bound:.4f})")


if __name__ == "__main__":
    assert jax.device_count() == 8, jax.devices()
    check_fused_mm_rs_bit_identity()
    check_fused_mm_rs_fallbacks()
    check_fused_ar_rmsnorm()
    check_ring_ef8_execution()
    print("ALL-FUSION-OK")
