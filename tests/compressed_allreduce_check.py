"""Device-level checks for the int8-compressed all-reduce (+error feedback).

Run as a subprocess by test_compressed_allreduce.py with 4 host devices.
Asserts the documented quantization-error bound against ``lax.psum`` and
the error-feedback bias-shrinking property across steps.
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=4 "
    + os.environ.get("XLA_FLAGS", "")
)

import jax
import numpy as np
from jax import lax
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.comm.pccl_collectives import (
    ErrorFeedbackState,
    compressed_all_reduce,
    compressed_all_reduce_ef,
)

N = 4


def _mesh():
    return Mesh(jax.devices()[:N], ("x",))


def _smap(f, mesh, in_specs, out_specs):
    return jax.jit(
        jax.shard_map(
            f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
        )
    )


def check_quantization_bound_vs_psum():
    """|compressed_all_reduce - psum| within the documented per-hop bound.

    The wire format quantizes once per RS hop (n-1 hops, the payload being a
    partial sum of ≤ j addends) plus once before the AG phase (the full
    n-addend sum); each quantization errs ≤ scale/2 = max|payload| / 254.
    Summing the worst cases gives err ≤ A·(Σ_{j≤n-1} j + n) / 254 with
    A = max per-rank per-element magnitude — we assert with a 2× slack for
    the error the bound's own payload-magnitude estimate feeds back in.
    """
    mesh = _mesh()
    rng = np.random.default_rng(0)
    X = rng.normal(size=(N, N * 32)).astype(np.float32)

    out = np.asarray(
        _smap(lambda x: compressed_all_reduce(x[0], "x", N), mesh, P("x", None), P(None))(X)
    )
    want = np.asarray(
        _smap(lambda x: lax.psum(x[0], "x"), mesh, P("x", None), P(None))(X)
    )
    np.testing.assert_allclose(want, X.sum(axis=0), rtol=1e-6)

    A = np.abs(X).max()
    hops_weight = sum(range(1, N)) + N  # RS partial-sum hops + the AG quant
    bound = 2.0 * A * hops_weight / 254.0
    err = np.abs(out - want).max()
    assert err <= bound, (err, bound)
    # and the bound is doing work: the reduction is genuinely close
    rel = np.abs(out - want) / (np.abs(want) + 1e-6)
    assert np.median(rel) < 0.05, np.median(rel)
    print(f"quantization bound OK (err {err:.4f} <= bound {bound:.4f})")


def check_error_feedback_shrinks_bias():
    """Averaging EF-compensated reductions of the SAME gradient converges
    toward the exact sum; without EF the bias is static."""
    mesh = _mesh()
    rng = np.random.default_rng(1)
    X = rng.normal(size=(N, N * 16)).astype(np.float32)
    want = X.sum(axis=0)

    raw = np.asarray(
        _smap(lambda x: compressed_all_reduce(x[0], "x", N), mesh, P("x", None), P(None))(X)
    )
    err_raw = np.abs(raw - want).mean()

    def g(x, r):
        red, ef = compressed_all_reduce_ef(x[0], ErrorFeedbackState(r[0]), "x", N)
        return red, ef.residual[None]

    step = _smap(g, mesh, (P("x", None), P("x", None)), (P(None), P("x", None)))
    r = np.zeros_like(X)
    accum = np.zeros_like(want)
    errs = []
    for k in range(1, 9):
        red, r = step(X, r)
        accum += np.asarray(red)
        errs.append(np.abs(accum / k - want).mean())

    err_1, err_8 = errs[0], errs[-1]
    assert err_8 < err_1, (err_8, err_1)  # bias shrinks across steps
    assert err_8 <= err_raw * 1.05, (err_8, err_raw)
    print(f"error feedback OK (bias {err_1:.5f} -> {err_8:.5f}, raw {err_raw:.5f})")


def main():
    assert jax.device_count() == N, jax.devices()
    check_quantization_bound_vs_psum()
    check_error_feedback_shrinks_bias()
    print("ALL-COMPRESSED-OK")


if __name__ == "__main__":
    main()
