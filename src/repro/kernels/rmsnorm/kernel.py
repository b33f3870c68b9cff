"""Pallas TPU kernel: fused RMSNorm × weight.

Grid over row blocks of the flattened (rows, d) input; each program loads a
(block_rows, d) tile into VMEM, reduces in fp32, scales by the (d,)-broadcast
weight, and writes the tile back — one HBM round-trip instead of the three
(square-reduce / rsqrt-mul / weight-mul) an unfused lowering can incur.

The row block is sized from a VMEM budget (:func:`default_block_rows`):
each program holds the whole padded feature row, so the block shrinks as
``d`` grows — a fixed 256-row block does not fit the chip's scoped VMEM
past d≈2048 in fp32.  Both axes are padded to legal tile shapes: rows up
to a multiple of ``block_rows``, and the feature axis up to a multiple of
the 128-lane VPU width.  The lane padding is zeros, which contribute
exactly 0.0 to the square-sum, so dividing by the *true* ``d`` (not the
padded width) keeps the numerics bit-identical to the unpadded mean.
Degenerate inputs (``rows == 0`` or ``d == 0``) raise ``ValueError``
instead of building an empty grid.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128  # TPU VPU lane width: the last tile dim must be a multiple
MAX_BLOCK_ROWS = 256
# Bytes one program may keep in VMEM: well inside the compiler's default
# scoped limit (16 MiB on v5e), leaving room for its own temporaries.
VMEM_BUDGET = 8 * 1024 * 1024


def default_block_rows(rows: int, d_padded: int, itemsize: int) -> int:
    """Row block whose working set fits :data:`VMEM_BUDGET`.

    Per row: the input and output tiles, each double-buffered by the
    pipeline (``4 · itemsize`` bytes per element), plus about three fp32
    temporaries in the body (upcast, square, scaled product).  The block
    is a multiple of the dtype's sublane packing (8 rows of fp32, 16 of
    bf16), at least one packing and at most :data:`MAX_BLOCK_ROWS`; among
    those that fit, the largest that divides ``rows`` is preferred, so
    the common aligned case needs no row padding (an extra HBM copy).
    """
    sublane = 8 * max(1, 4 // itemsize)
    per_row = d_padded * (4 * itemsize + 3 * 4)
    cap = max(sublane, min(MAX_BLOCK_ROWS, VMEM_BUDGET // per_row) // sublane * sublane)
    for block in range(cap, 0, -sublane):
        if rows % block == 0:
            return block
    return cap


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float, d: int):
    x = x_ref[...].astype(jnp.float32)               # (rows, d_padded)
    w = w_ref[...].astype(jnp.float32)               # (1, d_padded)
    # zero lane-padding adds 0.0 to the sum; dividing by the true d gives
    # exactly the mean over the real features
    var = jnp.sum(jnp.square(x), axis=-1, keepdims=True) / d
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) * w).astype(o_ref.dtype)


def rmsnorm_pallas(
    x: jax.Array,
    w: jax.Array,
    *,
    eps: float = 1e-5,
    block_rows: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    orig_shape = x.shape
    d = x.shape[-1]
    if d == 0:
        raise ValueError(f"rmsnorm_pallas: feature dim is 0 (shape {orig_shape})")
    rows = x.size // d
    if rows == 0:
        raise ValueError(
            f"rmsnorm_pallas: input has no rows (shape {orig_shape}); "
            "an empty batch would build an empty Pallas grid"
        )
    if w.size != d:
        raise ValueError(
            f"rmsnorm_pallas: weight size {w.size} != feature dim {d}"
        )
    xf = x.reshape(rows, d)
    wf = w.reshape(1, d)
    lane_pad = (-d) % LANE
    if lane_pad:
        xf = jnp.pad(xf, ((0, 0), (0, lane_pad)))
        wf = jnp.pad(wf, ((0, 0), (0, lane_pad)))
    dp = d + lane_pad
    if block_rows is None:
        block_rows = default_block_rows(rows, dp, x.dtype.itemsize)
    block_rows = min(block_rows, rows)
    row_pad = (-rows) % block_rows
    if row_pad:
        xf = jnp.pad(xf, ((0, row_pad), (0, 0)))
    grid = (xf.shape[0] // block_rows,)
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps, d=d),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, dp), lambda i: (i, 0)),
            pl.BlockSpec((1, dp), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, dp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xf.shape, x.dtype),
        interpret=interpret,
    )(xf, wf)
    if row_pad or lane_pad:
        out = out[:rows, :d]
    return out.reshape(orig_shape)
