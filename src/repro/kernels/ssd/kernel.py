"""Pallas TPU kernel for the Mamba-2 SSD chunked scan.

Grid: (batch · heads, num_chunks).  The chunk axis is the *innermost* grid
dimension, which Pallas TPU iterates sequentially — the running recurrent
state R (P × N, fp32) lives in a VMEM scratch buffer and is carried across
chunk steps of the same (batch, head) program, reset when the chunk index
wraps to 0.  Per chunk the kernel computes

    Y_diag = ((C Bᵀ) ⊙ exp(cum_t − cum_s) tril) X        (intra-chunk, MXU)
    Y_off  = exp(cum_t) · (C R)                           (cross-chunk)
    R'     = exp(total) · R + Σ_s exp(total − cum_s) X_s ⊗ B_s

The per-chunk prefix sums ``cum`` of the log-decays are computed by the
wrapper (one XLA cumsum; Mosaic has no cumsum lowering) and fed to the
kernel as a lane-dense row (1, L).  The kernel turns the row into the
column it also needs with a masked lane reduction over the diagonal —
exact, since every other term is 0.0 — so neither a transpose of a
vector nor a scalar read is lowered, and HBM holds no (L, 1) column
padded 128-fold to the lane width.

Tile sizes: chunk length L × head_dim P and L × state N — L defaults to 128
(MXU-aligned); P/N are the model's head_dim/d_state (128/64 for the assigned
archs → aligned or half-aligned lanes).

B/C must be per-head here ((B,S,H,N)); the shared-across-heads layout of
Mamba-2's n_groups=1 is expanded by ``ops.ssd`` only when the Pallas path is
selected.  Validated against ``ref.ssd_reference`` in interpret mode.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    x_ref,      # (L, P)
    cum_ref,    # (1, L) per-chunk prefix sums of the log-decays
    b_ref,      # (L, N)
    c_ref,      # (L, N)
    y_ref,      # (L, P)
    fin_ref,    # (P, N) final-state output
    state_ref,  # (P, N) fp32 VMEM scratch — running inter-chunk state
):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[...].astype(jnp.float32)          # (L, P)
    cum_row = cum_ref[...]                      # (1, L)
    b = b_ref[...].astype(jnp.float32)          # (L, N)
    c = c_ref[...].astype(jnp.float32)          # (L, N)
    L = x.shape[0]
    total = cum_row[:, L - 1:]                  # (1, 1)

    row = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    cum_full = jnp.broadcast_to(cum_row, (L, L))
    cum = jnp.sum(jnp.where(row == col, cum_full, 0.0), axis=1, keepdims=True)  # (L, 1)

    # intra-chunk
    dec = cum - cum_row                         # (L, L): cum_t - cum_s
    tri = row >= col
    w = jnp.where(tri, jnp.exp(dec), 0.0) * (c @ b.T)
    y = w @ x                                   # (L, P)

    # cross-chunk using state BEFORE this chunk
    R = state_ref[...]                          # (P, N)
    y = y + jnp.exp(cum) * (c @ R.T)

    # update state
    decay_to_end = jnp.exp(total - cum)         # (L, 1)
    new_state = jnp.exp(total) * R + (x * decay_to_end).T @ b
    state_ref[...] = new_state
    y_ref[...] = y.astype(y_ref.dtype)

    nc = pl.num_programs(1)

    @pl.when(ci == nc - 1)
    def _emit():
        fin_ref[...] = new_state.astype(fin_ref.dtype)


def ssd_pallas(
    X: jax.Array,    # (B, S, H, P)
    la: jax.Array,   # (B, S, H)
    Bm: jax.Array,   # (B, S, N) or (B, S, H, N)
    Cm: jax.Array,   # same as Bm
    *,
    chunk: int = 128,
    initial_state=None,
    interpret: bool = False,
):
    assert initial_state is None, "pallas path starts from zero state"
    B, S, H, P = X.shape
    if Bm.ndim == 3:
        Bm = jnp.broadcast_to(Bm[:, :, None, :], (B, S, H, Bm.shape[-1]))
        Cm = jnp.broadcast_to(Cm[:, :, None, :], (B, S, H, Cm.shape[-1]))
    N = Bm.shape[-1]
    orig_S = S
    if S % chunk:
        pad = chunk - S % chunk
        X = jnp.pad(X, ((0, 0), (0, pad), (0, 0), (0, 0)))
        la = jnp.pad(la, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        S = X.shape[1]
    nc = S // chunk

    xb = X.transpose(0, 2, 1, 3).reshape(B * H, S, P)
    cum = jnp.cumsum(
        la.astype(jnp.float32).transpose(0, 2, 1).reshape(B * H, nc, chunk),
        axis=-1,
    )
    cum = cum.reshape(B * H, nc, 1, chunk)
    bb = Bm.transpose(0, 2, 1, 3).reshape(B * H, S, N)
    cb = Cm.transpose(0, 2, 1, 3).reshape(B * H, S, N)

    grid = (B * H, nc)
    y, fin = pl.pallas_call(
        _ssd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, chunk, P), lambda h, c: (h, c, 0)),
            pl.BlockSpec((None, None, 1, chunk), lambda h, c: (h, c, 0, 0)),
            pl.BlockSpec((None, chunk, N), lambda h, c: (h, c, 0)),
            pl.BlockSpec((None, chunk, N), lambda h, c: (h, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, chunk, P), lambda h, c: (h, c, 0)),
            pl.BlockSpec((None, P, N), lambda h, c: (h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, P), X.dtype),
            jax.ShapeDtypeStruct((B * H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xb, cum, bb, cb)
    Y = y.reshape(B, H, S, P).transpose(0, 2, 1, 3)[:, :orig_S]
    final = fin.reshape(B, H, P, N)
    return Y, final
