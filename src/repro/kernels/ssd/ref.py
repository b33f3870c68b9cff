"""Pure-jnp oracle for the Mamba-2 SSD chunked scan.

State space:  h_t = exp(la_t) · h_{t-1} + X_t ⊗ B_t ,   y_t = C_t · h_t
with per-(step, head) log-decay ``la`` (= dt·a for Mamba-2, = log f for
mLSTM-style gated linear attention) and pre-weighted inputs ``X`` (= dt·x for
Mamba-2, = i·v for mLSTM).

The chunked algorithm (chunk length L):
  * intra-chunk: Y_diag[t] = Σ_{s≤t, same chunk} exp(cum_t − cum_s)(C_t·B_s) X_s
  * chunk states: S_c = Σ_s exp(cum_last − cum_s) X_s ⊗ B_s
  * inter-chunk recurrence: R_{c+1} = exp(Σ la_c)·R_c + S_c   (lax.scan)
  * cross-chunk output: Y_off[t] = C_t · (exp(cum_t)·R_c)

B/C may be per-head (B,S,H,N) or shared across heads (B,S,N).
Returns (Y (B,S,H,P), final_state (B,H,P,N)).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def _bc_expand(m: jax.Array, H: int) -> jax.Array:
    if m.ndim == 3:  # (B,S,N) shared across heads
        return m[:, :, None, :]
    return m  # (B,S,H,N)


def ssd_reference(
    X: jax.Array,            # (B,S,H,P) pre-weighted inputs
    la: jax.Array,           # (B,S,H)   log decay per step
    Bm: jax.Array,           # (B,S,N) or (B,S,H,N)
    Cm: jax.Array,           # (B,S,N) or (B,S,H,N)
    *,
    chunk: int = 64,
    initial_state: Optional[jax.Array] = None,  # (B,H,P,N)
) -> Tuple[jax.Array, jax.Array]:
    B, S, H, P = X.shape
    orig_S = S
    if S % chunk:
        pad = chunk - S % chunk
        X = jnp.pad(X, ((0, 0), (0, pad), (0, 0), (0, 0)))
        la = jnp.pad(la, ((0, 0), (0, pad), (0, 0)))
        pad_spec = ((0, 0), (0, pad)) + ((0, 0),) * (Bm.ndim - 2)
        Bm = jnp.pad(Bm, pad_spec)
        Cm = jnp.pad(Cm, pad_spec)
        S = X.shape[1]
    L = chunk
    nc = S // L
    N = Bm.shape[-1]

    f32 = jnp.float32
    Xc = X.reshape(B, nc, L, H, P).astype(f32)
    lac = la.reshape(B, nc, L, H).astype(f32)
    Bc = _bc_expand(Bm, H).reshape(B, nc, L, -1, N).astype(f32)
    Cc = _bc_expand(Cm, H).reshape(B, nc, L, -1, N).astype(f32)
    Hb = Bc.shape[3]  # 1 (shared) or H

    cum = jnp.cumsum(lac, axis=2)                              # (B,nc,L,H)
    total = cum[:, :, -1, :]                                   # (B,nc,H)

    # intra-chunk: decay[t,s] = exp(cum_t - cum_s) for s<=t
    dec = cum[:, :, :, None, :] - cum[:, :, None, :, :]        # (B,nc,t,s,H)
    tri = jnp.tril(jnp.ones((L, L), bool))
    dec = jnp.where(tri[None, None, :, :, None], jnp.exp(dec), 0.0)
    scores = jnp.einsum("bclgn,bcmgn->bclmg", Cc, Bc)          # (B,nc,L,L,Hb)
    if Hb == 1:
        scores = jnp.broadcast_to(scores, scores.shape[:-1] + (H,))
    w = scores * dec                                           # (B,nc,L,L,H)
    Y_diag = jnp.einsum("bclmh,bcmhp->bclhp", w, Xc)

    # chunk states: S_c = Σ_s exp(total - cum_s) X_s ⊗ B_s   → (B,nc,H,P,N)
    decay_to_end = jnp.exp(total[:, :, None, :] - cum)         # (B,nc,L,H)
    Xw = Xc * decay_to_end[..., None]
    if Hb == 1:
        states = jnp.einsum("bclhp,bclgn->bchpn", Xw, Bc)  # g==1 summed out
    else:
        states = jnp.einsum("bclhp,bclhn->bchpn", Xw, Bc)

    # inter-chunk recurrence
    init = (
        jnp.zeros((B, H, P, N), f32)
        if initial_state is None
        else initial_state.astype(f32)
    )

    def step(carry, inp):
        st, tot = inp                                          # (B,H,P,N),(B,H)
        new = carry * jnp.exp(tot)[:, :, None, None] + st
        return new, carry                                      # emit state BEFORE chunk

    final, R = jax.lax.scan(
        step,
        init,
        (states.swapaxes(0, 1), total.swapaxes(0, 1)),
    )
    R = R.swapaxes(0, 1)                                       # (B,nc,H,P,N)

    # cross-chunk output: C_t · (exp(cum_t) · R_c)
    if Hb == 1:
        Y_off = jnp.einsum("bclgn,bchpn->bclhp", Cc, R)  # g broadcasts (g==1)
    else:
        Y_off = jnp.einsum("bclhn,bchpn->bclhp", Cc, R)
    Y_off = Y_off * jnp.exp(cum)[..., None]

    Y = (Y_diag + Y_off).reshape(B, S, H, P)[:, :orig_S]
    return Y.astype(X.dtype), final.astype(X.dtype)


def ssd_decode_step(
    state: jax.Array,        # (B,H,P,N)
    x: jax.Array,            # (B,H,P) pre-weighted input (dt·x)
    la: jax.Array,           # (B,H)   log decay
    Bm: jax.Array,           # (B,N) or (B,H,N)
    Cm: jax.Array,           # (B,N) or (B,H,N)
) -> Tuple[jax.Array, jax.Array]:
    """Single recurrent step: O(1) in context length."""
    f32 = jnp.float32
    if Bm.ndim == 2:
        Bm = Bm[:, None, :]
    if Cm.ndim == 2:
        Cm = Cm[:, None, :]
    st = state.astype(f32) * jnp.exp(la.astype(f32))[:, :, None, None]
    st = st + jnp.einsum("bhp,bhn->bhpn", x.astype(f32), jnp.broadcast_to(Bm, (x.shape[0], x.shape[1], Bm.shape[-1])).astype(f32))
    y = jnp.einsum("bhpn,bhn->bhp", st, jnp.broadcast_to(Cm, (x.shape[0], x.shape[1], Cm.shape[-1])).astype(f32))
    return y.astype(x.dtype), st.astype(state.dtype)
