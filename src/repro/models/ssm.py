"""State-space & recurrent blocks: Mamba-2 (SSD), xLSTM (mLSTM + sLSTM).

All three expose the same three-mode interface as attention layers:
* ``train/prefill`` — chunkwise-parallel over the sequence (SSD scan);
  prefill also returns the recurrent state so decode can continue from it;
* ``decode`` — O(1)-per-token recurrent update (what makes 500 K-token
  decode tractable for the ssm/hybrid archs — DESIGN.md §3.2).

Deviation notes (DESIGN.md §3.1): mLSTM uses a sigmoid input gate instead of
the exp-gate + m-stabilizer (same state-space form, numerically robust in
bf16; the n-normalizer is kept).  The pre-QK causal conv of xLSTM is elided.
"""

from __future__ import annotations

import math
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.ssd import ops as ssd_ops
from repro.sharding import shard

from .module import Box, KeyGen, const_init, normal_init, ones_init, zeros_init

# =============================================================== Mamba-2


class Mamba2State(NamedTuple):
    conv: jax.Array   # (B, conv_width-1, d_inner + 2*d_state)
    ssm: jax.Array    # (B, H, P, N)


def _mamba_dims(cfg: ModelConfig):
    s = cfg.ssm
    di = s.expand * cfg.d_model
    H = di // s.head_dim
    return s, di, H, s.head_dim, s.d_state


def init_mamba2(key, cfg: ModelConfig) -> Dict[str, Box]:
    s, di, H, P, N = _mamba_dims(cfg)
    kg = KeyGen(key)
    d = cfg.d_model
    proj_out = 2 * di + 2 * N + H
    dt_init = jnp.log(jnp.exp(jnp.linspace(1e-3, 0.1, H)) - 1.0)  # softplus^-1
    return {
        "in_proj": normal_init(kg(), (d, proj_out), ("embed", "ssm_inner")),
        "conv_w": normal_init(kg(), (s.conv_width, di + 2 * N), (None, "ssm_inner"), scale=0.5),
        "conv_b": zeros_init((di + 2 * N,), ("ssm_inner",)),
        "A_log": const_init(jnp.log(jnp.linspace(1.0, 16.0, H)), ("ssm_heads",)),
        "D": ones_init((H,), ("ssm_heads",)),
        "dt_bias": const_init(dt_init, ("ssm_heads",)),
        "norm_scale": ones_init((di,), ("ssm_inner",)),
        "out_proj": normal_init(kg(), (di, d), ("ssm_inner", "embed")),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 state: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal conv via shifted adds. x: (B,S,C); w: (cw,C).
    If `state` (B,cw-1,C) is given it provides left context (decode/prefill
    continuation); returns (y, new_state = last cw-1 inputs)."""
    cw = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], cw - 1, x.shape[2]), x.dtype)
    ext = jnp.concatenate([state, x], axis=1)          # (B, S+cw-1, C)
    y = b
    S = x.shape[1]
    for j in range(cw):
        y = y + ext[:, j : j + S, :] * w[j]
    new_state = ext[:, -(cw - 1) :, :] if cw > 1 else state
    return y, new_state


def apply_mamba2(
    p,
    cfg: ModelConfig,
    x: jax.Array,
    *,
    state: Optional[Mamba2State] = None,
    mode: str = "train",
) -> Tuple[jax.Array, Optional[Mamba2State]]:
    s, di, H, P, N = _mamba_dims(cfg)
    dt_ = x.dtype
    B, S, _ = x.shape

    proj = x @ p["in_proj"].astype(dt_)
    z, xin, Bc, Cc, dtr = jnp.split(proj, [di, 2 * di, 2 * di + N, 2 * di + 2 * N], axis=-1)

    xBC = jnp.concatenate([xin, Bc, Cc], axis=-1)
    conv_state_in = state.conv if state is not None else None

    if mode == "decode":
        assert state is not None and S == 1
        xBC, new_conv = _causal_conv(xBC, p["conv_w"].astype(dt_), p["conv_b"].astype(dt_), conv_state_in)
    else:
        xBC, new_conv = _causal_conv(xBC, p["conv_w"].astype(dt_), p["conv_b"].astype(dt_), None)
    xBC = jax.nn.silu(xBC)
    xin, Bc, Cc = jnp.split(xBC, [di, di + N], axis=-1)

    xh = xin.reshape(B, S, H, P)
    xh = shard(xh, ("batch", "seq", "ssm_heads", None))
    dt = jax.nn.softplus(dtr.astype(jnp.float32) + p["dt_bias"])         # (B,S,H)
    a = -jnp.exp(p["A_log"].astype(jnp.float32))                          # (H,)
    la = (dt * a).astype(jnp.float32)
    Xw = (xh.astype(jnp.float32) * dt[..., None]).astype(dt_)

    new_state: Optional[Mamba2State] = None
    if mode == "decode":
        y, new_ssm = ssd_ops.ssd_decode_step(
            state.ssm, Xw[:, 0], la[:, 0], Bc[:, 0], Cc[:, 0]
        )
        y = y[:, None]                                                    # (B,1,H,P)
        new_state = Mamba2State(new_conv, new_ssm)
    else:
        init = state.ssm if state is not None else None
        y, final = ssd_ops.ssd(
            Xw, la, Bc, Cc, chunk=s.chunk, initial_state=init,
            use_pallas=cfg.use_pallas,
        )
        if mode == "prefill":
            new_state = Mamba2State(new_conv, final)

    y = y + xh * p["D"].astype(dt_)[None, None, :, None]
    y = y.reshape(B, S, di)
    # gated RMSNorm (Mamba-2): norm(y * silu(z)) * scale
    g = (y * jax.nn.silu(z)).astype(jnp.float32)
    var = jnp.mean(jnp.square(g), axis=-1, keepdims=True)
    g = (g * jax.lax.rsqrt(var + cfg.norm_eps) * p["norm_scale"]).astype(dt_)
    out = g @ p["out_proj"].astype(dt_)
    return shard(out, ("batch", "seq", "act_embed")), new_state


def init_mamba2_state(cfg: ModelConfig, batch: int, dtype) -> Mamba2State:
    s, di, H, P, N = _mamba_dims(cfg)
    return Mamba2State(
        conv=jnp.zeros((batch, s.conv_width - 1, di + 2 * N), dtype),
        ssm=jnp.zeros((batch, H, P, N), dtype),
    )


# ================================================================ mLSTM


class MLSTMState(NamedTuple):
    C: jax.Array     # (B, H, P, N) matrix memory
    n: jax.Array     # (B, H, 1, N) normalizer


def _mlstm_dims(cfg: ModelConfig):
    pf = cfg.xlstm.proj_factor
    di = int(pf * cfg.d_model)
    H = cfg.n_heads
    P = di // H
    N = cfg.d_model // H  # qk head dim = assigned head_dim
    return di, H, P, N


def init_mlstm(key, cfg: ModelConfig) -> Dict[str, Box]:
    kg = KeyGen(key)
    d = cfg.d_model
    di, H, P, N = _mlstm_dims(cfg)
    return {
        "up": normal_init(kg(), (d, 2 * di), ("embed", "ssm_inner")),
        # block-diagonal per-head projections (xLSTM's design; keeps the
        # 1.3B budget: dense di×di q/k/v would triple the block size)
        "wq": normal_init(kg(), (H, P, N), ("ssm_heads", None, None), fan_in=P),
        "wk": normal_init(kg(), (H, P, N), ("ssm_heads", None, None), fan_in=P),
        "wv": normal_init(kg(), (H, P, P), ("ssm_heads", None, None), fan_in=P),
        "w_igate": normal_init(kg(), (d, H), ("embed", "ssm_heads"), scale=0.02),
        "b_igate": zeros_init((H,), ("ssm_heads",)),
        "w_fgate": normal_init(kg(), (d, H), ("embed", "ssm_heads"), scale=0.02),
        "b_fgate": const_init(jnp.full((H,), 3.0), ("ssm_heads",)),  # open forget
        "norm_scale": ones_init((di,), ("ssm_inner",)),
        "down": normal_init(kg(), (di, d), ("ssm_inner", "embed")),
    }


def apply_mlstm(
    p,
    cfg: ModelConfig,
    x: jax.Array,
    *,
    state: Optional[MLSTMState] = None,
    mode: str = "train",
) -> Tuple[jax.Array, Optional[MLSTMState]]:
    di, H, P, N = _mlstm_dims(cfg)
    dt_ = x.dtype
    B, S, _ = x.shape
    up = x @ p["up"].astype(dt_)
    u, z = jnp.split(up, 2, axis=-1)
    uh = u.reshape(B, S, H, P)
    q = jnp.einsum("bshp,hpn->bshn", uh, p["wq"].astype(dt_)) / math.sqrt(N)
    k = jnp.einsum("bshp,hpn->bshn", uh, p["wk"].astype(dt_)) / math.sqrt(N)
    v = jnp.einsum("bshp,hpq->bshq", uh, p["wv"].astype(dt_))
    i = jax.nn.sigmoid((x @ p["w_igate"].astype(dt_)).astype(jnp.float32) + p["b_igate"])
    la = jax.nn.log_sigmoid((x @ p["w_fgate"].astype(dt_)).astype(jnp.float32) + p["b_fgate"])

    Xw = (v.astype(jnp.float32) * i[..., None]).astype(dt_)       # i·v
    ones = (jnp.ones((B, S, H, 1), jnp.float32) * i[..., None]).astype(dt_)

    new_state: Optional[MLSTMState] = None
    if mode == "decode":
        assert state is not None and S == 1
        num, newC = ssd_ops.ssd_decode_step(state.C, Xw[:, 0], la[:, 0], k[:, 0], q[:, 0])
        den, newn = ssd_ops.ssd_decode_step(state.n, ones[:, 0], la[:, 0], k[:, 0], q[:, 0])
        num, den = num[:, None], den[:, None]
        new_state = MLSTMState(newC, newn)
    else:
        initC = state.C if state is not None else None
        initn = state.n if state is not None else None
        num, finC = ssd_ops.ssd(Xw, la, k, q, chunk=cfg.xlstm.chunk,
                                initial_state=initC, use_pallas=cfg.use_pallas)
        den, finn = ssd_ops.ssd(ones, la, k, q, chunk=cfg.xlstm.chunk,
                                initial_state=initn)
        if mode == "prefill":
            new_state = MLSTMState(finC, finn)

    y = num.astype(jnp.float32) / jnp.maximum(jnp.abs(den.astype(jnp.float32)), 1.0)
    y = y.reshape(B, S, di).astype(dt_)
    # output norm, gated by silu(z)
    var = jnp.mean(jnp.square(y.astype(jnp.float32)), axis=-1, keepdims=True)
    y = (y.astype(jnp.float32) * jax.lax.rsqrt(var + cfg.norm_eps) * p["norm_scale"]).astype(dt_)
    y = y * jax.nn.silu(z)
    out = y @ p["down"].astype(dt_)
    return shard(out, ("batch", "seq", "act_embed")), new_state


def init_mlstm_state(cfg: ModelConfig, batch: int, dtype) -> MLSTMState:
    di, H, P, N = _mlstm_dims(cfg)
    return MLSTMState(
        C=jnp.zeros((batch, H, P, N), dtype),
        n=jnp.zeros((batch, H, 1, N), dtype),
    )


# ================================================================ sLSTM


class SLSTMState(NamedTuple):
    h: jax.Array    # (B, H, Dh)
    c: jax.Array    # (B, H, Dh)
    n: jax.Array    # (B, H, Dh)
    m: jax.Array    # (B, H, Dh)


def _slstm_dims(cfg: ModelConfig):
    H = cfg.n_heads
    Dh = cfg.d_model // H
    return H, Dh


def init_slstm(key, cfg: ModelConfig) -> Dict[str, Box]:
    kg = KeyGen(key)
    d = cfg.d_model
    H, Dh = _slstm_dims(cfg)
    f_mlp = max(int(4 * d / 3) // 2 * 2, 8)
    return {
        "w": normal_init(kg(), (d, 4, H, Dh), ("embed", None, "ssm_heads", None)),
        "r": normal_init(kg(), (H, Dh, 4, Dh), ("ssm_heads", None, None, None), fan_in=Dh),
        "b": const_init(
            jnp.concatenate([jnp.zeros((2, H, Dh)) , jnp.zeros((2, H, Dh))]).reshape(4, H, Dh)
            .at[1].set(2.0),  # forget-gate bias
            (None, "ssm_heads", None),
        ),
        "norm_scale": ones_init((d,), ("embed",)),
        "ff1": normal_init(kg(), (d, 2 * f_mlp), ("embed", "mlp")),
        "ff2": normal_init(kg(), (f_mlp, d), ("mlp", "embed")),
    }


def _slstm_step(p, x_t: jax.Array, st: SLSTMState) -> Tuple[jax.Array, SLSTMState]:
    """One sLSTM timestep with exp gating + m-stabilizer. x_t: (B, d)."""
    f32 = jnp.float32
    pre = jnp.einsum("bd,dghk->bghk", x_t.astype(f32), p["w"].astype(f32))
    pre = pre + jnp.einsum("bhk,hkgj->bghj", st.h.astype(f32), p["r"].astype(f32))
    pre = pre + p["b"].astype(f32)
    iraw, fraw, zraw, oraw = pre[:, 0], pre[:, 1], pre[:, 2], pre[:, 3]
    m_new = jnp.maximum(fraw + st.m.astype(f32), iraw)
    i = jnp.exp(iraw - m_new)
    f = jnp.exp(fraw + st.m.astype(f32) - m_new)
    c = f * st.c.astype(f32) + i * jnp.tanh(zraw)
    n = f * st.n.astype(f32) + i
    h = jax.nn.sigmoid(oraw) * c / jnp.maximum(n, 1.0)
    new = SLSTMState(h.astype(st.h.dtype), c.astype(st.c.dtype),
                     n.astype(st.n.dtype), m_new.astype(st.m.dtype))
    return h, new


def apply_slstm(
    p,
    cfg: ModelConfig,
    x: jax.Array,
    *,
    state: Optional[SLSTMState] = None,
    mode: str = "train",
) -> Tuple[jax.Array, Optional[SLSTMState]]:
    H, Dh = _slstm_dims(cfg)
    dt_ = x.dtype
    B, S, d = x.shape
    st = state if state is not None else init_slstm_state(cfg, B, jnp.float32)

    if mode == "decode":
        assert S == 1
        h, new_state = _slstm_step(p, x[:, 0], st)
        y = h.reshape(B, 1, d).astype(dt_)
    else:
        def body(carry, x_t):
            h, new = _slstm_step(p, x_t, carry)
            return new, h

        final, hs = jax.lax.scan(body, st, x.swapaxes(0, 1))
        y = hs.swapaxes(0, 1).reshape(B, S, d).astype(dt_)
        new_state = final if mode == "prefill" else None

    # output norm + small GLU FFN (xLSTM sLSTM block carries its own MLP)
    var = jnp.mean(jnp.square(y.astype(jnp.float32)), axis=-1, keepdims=True)
    y = (y.astype(jnp.float32) * jax.lax.rsqrt(var + cfg.norm_eps) * p["norm_scale"]).astype(dt_)
    g, u = jnp.split(y @ p["ff1"].astype(dt_), 2, axis=-1)
    y = (jax.nn.gelu(g) * u) @ p["ff2"].astype(dt_)
    return shard(y, ("batch", "seq", "act_embed")), new_state


def init_slstm_state(cfg: ModelConfig, batch: int, dtype) -> SLSTMState:
    H, Dh = _slstm_dims(cfg)
    z = jnp.zeros((batch, H, Dh), dtype)
    return SLSTMState(z, z, z, jnp.full((batch, H, Dh), -30.0, dtype))
