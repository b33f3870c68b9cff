"""Attention: GQA/MQA with KV cache, MLA (DeepSeek-V2), cross-attention.

Three execution modes per layer:
* train     — full causal attention, no cache (flash kernel when enabled);
* prefill   — causal attention that also materializes the KV cache;
* decode    — one query token against a fixed-capacity cache.

MLA decode uses the *absorbed* formulation: queries are projected into the
KV-LoRA space so the cache stores only (c_kv, k_rope) — the paper-level
memory saving that makes deepseek-v2-lite's 32 K cache small.
"""

from __future__ import annotations

import math
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MLAConfig, ModelConfig
from repro.sharding import shard

from .layers import apply_rope
from .module import Box, KeyGen, normal_init

NEG_INF = -1e30


class KVCache(NamedTuple):
    k: jax.Array          # (B, T, K, Dh)  [MLA: (B, T, kv_lora)]
    v: jax.Array          # (B, T, K, Dv)  [MLA: (B, T, rope_dim) = k_rope]
    length: jax.Array     # () int32 — valid prefix


def init_cache(batch: int, max_len: int, n_kv: int, dh: int, dv: int, dtype) -> KVCache:
    return KVCache(
        k=jnp.zeros((batch, max_len, n_kv, dh), dtype),
        v=jnp.zeros((batch, max_len, n_kv, dv), dtype),
        length=jnp.zeros((), jnp.int32),
    )


def init_mla_cache(batch: int, max_len: int, mla: MLAConfig, dtype) -> KVCache:
    return KVCache(
        k=jnp.zeros((batch, max_len, mla.kv_lora), dtype),
        v=jnp.zeros((batch, max_len, mla.qk_rope_dim), dtype),
        length=jnp.zeros((), jnp.int32),
    )


# ------------------------------------------------------------------- GQA


def init_gqa(key, cfg: ModelConfig) -> Dict[str, Box]:
    kg = KeyGen(key)
    d, H, K, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    return {
        "wq": normal_init(kg(), (d, H, Dh), ("embed", "heads", None)),
        "wk": normal_init(kg(), (d, K, Dh), ("embed", "kv_heads", None)),
        "wv": normal_init(kg(), (d, K, Dh), ("embed", "kv_heads", None)),
        "wo": normal_init(kg(), (H, Dh, d), ("heads", None, "embed"), fan_in=H * Dh),
    }


def _attend(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool,
            q_positions: jax.Array, kv_valid_len: Optional[jax.Array]) -> jax.Array:
    """q: (B,S,H,D); k/v: (B,T,K,D). Grouped (GQA) softmax attention, fp32
    accumulators. q_positions: (B,S) absolute positions for causal masking.
    kv_valid_len limits attention to the cache's valid prefix."""
    B, S, H, D = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    qg = q.reshape(B, S, K, G, D)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32)
    scores = scores / math.sqrt(D)
    kv_pos = jnp.arange(T)[None, None, None, None, :]
    mask = jnp.ones((B, 1, 1, S, T), bool)
    if causal:
        mask = mask & (kv_pos <= q_positions[:, None, None, :, None])
    if kv_valid_len is not None:
        mask = mask & (kv_pos < kv_valid_len)
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(B, S, H, D)


def _attend_blocked(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool,
    block_q: int = 1024, block_k: int = 1024,
) -> jax.Array:
    """Online-softmax attention over KV blocks (the flash decomposition in
    pure jnp, Python-unrolled so HLO cost analysis stays exact).

    Never materializes the (S, T) score matrix — the §Perf lever for the
    memory-bound prefill cells — and skips KV blocks strictly above the
    causal diagonal (the ~2× causal FLOP saving the full einsum pays for).
    Assumes aligned q/kv windows (q position i attends kv ≤ i)."""
    B, S, H, D = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    bq, bk = min(block_q, S), min(block_k, T)
    assert S % bq == 0 and T % bk == 0, (S, T, bq, bk)
    scale = 1.0 / math.sqrt(D)

    outs = []
    for i in range(S // bq):
        qi = (q[:, i * bq : (i + 1) * bq].reshape(B, bq, K, G, D)
              .astype(jnp.float32) * scale)
        m = jnp.full((B, K, G, bq, 1), NEG_INF, jnp.float32)
        l = jnp.zeros((B, K, G, bq, 1), jnp.float32)
        acc = jnp.zeros((B, K, G, bq, D), jnp.float32)
        q_hi = (i + 1) * bq - 1
        for j in range(T // bk):
            if causal and j * bk > q_hi:
                break  # fully masked block: skipped statically
            kj = k[:, j * bk : (j + 1) * bk].astype(jnp.float32)
            vj = v[:, j * bk : (j + 1) * bk].astype(jnp.float32)
            s = jnp.einsum("bqkgd,btkd->bkgqt", qi, kj)
            if causal and (j + 1) * bk - 1 > i * bq:  # diagonal block
                qpos = i * bq + jnp.arange(bq)[:, None]
                kpos = j * bk + jnp.arange(bk)[None, :]
                s = jnp.where(kpos <= qpos, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            pbl = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l = alpha * l + pbl.sum(axis=-1, keepdims=True)
            acc = alpha * acc + jnp.einsum("bkgqt,btkd->bkgqd", pbl, vj)
            m = m_new
        o = (acc / jnp.maximum(l, 1e-30)).transpose(0, 3, 1, 2, 4)  # (B,bq,K,G,D)
        outs.append(o.reshape(B, bq, H, D))
    return jnp.concatenate(outs, axis=1).astype(q.dtype)


def apply_gqa(
    p,
    cfg: ModelConfig,
    x: jax.Array,
    *,
    positions: jax.Array,
    cache: Optional[KVCache] = None,
    mode: str = "train",            # train | prefill | decode
    rope_style: Optional[str] = None,
) -> Tuple[jax.Array, Optional[KVCache]]:
    dt = x.dtype
    B, S, _ = x.shape
    style = rope_style if rope_style is not None else cfg.rope_style
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    q = apply_rope(q, positions, style=style)
    k = apply_rope(k, positions, style=style)
    if mode == "decode":
        # Decode queries replicate over the model axis: the KV cache is
        # seq-sharded, and a heads-sharded q forces the partitioner to
        # re-shard (≈replicate) the whole cache every step (measured ~GB/step
        # — EXPERIMENTS.md §Perf chatglm iteration 2). Replicated q keeps the
        # score/context contractions local over the sharded cache length,
        # leaving only a small per-layer all-reduce of the (B,1,H,Dh) output.
        q = shard(q, ("batch", None, None, None))
    else:
        q = shard(q, ("batch", "seq", "heads", None))
    k = shard(k, ("batch", "seq", "kv_heads", None))
    v = shard(v, ("batch", "seq", "kv_heads", None))

    new_cache = None
    if mode == "bidir":  # encoder self-attention (whisper)
        ctx = _attend(q, k, v, causal=False, q_positions=positions, kv_valid_len=None)
    elif mode == "train":
        if cfg.use_pallas:
            from repro.kernels.flash import ops as flash_ops

            ctx = flash_ops.flash_attention(q, k, v, causal=True)
        elif cfg.attention_impl == "blocked":
            ctx = _attend_blocked(q, k, v, causal=True)
        else:
            ctx = _attend(q, k, v, causal=True, q_positions=positions, kv_valid_len=None)
    elif mode == "prefill":
        assert cache is not None
        ck = jax.lax.dynamic_update_slice(cache.k, k, (0, 0, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache.v, v, (0, 0, 0, 0))
        new_cache = KVCache(ck, cv, jnp.asarray(S, jnp.int32))
        if cfg.use_pallas:
            from repro.kernels.flash import ops as flash_ops

            ctx = flash_ops.flash_attention(q, k, v, causal=True)
        elif cfg.attention_impl == "blocked":
            ctx = _attend_blocked(q, k, v, causal=True)
        else:
            ctx = _attend(q, k, v, causal=True, q_positions=positions, kv_valid_len=None)
    elif mode == "decode":
        assert cache is not None and S == 1
        idx = cache.length
        # one-hot masked write instead of dynamic_update_slice: a DUS at a
        # dynamic offset along the seq-sharded cache axis makes the SPMD
        # partitioner reshard (≈replicate) the cache every step (measured
        # ~1 GB collective per layer per token — EXPERIMENTS.md §Perf
        # chatglm iteration 3). The masked write is elementwise → fully
        # local on a seq-sharded cache.
        T = cache.k.shape[1]
        sel = (jnp.arange(T) == idx)[None, :, None, None]
        ck = jnp.where(sel, k.astype(cache.k.dtype), cache.k)
        cv = jnp.where(sel, v.astype(cache.v.dtype), cache.v)
        ck = shard(ck, ("batch", "kv_seq", "kv_heads", None))
        cv = shard(cv, ("batch", "kv_seq", "kv_heads", None))
        new_cache = KVCache(ck, cv, idx + 1)
        ctx = _attend(
            q, ck, cv, causal=False, q_positions=positions, kv_valid_len=idx + 1
        )
    else:
        raise ValueError(mode)
    out = jnp.einsum("bshk,hkd->bsd", ctx, p["wo"].astype(dt))
    return shard(out, ("batch", "seq", "act_embed")), new_cache


# ------------------------------------------------------------------- MLA


def init_mla(key, cfg: ModelConfig) -> Dict[str, Box]:
    m = cfg.mla
    assert m is not None
    kg = KeyGen(key)
    d, H = cfg.d_model, cfg.n_heads
    qd = m.qk_nope_dim + m.qk_rope_dim
    return {
        "wq": normal_init(kg(), (d, H, qd), ("embed", "heads", None)),
        "w_dkv": normal_init(kg(), (d, m.kv_lora), ("embed", "kv_lora")),
        "w_kr": normal_init(kg(), (d, m.qk_rope_dim), ("embed", None)),
        "w_uk": normal_init(kg(), (m.kv_lora, H, m.qk_nope_dim), ("kv_lora", "heads", None)),
        "w_uv": normal_init(kg(), (m.kv_lora, H, m.v_dim), ("kv_lora", "heads", None)),
        "wo": normal_init(kg(), (H, m.v_dim, d), ("heads", None, "embed"), fan_in=H * m.v_dim),
    }


def apply_mla(
    p,
    cfg: ModelConfig,
    x: jax.Array,
    *,
    positions: jax.Array,
    cache: Optional[KVCache] = None,
    mode: str = "train",
) -> Tuple[jax.Array, Optional[KVCache]]:
    m = cfg.mla
    dt = x.dtype
    B, S, _ = x.shape
    H = cfg.n_heads
    dn, dr, dv = m.qk_nope_dim, m.qk_rope_dim, m.v_dim
    scale = 1.0 / math.sqrt(dn + dr)

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, style="full")
    c_kv = x @ p["w_dkv"].astype(dt)                       # (B,S,kv_lora)
    k_rope = (x @ p["w_kr"].astype(dt))[:, :, None, :]     # (B,S,1,dr)
    k_rope = apply_rope(k_rope, positions, style="full")[:, :, 0, :]

    def expanded_attention(q_nope, q_rope, c_kv_all, k_rope_all, kv_valid, causal):
        k_nope = jnp.einsum("btl,lhk->bthk", c_kv_all, p["w_uk"].astype(dt))
        v = jnp.einsum("btl,lhk->bthk", c_kv_all, p["w_uv"].astype(dt))
        s_nope = jnp.einsum("bshk,bthk->bhst", q_nope, k_nope)
        # rope part is per-head in q; the single shared k_rope broadcasts:
        s_rope = jnp.einsum("bshk,btk->bhst", q_rope, k_rope_all)
        scores = ((s_nope + s_rope) * scale).astype(jnp.float32)
        T = c_kv_all.shape[1]
        kv_pos = jnp.arange(T)[None, None, None, :]
        mask = jnp.ones((B, 1, S, T), bool)
        if causal:
            mask = mask & (kv_pos <= positions[:, None, :, None])
        if kv_valid is not None:
            mask = mask & (kv_pos < kv_valid)
        scores = jnp.where(mask, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(dt)
        return jnp.einsum("bhst,bthk->bshk", probs, v)

    new_cache = None
    if mode == "train":
        ctx = expanded_attention(q_nope, q_rope, c_kv, k_rope, None, True)
    elif mode == "prefill":
        assert cache is not None
        ck = jax.lax.dynamic_update_slice(cache.k, c_kv, (0, 0, 0))
        cr = jax.lax.dynamic_update_slice(cache.v, k_rope, (0, 0, 0))
        new_cache = KVCache(ck, cr, jnp.asarray(S, jnp.int32))
        ctx = expanded_attention(q_nope, q_rope, c_kv, k_rope, None, True)
    elif mode == "decode":
        assert cache is not None and S == 1
        idx = cache.length
        # masked write (see apply_gqa decode): local on a seq-sharded cache
        T = cache.k.shape[1]
        sel = (jnp.arange(T) == idx)[None, :, None]
        ck = jnp.where(sel, c_kv.astype(cache.k.dtype), cache.k)
        cr = jnp.where(sel, k_rope.astype(cache.v.dtype), cache.v)
        ck = shard(ck, ("batch", "kv_seq", None))
        cr = shard(cr, ("batch", "kv_seq", None))
        new_cache = KVCache(ck, cr, idx + 1)
        # absorbed decode: q_c = q_nope @ w_uk  → score against c_kv directly;
        # decode queries replicate over the model axis (see apply_gqa)
        q_c = jnp.einsum("bshk,lhk->bshl", q_nope, p["w_uk"].astype(dt))
        q_c = shard(q_c, ("batch", None, None, None))
        q_rope = shard(q_rope, ("batch", None, None, None))
        s_nope = jnp.einsum("bshl,btl->bhst", q_c, ck)
        s_rope = jnp.einsum("bshk,btk->bhst", q_rope, cr)
        scores = ((s_nope + s_rope) * scale).astype(jnp.float32)
        T = ck.shape[1]
        kv_pos = jnp.arange(T)[None, None, None, :]
        scores = jnp.where(kv_pos < idx + 1, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(dt)
        ctx_c = jnp.einsum("bhst,btl->bshl", probs, ck)      # (B,1,H,kv_lora)
        ctx = jnp.einsum("bshl,lhk->bshk", ctx_c, p["w_uv"].astype(dt))
    else:
        raise ValueError(mode)

    out = jnp.einsum("bshk,hkd->bsd", ctx, p["wo"].astype(dt))
    return shard(out, ("batch", "seq", "act_embed")), new_cache


# --------------------------------------------------------- cross-attention


def init_cross_attn(key, cfg: ModelConfig) -> Dict[str, Box]:
    return init_gqa(key, cfg)


def apply_cross_attn(p, cfg: ModelConfig, x: jax.Array, enc: jax.Array) -> jax.Array:
    """Decoder query over encoder memory (whisper). No causal mask, no rope."""
    dt = x.dtype
    k = jnp.einsum("btd,dhk->bthk", enc, p["wk"].astype(dt))
    v = jnp.einsum("btd,dhk->bthk", enc, p["wv"].astype(dt))
    return apply_cross_attn_cached(p, cfg, x, {"k": k, "v": v})


def apply_cross_attn_cached(p, cfg: ModelConfig, x: jax.Array, kv) -> jax.Array:
    """Cross-attention against precomputed encoder K/V (serving path)."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    B, S = x.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    ctx = _attend(q, kv["k"], kv["v"], causal=False, q_positions=pos, kv_valid_len=None)
    out = jnp.einsum("bshk,hkd->bsd", ctx, p["wo"].astype(dt))
    return shard(out, ("batch", "seq", "act_embed"))
