"""Model builders for all assigned architecture families.

``build_model(cfg)`` returns a :class:`Model` exposing:

* ``init(key)``                          → Box param tree (values + logical axes)
* ``loss(params, batch, rng)``           → (scalar loss, metrics)     [train_4k]
* ``prefill(params, batch)``             → (last-pos logits, decode state)
                                                                      [prefill_32k]
* ``decode_step(params, state, tokens)`` → (logits, new state)        [decode_32k,
                                                                       long_500k]
* ``init_decode_state(batch, max_len)``  → zeroed cache/state tree

Layer stacks are ``lax.scan``-ed over stacked parameters (one compiled layer
body regardless of depth — essential for 88-layer dry-run compiles), with
``jax.checkpoint`` remat around the train body.  Heterogeneous stacks
(DeepSeek dense layer 0, xLSTM sLSTM cadence, Zamba2 shared-attention cadence)
scan over repeating *groups*.

Modality frontends are stubs per the assignment: batches carry precomputed
``img_embeds`` (vlm) / ``enc_frames`` (audio) at ``d_model`` width.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.sharding import shard

from . import attention as A
from . import moe as M
from . import ssm as SSM
from .layers import (
    apply_mlp,
    apply_norm,
    embed_lookup,
    init_embedding,
    init_mlp,
    init_norm,
    logits_projection,
    sinusoidal_positions,
)
from .module import Box, KeyGen, normal_init, stack_init

Batch = Dict[str, jax.Array]

# Named scopes of the encoder-decoder's parts, which profiler traces name
# its ops by (bench/encdec_scopes.py reads them there)
ENCODER_SCOPE = "encdec_encoder"
CROSS_ATTENTION_SCOPE = "encdec_cross_attention"


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def _maybe_scan(cfg: ModelConfig, body, carry, xs):
    """lax.scan over stacked layer params, or a Python unroll when
    cfg.scan_layers=False (the roofline path: XLA cost analysis counts while
    bodies once, so exact FLOP/byte accounting needs unrolled modules)."""
    if cfg.scan_layers:
        return jax.lax.scan(body, carry, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        x_i = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
    else:
        ys = None
    return carry, ys


def _positions(B: int, S: int, offset=0) -> jax.Array:
    return jnp.broadcast_to(jnp.arange(S)[None] + offset, (B, S))


def _xent(logits: jax.Array, targets: jax.Array) -> jax.Array:
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return (lse - gold).mean()


# ===========================================================================
# Transformer decoder layer (dense / moe / vlm / audio-decoder)
# ===========================================================================


def _init_decoder_layer(key, cfg: ModelConfig, *, kind: str, cross: bool = False):
    kg = KeyGen(key)
    p: Dict[str, Any] = {"ln1": init_norm(cfg.d_model, cfg.norm_type)}
    p["attn"] = A.init_mla(kg(), cfg) if cfg.mla else A.init_gqa(kg(), cfg)
    if cross:
        p["ln_x"] = init_norm(cfg.d_model, cfg.norm_type)
        p["xattn"] = A.init_cross_attn(kg(), cfg)
    p["ln2"] = init_norm(cfg.d_model, cfg.norm_type)
    if kind == "moe":
        p["ffn"] = M.init_moe(kg(), cfg)
    elif kind == "dense_wide":  # DeepSeek first dense layer
        p["ffn"] = init_mlp(kg(), cfg.d_model, cfg.moe.d_first_dense_ff, cfg.mlp_type)
    else:
        p["ffn"] = init_mlp(kg(), cfg.d_model, cfg.d_ff, cfg.mlp_type)
    return p


def _apply_decoder_layer(
    p, cfg: ModelConfig, x, *, positions, cache, mode, kind: str,
    enc: Optional[jax.Array] = None, cross_kv=None,
):
    h = apply_norm(p["ln1"], x, eps=cfg.norm_eps, norm_type=cfg.norm_type)
    attn_fn = A.apply_mla if cfg.mla else A.apply_gqa
    a_out, new_cache = attn_fn(p["attn"], cfg, h, positions=positions, cache=cache, mode=mode)
    x = x + a_out
    if "xattn" in p:
        with jax.named_scope(CROSS_ATTENTION_SCOPE):
            h = apply_norm(p["ln_x"], x, eps=cfg.norm_eps, norm_type=cfg.norm_type)
            if cross_kv is not None:
                xa = A.apply_cross_attn_cached(p["xattn"], cfg, h, cross_kv)
            else:
                xa = A.apply_cross_attn(p["xattn"], cfg, h, enc)
            x = x + xa
    h = apply_norm(p["ln2"], x, eps=cfg.norm_eps, norm_type=cfg.norm_type)
    if kind == "moe":
        f_out, aux = M.apply_moe(p["ffn"], cfg, h)
    else:
        f_out, aux = apply_mlp(p["ffn"], h, mlp_type=cfg.mlp_type), jnp.zeros((), jnp.float32)
    return x + f_out, new_cache, aux


# ===========================================================================
# Model base
# ===========================================================================


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # -- to be provided by subclasses ------------------------------------
    def init(self, key):  # pragma: no cover - interface
        raise NotImplementedError

    def loss(self, params, batch: Batch, rng=None):
        raise NotImplementedError

    def prefill(self, params, batch: Batch):
        raise NotImplementedError

    def decode_step(self, params, state, tokens: jax.Array):
        raise NotImplementedError

    def init_decode_state(self, batch: int, max_len: int):
        raise NotImplementedError

    def decode_state_axes(self):
        """Logical-axis tree matching init_decode_state's structure (used by
        the launcher to build decode-state shardings; fit-or-drop handles
        non-divisible dims like batch=1 or kv_heads < TP degree)."""
        raise NotImplementedError

    # -- conveniences ------------------------------------------------------
    def cache_dtype(self):
        return self.cfg.act_dtype()


_KV_AXES = A.KVCache(
    k=(None, "batch", "kv_seq", "kv_heads", None),
    v=(None, "batch", "kv_seq", "kv_heads", None),
    length=(None,),
)
_MLA_KV_AXES = A.KVCache(
    k=(None, "batch", "kv_seq", None),
    v=(None, "batch", "kv_seq", None),
    length=(None,),
)


# ===========================================================================
# Decoder-only LM (dense / moe / vlm)
# ===========================================================================


class DecoderLM(Model):
    def __init__(self, cfg: ModelConfig):
        super().__init__(cfg)
        moe = cfg.moe
        self.n_front = moe.first_dense if moe else 0
        self.n_scan = cfg.n_layers - self.n_front
        self.kind = "moe" if moe else "dense"

    # ------------------------------------------------------------- params
    def init(self, key):
        cfg = self.cfg
        kg = KeyGen(key)
        p: Dict[str, Any] = {
            "embed": init_embedding(kg(), cfg.vocab, cfg.d_model),
            "ln_f": init_norm(cfg.d_model, cfg.norm_type),
            "lm_head": normal_init(kg(), (cfg.vocab, cfg.d_model), ("vocab", "embed"), scale=0.02),
        }
        for i in range(self.n_front):
            p[f"front_{i}"] = _init_decoder_layer(kg(), cfg, kind="dense_wide")
        p["layers"] = stack_init(
            lambda k: _init_decoder_layer(k, cfg, kind=self.kind), kg(), self.n_scan
        )
        if cfg.vlm:
            p["img_proj"] = normal_init(kg(), (cfg.d_model, cfg.d_model), ("embed", "embed"))
        return p

    # ------------------------------------------------------------ helpers
    def _embed_inputs(self, params, batch: Batch) -> jax.Array:
        cfg = self.cfg
        x = embed_lookup(params["embed"], batch["tokens"], cfg.act_dtype())
        if cfg.vlm:
            img = batch["img_embeds"].astype(cfg.act_dtype()) @ params["img_proj"].astype(cfg.act_dtype())
            x = jnp.concatenate([img, x], axis=1)
        return shard(x, ("batch", "seq", "act_embed"))

    def _stack(self, params, x, positions, caches, mode):
        cfg = self.cfg
        aux_total = jnp.zeros((), jnp.float32)
        front_caches = []
        for i in range(self.n_front):
            c = None if caches is None else jax.tree.map(lambda a: a[i], caches)
            x, nc, aux = _apply_decoder_layer(
                params[f"front_{i}"], cfg, x, positions=positions, cache=c,
                mode=mode, kind="dense_wide",
            )
            aux_total = aux_total + aux
            front_caches.append(nc)

        layer_params = params["layers"]

        if mode == "train":
            def body(carry, lp):
                h, aux = carry
                h, _, a = _apply_decoder_layer(
                    lp, cfg, h, positions=positions, cache=None, mode="train",
                    kind=self.kind,
                )
                return (h, aux + a), None

            (x, aux_total), _ = _maybe_scan(
                cfg, _remat(body, cfg), (x, aux_total), layer_params
            )
            new_caches = None
        else:
            scan_caches = (
                None if caches is None
                else jax.tree.map(lambda a: a[self.n_front :], caches)
            )

            def body(h, xs):
                lp, c = xs
                h, nc, _ = _apply_decoder_layer(
                    lp, cfg, h, positions=positions, cache=c, mode=mode,
                    kind=self.kind,
                )
                return h, nc

            x, new_scan = _maybe_scan(cfg, body, x, (layer_params, scan_caches))
            new_caches = new_scan
            if self.n_front:
                new_caches = jax.tree.map(
                    lambda f, s: jnp.concatenate([f, s], axis=0),
                    _stack_front(front_caches),
                    new_scan,
                )
        return x, new_caches, aux_total

    # -------------------------------------------------------------- train
    def loss(self, params, batch: Batch, rng=None):
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        B, S = x.shape[:2]
        positions = _positions(B, S)
        x, _, aux = self._stack(params, x, positions, None, "train")
        x = apply_norm(params["ln_f"], x, eps=cfg.norm_eps, norm_type=cfg.norm_type)
        n_img = cfg.vlm.n_img_tokens if cfg.vlm else 0
        text = x[:, n_img:, :]
        logits = logits_projection(params["lm_head"], text[:, :-1])
        loss = _xent(logits, batch["tokens"][:, 1:])
        if cfg.moe:
            loss = loss + 0.01 * aux / max(self.n_scan, 1)
        return loss, {"xent": loss, "aux": aux}

    # ------------------------------------------------------------ serving
    def init_decode_state(self, batch: int, max_len: int):
        cfg = self.cfg
        L = cfg.n_layers
        dt = self.cache_dtype()
        if cfg.mla:
            one = A.init_mla_cache(batch, max_len, cfg.mla, dt)
        else:
            one = A.init_cache(
                batch, max_len, cfg.n_kv_heads, cfg.resolved_head_dim,
                cfg.resolved_head_dim, dt,
            )
        return jax.tree.map(lambda a: jnp.broadcast_to(a[None], (L,) + a.shape).copy(), one)

    def decode_state_axes(self):
        return _MLA_KV_AXES if self.cfg.mla else _KV_AXES

    def prefill(self, params, batch: Batch, max_len: Optional[int] = None):
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        B, S = x.shape[:2]
        positions = _positions(B, S)
        # cache headroom: decode appends AFTER the prompt — without it the
        # first decoded token has no slot (and a clamped dynamic-update-slice
        # silently corrupts the last prompt position)
        caches = self.init_decode_state(B, max_len=max_len or S + 64)
        x, new_caches, _ = self._stack(params, x, positions, caches, "prefill")
        x = apply_norm(params["ln_f"], x, eps=cfg.norm_eps, norm_type=cfg.norm_type)
        logits = logits_projection(params["lm_head"], x[:, -1:])
        return logits, new_caches

    def decode_step(self, params, state, tokens: jax.Array):
        cfg = self.cfg
        x = embed_lookup(params["embed"], tokens, cfg.act_dtype())
        B = x.shape[0]
        length = state.length[0] if hasattr(state, "length") else state["length"][0]
        positions = jnp.broadcast_to(length[None, None], (B, 1)).astype(jnp.int32)
        x, new_caches, _ = self._stack(params, x, positions, state, "decode")
        x = apply_norm(params["ln_f"], x, eps=cfg.norm_eps, norm_type=cfg.norm_type)
        logits = logits_projection(params["lm_head"], x)
        return logits, new_caches


def _stack_front(front_caches):
    """Stack a list of per-layer cache trees along a new leading axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *front_caches)


# ===========================================================================
# Encoder–decoder (whisper)
# ===========================================================================


def _init_encoder_layer(key, cfg: ModelConfig):
    kg = KeyGen(key)
    return {
        "ln1": init_norm(cfg.d_model, cfg.norm_type),
        "attn": A.init_gqa(kg(), cfg),
        "ln2": init_norm(cfg.d_model, cfg.norm_type),
        "ffn": init_mlp(kg(), cfg.d_model, cfg.d_ff, cfg.mlp_type),
    }


def _apply_encoder_layer(p, cfg: ModelConfig, x):
    h = apply_norm(p["ln1"], x, eps=cfg.norm_eps, norm_type=cfg.norm_type)
    B, S = h.shape[:2]
    a, _ = A.apply_gqa(
        p["attn"], cfg, h, positions=_positions(B, S), mode="bidir",
        rope_style="none",
    )
    x = x + a
    h = apply_norm(p["ln2"], x, eps=cfg.norm_eps, norm_type=cfg.norm_type)
    return x + apply_mlp(p["ffn"], h, mlp_type=cfg.mlp_type)


class EncDecLM(Model):
    """Whisper-style: stubbed mel-frame embeddings → encoder → decoder LM.

    The encoder (frames and positions through its layers and ``ln_enc``)
    runs under ``jax.named_scope(ENCODER_SCOPE)``, and each decoder layer's
    cross-attention (its norm, the K/V projection of the frames, the
    attention and its residual add) and the serving path's precomputed
    cross K/V under ``CROSS_ATTENTION_SCOPE``.  A scope sets HLO
    ``op_name`` metadata and changes no instruction.
    """

    def init(self, key):
        cfg = self.cfg
        kg = KeyGen(key)
        ed = cfg.enc_dec
        return {
            "embed": init_embedding(kg(), cfg.vocab, cfg.d_model),
            "lm_head": normal_init(kg(), (cfg.vocab, cfg.d_model), ("vocab", "embed"), scale=0.02),
            "ln_f": init_norm(cfg.d_model, cfg.norm_type),
            "ln_enc": init_norm(cfg.d_model, cfg.norm_type),
            "enc_layers": stack_init(lambda k: _init_encoder_layer(k, cfg), kg(), ed.n_enc_layers),
            "dec_layers": stack_init(
                lambda k: _init_decoder_layer(k, cfg, kind="dense", cross=True),
                kg(),
                cfg.n_layers,
            ),
        }

    def _encode(self, params, frames: jax.Array) -> jax.Array:
        cfg = self.cfg

        def body(h, lp):
            return _apply_encoder_layer(lp, cfg, h), None

        with jax.named_scope(ENCODER_SCOPE):
            x = frames.astype(cfg.act_dtype())
            x = x + sinusoidal_positions(x.shape[1], cfg.d_model).astype(x.dtype)[None]
            x = shard(x, ("batch", "seq", "act_embed"))
            x, _ = _maybe_scan(cfg, _remat(body, cfg), x, params["enc_layers"])
            return apply_norm(params["ln_enc"], x, eps=cfg.norm_eps, norm_type=cfg.norm_type)

    def _decode_stack(self, params, x, positions, enc, caches, mode, cross_kv=None):
        cfg = self.cfg

        def body(h, xs):
            if caches is None:
                lp = xs
                h, _, _ = _apply_decoder_layer(
                    lp, cfg, h, positions=positions, cache=None, mode="train",
                    kind="dense", enc=enc,
                )
                return h, None
            lp, c, ckv = xs
            h, nc, _ = _apply_decoder_layer(
                lp, cfg, h, positions=positions, cache=c, mode=mode,
                kind="dense", enc=enc, cross_kv=ckv,
            )
            return h, nc

        if caches is None:
            x, _ = _maybe_scan(
                cfg, _remat(body, cfg) if mode == "train" else body, x,
                params["dec_layers"],
            )
            return x, None
        x, new_caches = _maybe_scan(cfg, body, x, (params["dec_layers"], caches, cross_kv))
        return x, new_caches

    def loss(self, params, batch: Batch, rng=None):
        cfg = self.cfg
        enc = self._encode(params, batch["enc_frames"])
        x = embed_lookup(params["embed"], batch["tokens"], cfg.act_dtype())
        B, S = x.shape[:2]
        x = x + sinusoidal_positions(S, cfg.d_model).astype(x.dtype)[None]
        x, _ = self._decode_stack(params, x, _positions(B, S), enc, None, "train")
        x = apply_norm(params["ln_f"], x, eps=cfg.norm_eps, norm_type=cfg.norm_type)
        logits = logits_projection(params["lm_head"], x[:, :-1])
        loss = _xent(logits, batch["tokens"][:, 1:])
        return loss, {"xent": loss}

    def _cross_kv(self, params, enc: jax.Array):
        """Precompute per-layer cross-attention K/V from encoder output."""
        dt = enc.dtype

        def one(lp):
            k = jnp.einsum("btd,dhk->bthk", enc, lp["xattn"]["wk"].astype(dt))
            v = jnp.einsum("btd,dhk->bthk", enc, lp["xattn"]["wv"].astype(dt))
            return {"k": k, "v": v}

        with jax.named_scope(CROSS_ATTENTION_SCOPE):
            return jax.vmap(one)(params["dec_layers"])

    def init_decode_state(self, batch: int, max_len: int):
        cfg = self.cfg
        dt = self.cache_dtype()
        L = cfg.n_layers
        one = A.init_cache(batch, max_len, cfg.n_kv_heads, cfg.resolved_head_dim,
                           cfg.resolved_head_dim, dt)
        self_c = jax.tree.map(lambda a: jnp.broadcast_to(a[None], (L,) + a.shape).copy(), one)
        H, Dh = cfg.n_kv_heads, cfg.resolved_head_dim
        cross = {
            "k": jnp.zeros((L, batch, cfg.enc_dec.enc_seq, H, Dh), dt),
            "v": jnp.zeros((L, batch, cfg.enc_dec.enc_seq, H, Dh), dt),
        }
        return {"self": self_c, "cross": cross}

    def decode_state_axes(self):
        return {
            "self": _KV_AXES,
            "cross": {
                "k": (None, "batch", None, "kv_heads", None),
                "v": (None, "batch", None, "kv_heads", None),
            },
        }

    def prefill(self, params, batch: Batch, max_len: Optional[int] = None):
        cfg = self.cfg
        enc = self._encode(params, batch["enc_frames"])
        x = embed_lookup(params["embed"], batch["tokens"], cfg.act_dtype())
        B, S = x.shape[:2]
        x = x + sinusoidal_positions(max_len or S + 64, cfg.d_model).astype(x.dtype)[None, :S]
        caches = self.init_decode_state(B, max_len or S + 64)["self"]
        cross = self._cross_kv(params, enc)
        x, new_caches = self._decode_stack(
            params, x, _positions(B, S), None, caches, "prefill", cross_kv=cross
        )
        x = apply_norm(params["ln_f"], x, eps=cfg.norm_eps, norm_type=cfg.norm_type)
        logits = logits_projection(params["lm_head"], x[:, -1:])
        return logits, {"self": new_caches, "cross": cross}

    def decode_step(self, params, state, tokens: jax.Array):
        cfg = self.cfg
        x = embed_lookup(params["embed"], tokens, cfg.act_dtype())
        B = x.shape[0]
        length = state["self"].length[0]
        pos_tab = sinusoidal_positions(state["self"].k.shape[2], cfg.d_model)
        x = x + jax.lax.dynamic_slice_in_dim(pos_tab, length, 1, axis=0).astype(x.dtype)[None]
        positions = jnp.broadcast_to(length[None, None], (B, 1)).astype(jnp.int32)
        x, new_caches = self._decode_stack(
            params, x, positions, None, state["self"], "decode", cross_kv=state["cross"]
        )
        x = apply_norm(params["ln_f"], x, eps=cfg.norm_eps, norm_type=cfg.norm_type)
        logits = logits_projection(params["lm_head"], x)
        return logits, {"self": new_caches, "cross": state["cross"]}


# ===========================================================================
# xLSTM (groups of mLSTM with an sLSTM every `slstm_every`)
# ===========================================================================


class XLSTMLM(Model):
    def __init__(self, cfg: ModelConfig):
        super().__init__(cfg)
        xc = cfg.xlstm
        assert cfg.n_layers % xc.slstm_every == 0
        self.n_groups = cfg.n_layers // xc.slstm_every
        self.m_per_group = xc.slstm_every - 1

    def init(self, key):
        cfg = self.cfg
        kg = KeyGen(key)

        def group_init(k):
            kg2 = KeyGen(k)
            return {
                "mlstm": stack_init(lambda kk: _with_norm(SSM.init_mlstm, kk, cfg), kg2(), self.m_per_group),
                "slstm": _with_norm(SSM.init_slstm, kg2(), cfg),
            }

        return {
            "embed": init_embedding(kg(), cfg.vocab, cfg.d_model),
            "lm_head": normal_init(kg(), (cfg.vocab, cfg.d_model), ("vocab", "embed"), scale=0.02),
            "ln_f": init_norm(cfg.d_model, cfg.norm_type),
            "groups": stack_init(group_init, kg(), self.n_groups),
        }

    def _apply_block(self, gp, x, states, mode):
        cfg = self.cfg

        def m_body(h, xs):
            lp, st = xs
            hh = apply_norm(lp["ln"], h, eps=cfg.norm_eps, norm_type=cfg.norm_type)
            out, new_st = SSM.apply_mlstm(lp["p"], cfg, hh, state=st, mode=mode)
            if new_st is None:
                new_st = st
            return h + out, new_st

        x, new_m = _maybe_scan(self.cfg, m_body, x, (gp["mlstm"], states["mlstm"]))
        sp = gp["slstm"]
        hh = apply_norm(sp["ln"], x, eps=cfg.norm_eps, norm_type=cfg.norm_type)
        out, new_s = SSM.apply_slstm(sp["p"], cfg, hh, state=states["slstm"], mode=mode)
        if new_s is None:
            new_s = states["slstm"]
        return x + out, {"mlstm": new_m, "slstm": new_s}

    def _stack(self, params, x, states, mode):
        def body(h, xs):
            gp, st = xs
            h, new_st = self._apply_block(gp, h, st, mode)
            return h, new_st

        wrapped = _remat(body, self.cfg) if mode == "train" else body
        x, new_states = _maybe_scan(self.cfg, wrapped, x, (params["groups"], states))
        return x, new_states

    def init_decode_state(self, batch: int, max_len: int = 0):
        cfg = self.cfg
        dt = jnp.float32  # recurrent states in fp32 for stability
        m_one = SSM.init_mlstm_state(cfg, batch, dt)
        s_one = SSM.init_slstm_state(cfg, batch, dt)
        G, Mg = self.n_groups, self.m_per_group
        return {
            "mlstm": jax.tree.map(lambda a: jnp.broadcast_to(a[None, None], (G, Mg) + a.shape).copy(), m_one),
            "slstm": jax.tree.map(lambda a: jnp.broadcast_to(a[None], (G,) + a.shape).copy(), s_one),
        }

    def decode_state_axes(self):
        return {
            "mlstm": SSM.MLSTMState(
                C=(None, None, "batch", "ssm_heads", "ssm_inner", None),
                n=(None, None, "batch", "ssm_heads", None, None),
            ),
            "slstm": SSM.SLSTMState(
                h=(None, "batch", "ssm_heads", None),
                c=(None, "batch", "ssm_heads", None),
                n=(None, "batch", "ssm_heads", None),
                m=(None, "batch", "ssm_heads", None),
            ),
        }

    def loss(self, params, batch: Batch, rng=None):
        cfg = self.cfg
        x = embed_lookup(params["embed"], batch["tokens"], cfg.act_dtype())
        B = x.shape[0]
        states = self.init_decode_state(B)
        x, _ = self._stack(params, x, states, "train")
        x = apply_norm(params["ln_f"], x, eps=cfg.norm_eps, norm_type=cfg.norm_type)
        logits = logits_projection(params["lm_head"], x[:, :-1])
        loss = _xent(logits, batch["tokens"][:, 1:])
        return loss, {"xent": loss}

    def prefill(self, params, batch: Batch):
        cfg = self.cfg
        x = embed_lookup(params["embed"], batch["tokens"], cfg.act_dtype())
        B = x.shape[0]
        states = self.init_decode_state(B)
        x, new_states = self._stack(params, x, states, "prefill")
        x = apply_norm(params["ln_f"], x, eps=cfg.norm_eps, norm_type=cfg.norm_type)
        logits = logits_projection(params["lm_head"], x[:, -1:])
        return logits, new_states

    def decode_step(self, params, state, tokens: jax.Array):
        cfg = self.cfg
        x = embed_lookup(params["embed"], tokens, cfg.act_dtype())
        x, new_states = self._stack(params, x, state, "decode")
        x = apply_norm(params["ln_f"], x, eps=cfg.norm_eps, norm_type=cfg.norm_type)
        logits = logits_projection(params["lm_head"], x)
        return logits, new_states


def _with_norm(init_fn, key, cfg):
    kg = KeyGen(key)
    return {"ln": init_norm(cfg.d_model, cfg.norm_type), "p": init_fn(kg(), cfg)}


# ===========================================================================
# Zamba2 hybrid: Mamba2 stack + one shared attention block with LoRA
# ===========================================================================


class HybridLM(Model):
    def __init__(self, cfg: ModelConfig):
        super().__init__(cfg)
        hc = cfg.hybrid
        assert cfg.n_layers % hc.shared_attn_every == 0
        self.n_groups = cfg.n_layers // hc.shared_attn_every
        self.per_group = hc.shared_attn_every

    def init(self, key):
        cfg = self.cfg
        kg = KeyGen(key)
        r = cfg.hybrid.lora_rank
        d, H, K, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim

        def lora_init(k):
            kg2 = KeyGen(k)
            return {
                name: {
                    "a": normal_init(kg2(), (d, r), ("embed", None), scale=0.02),
                    "b": normal_init(kg2(), (r, heads, Dh), (None, ax, None), scale=0.02),
                }
                for name, heads, ax in [("q", H, "heads"), ("k", K, "kv_heads"), ("v", K, "kv_heads")]
            }

        shared = {
            "ln1": init_norm(d, cfg.norm_type),
            "attn": A.init_gqa(kg(), cfg),
            "ln2": init_norm(d, cfg.norm_type),
            "ffn": init_mlp(kg(), d, cfg.d_ff, cfg.mlp_type),
        }
        return {
            "embed": init_embedding(kg(), cfg.vocab, d),
            "lm_head": normal_init(kg(), (cfg.vocab, d), ("vocab", "embed"), scale=0.02),
            "ln_f": init_norm(d, cfg.norm_type),
            "shared": shared,
            "mamba": stack_init(
                lambda k: _with_norm(SSM.init_mamba2, k, cfg), kg(),
                self.n_groups * self.per_group,
            ),
            "lora": stack_init(lora_init, kg(), self.n_groups),
        }

    def _shared_attn(self, params, lora, cfg, x, positions, cache, mode):
        """Shared transformer block with per-invocation LoRA on q/k/v."""
        sp = params["shared"]
        dt = x.dtype
        h = apply_norm(sp["ln1"], x, eps=cfg.norm_eps, norm_type=cfg.norm_type)
        p_attn = dict(sp["attn"])
        # effective weights: w + a @ b  (rank-r update per invocation)
        for name, wname in [("q", "wq"), ("k", "wk"), ("v", "wv")]:
            delta = jnp.einsum("dr,rhk->dhk", lora[name]["a"], lora[name]["b"])
            p_attn[wname] = sp["attn"][wname] + delta
        a_out, new_cache = A.apply_gqa(p_attn, cfg, h, positions=positions, cache=cache, mode=mode)
        x = x + a_out
        h = apply_norm(sp["ln2"], x, eps=cfg.norm_eps, norm_type=cfg.norm_type)
        return x + apply_mlp(sp["ffn"], h, mlp_type=cfg.mlp_type), new_cache

    def _stack(self, params, x, positions, states, mode):
        cfg = self.cfg
        G, Pg = self.n_groups, self.per_group
        mamba_params = jax.tree.map(
            lambda a: a.reshape((G, Pg) + a.shape[1:]), params["mamba"]
        )

        def group_body(carry, xs):
            h = carry
            gp, lora, st = xs

            def m_body(hh, inner):
                lp, mst = inner
                z = apply_norm(lp["ln"], hh, eps=cfg.norm_eps, norm_type=cfg.norm_type)
                out, new_st = SSM.apply_mamba2(lp["p"], cfg, z, state=mst, mode=mode)
                if new_st is None:
                    new_st = mst
                return hh + out, new_st

            h, new_m = _maybe_scan(cfg, m_body, h, (gp, st["mamba"]))
            h, new_kv = self._shared_attn(params, lora, cfg, h, positions, st["attn"], mode)
            if new_kv is None:
                new_kv = st["attn"]
            return h, {"mamba": new_m, "attn": new_kv}

        wrapped = _remat(group_body, cfg) if mode == "train" else group_body
        x, new_states = _maybe_scan(cfg, wrapped, x, (mamba_params, params["lora"], states))
        return x, new_states

    def init_decode_state(self, batch: int, max_len: int):
        cfg = self.cfg
        G, Pg = self.n_groups, self.per_group
        m_one = SSM.init_mamba2_state(cfg, batch, jnp.float32)
        kv_one = A.init_cache(batch, max_len, cfg.n_kv_heads, cfg.resolved_head_dim,
                              cfg.resolved_head_dim, self.cache_dtype())
        return {
            "mamba": jax.tree.map(lambda a: jnp.broadcast_to(a[None, None], (G, Pg) + a.shape).copy(), m_one),
            "attn": jax.tree.map(lambda a: jnp.broadcast_to(a[None], (G,) + a.shape).copy(), kv_one),
        }

    def decode_state_axes(self):
        return {
            "mamba": SSM.Mamba2State(
                conv=(None, None, "batch", None, "ssm_inner"),
                ssm=(None, None, "batch", "ssm_heads", None, None),
            ),
            "attn": _KV_AXES,
        }

    def loss(self, params, batch: Batch, rng=None):
        cfg = self.cfg
        x = embed_lookup(params["embed"], batch["tokens"], cfg.act_dtype())
        B, S = x.shape[:2]
        states = self.init_decode_state(B, max_len=S)
        # train mode ignores the attn caches; mamba states start at zero
        x, _ = self._stack(params, x, _positions(B, S), states, "train")
        x = apply_norm(params["ln_f"], x, eps=cfg.norm_eps, norm_type=cfg.norm_type)
        logits = logits_projection(params["lm_head"], x[:, :-1])
        loss = _xent(logits, batch["tokens"][:, 1:])
        return loss, {"xent": loss}

    def prefill(self, params, batch: Batch, max_len: Optional[int] = None):
        cfg = self.cfg
        x = embed_lookup(params["embed"], batch["tokens"], cfg.act_dtype())
        B, S = x.shape[:2]
        states = self.init_decode_state(B, max_len=max_len or S + 64)
        x, new_states = self._stack(params, x, _positions(B, S), states, "prefill")
        x = apply_norm(params["ln_f"], x, eps=cfg.norm_eps, norm_type=cfg.norm_type)
        logits = logits_projection(params["lm_head"], x[:, -1:])
        return logits, new_states

    def decode_step(self, params, state, tokens: jax.Array):
        cfg = self.cfg
        x = embed_lookup(params["embed"], tokens, cfg.act_dtype())
        B = x.shape[0]
        length = state["attn"].length[0]
        positions = jnp.broadcast_to(length[None, None], (B, 1)).astype(jnp.int32)
        x, new_states = self._stack(params, x, positions, state, "decode")
        x = apply_norm(params["ln_f"], x, eps=cfg.norm_eps, norm_type=cfg.norm_type)
        logits = logits_projection(params["lm_head"], x)
        return logits, new_states


# ===========================================================================


def build_model(cfg: ModelConfig) -> Model:
    if cfg.family == "audio":
        return EncDecLM(cfg)
    if cfg.family == "ssm" and cfg.xlstm:
        return XLSTMLM(cfg)
    if cfg.family == "hybrid":
        return HybridLM(cfg)
    return DecoderLM(cfg)
