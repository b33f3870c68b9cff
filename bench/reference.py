"""Plain reference for the benchmark's training cells.

A straightforward float32 implementation of the two model families the
cells train (a decoder-only transformer and an encoder-decoder), their
next-token cross-entropy, and AdamW with global-norm clipping.  It imports
nothing of the program under test and takes nothing that the program has
made: the weights come from :func:`init_params`, which the benchmark also
uses to give the program its starting weights.

Every matrix product runs at ``Precision.HIGHEST`` (a float32 product on
the TPU runs at bfloat16 unless told otherwise).  ``precision="fp8"``
computes the same model with every matrix product's operands rounded to
float8 e4m3 with a per-tensor power-of-two scale: that is the control,
the precision one step below the bfloat16 the configurations state.

The parameter tree follows the layout the program keeps (nested dicts,
layers stacked on a leading axis), so that the two trees can be compared
leaf by leaf; :func:`param_spec` builds it from the configuration file's
widths alone.
"""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8 e4m3fn


# ---------------------------------------------------------------- precision


def _fp8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 under a per-tensor power-of-two scale.

    The rounding passes gradients straight through, so the backward pass
    multiplies float32 cotangents with the rounded operands, as float8
    training with scaled gradients does; it does not flush them to zero.
    """
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    scale = jnp.exp2(jnp.ceil(jnp.log2(jnp.maximum(amax, 1e-30) / F8_MAX)))
    rounded = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(rounded - x)


def mm(spec: str, a: jax.Array, b: jax.Array, precision: str) -> jax.Array:
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    elif precision != "fp32":
        raise ValueError(f"unknown reference precision {precision!r}")
    return jnp.einsum(spec, a, b, precision=HIGHEST)


# ------------------------------------------------------------------- layers


def layer_norm(p, x, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def sinusoids(n: int, d: int) -> jax.Array:
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    inv = jnp.exp(-math.log(10000.0) * 2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)
    ang = pos * inv[None]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def rope(x, positions):
    """Rotary embedding over the whole head, halves paired (x: B,S,H,D)."""
    d = x.shape[-1]
    inv = 1.0 / (10000.0 ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[:, None].astype(jnp.float32) * inv[None]          # (S, D/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(p, xq, xkv, *, causal, use_rope, precision):
    q = mm("bsd,dhk->bshk", xq, p["wq"], precision)
    k = mm("btd,dhk->bthk", xkv, p["wk"], precision)
    v = mm("btd,dhk->bthk", xkv, p["wv"], precision)
    if use_rope:
        q = rope(q, jnp.arange(q.shape[1]))
        k = rope(k, jnp.arange(k.shape[1]))
    s = mm("bshk,bthk->bhst", q, k, precision) / math.sqrt(q.shape[-1])
    if causal:
        keep = jnp.arange(k.shape[1])[None, :] <= jnp.arange(q.shape[1])[:, None]
        s = jnp.where(keep[None, None], s, -1e30)
    prob = jax.nn.softmax(s, axis=-1)
    ctx = mm("bhst,bthk->bshk", prob, v, precision)
    return mm("bshk,hkd->bsd", ctx, p["wo"], precision)


def mlp(p, x, precision):
    return mm("bsf,fd->bsd", gelu_tanh(mm("bsd,df->bsf", x, p["wi"], precision)),
              p["wo"], precision)


def xent(p_head, x, tokens, precision):
    logits = mm("bsd,vd->bsv", x[:, :-1], p_head, precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


# ------------------------------------------------------------------- models


def decoder_loss(params, batch, cfg, precision):
    """Causal LM: pre-norm layers, rotary attention, GELU MLP, untied head."""
    eps = cfg["norm_eps"]
    x = jnp.take(params["embed"], batch["tokens"], axis=0)

    @jax.checkpoint
    def layer(h, lp):
        a = layer_norm(lp["ln1"], h, eps)
        h = h + attention(lp["attn"], a, a, causal=True, use_rope=True, precision=precision)
        return h + mlp(lp["ffn"], layer_norm(lp["ln2"], h, eps), precision), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = layer_norm(params["ln_f"], x, eps)
    return xent(params["lm_head"], x, batch["tokens"], precision)


def encdec_loss(params, batch, cfg, precision):
    """Whisper-style: frame embeddings through a bidirectional encoder, then
    a causal decoder with cross-attention; sinusoidal positions on both."""
    eps, d = cfg["norm_eps"], cfg["d_model"]
    e = batch["enc_frames"].astype(jnp.float32)
    e = e + sinusoids(e.shape[1], d)[None]

    @jax.checkpoint
    def enc_layer(h, lp):
        a = layer_norm(lp["ln1"], h, eps)
        h = h + attention(lp["attn"], a, a, causal=False, use_rope=False, precision=precision)
        return h + mlp(lp["ffn"], layer_norm(lp["ln2"], h, eps), precision), None

    e, _ = jax.lax.scan(enc_layer, e, params["enc_layers"])
    e = layer_norm(params["ln_enc"], e, eps)

    x = jnp.take(params["embed"], batch["tokens"], axis=0)
    x = x + sinusoids(x.shape[1], d)[None]

    @jax.checkpoint
    def dec_layer(h, lp):
        a = layer_norm(lp["ln1"], h, eps)
        h = h + attention(lp["attn"], a, a, causal=True, use_rope=False, precision=precision)
        a = layer_norm(lp["ln_x"], h, eps)
        h = h + attention(lp["xattn"], a, e, causal=False, use_rope=False, precision=precision)
        return h + mlp(lp["ffn"], layer_norm(lp["ln2"], h, eps), precision), None

    x, _ = jax.lax.scan(dec_layer, x, params["dec_layers"])
    x = layer_norm(params["ln_f"], x, eps)
    return xent(params["lm_head"], x, batch["tokens"], precision)


LOSSES = {"decoder": decoder_loss, "encdec": encdec_loss}


# ---------------------------------------------------------- parameter trees


def _attn_spec(cfg, L):
    d, H = cfg["d_model"], cfg["n_heads"]
    dh = d // H
    return {
        "wq": ((L, d, H, dh), d), "wk": ((L, d, H, dh), d), "wv": ((L, d, H, dh), d),
        "wo": ((L, H, dh, d), H * dh),
    }


def _norm_spec(d, L=None):
    shape = (d,) if L is None else (L, d)
    return {"scale": (shape, "ones"), "bias": (shape, "zeros")}


def _ffn_spec(cfg, L):
    d, f = cfg["d_model"], cfg["d_ff"]
    return {"wi": ((L, d, f), d), "wo": ((L, f, d), f)}


def param_spec(cfg) -> Dict[str, Any]:
    """Tree of ``(shape, init)``: init is a fan-in (truncated normal over
    its square root), "embed" (std 0.02), "ones" or "zeros"."""
    d, V = cfg["d_model"], cfg["vocab"]
    spec: Dict[str, Any] = {
        "embed": ((V, d), "embed"), "lm_head": ((V, d), "embed"), "ln_f": _norm_spec(d),
    }
    if cfg["family"] == "decoder":
        L = cfg["n_layers"]
        spec["layers"] = {"ln1": _norm_spec(d, L), "attn": _attn_spec(cfg, L),
                          "ln2": _norm_spec(d, L), "ffn": _ffn_spec(cfg, L)}
    elif cfg["family"] == "encdec":
        Le, Ld = cfg["n_enc_layers"], cfg["n_layers"]
        spec["ln_enc"] = _norm_spec(d)
        spec["enc_layers"] = {"ln1": _norm_spec(d, Le), "attn": _attn_spec(cfg, Le),
                              "ln2": _norm_spec(d, Le), "ffn": _ffn_spec(cfg, Le)}
        spec["dec_layers"] = {"ln1": _norm_spec(d, Ld), "attn": _attn_spec(cfg, Ld),
                              "ln_x": _norm_spec(d, Ld), "xattn": _attn_spec(cfg, Ld),
                              "ln2": _norm_spec(d, Ld), "ffn": _ffn_spec(cfg, Ld)}
    else:
        raise ValueError(f"unknown reference family {cfg['family']!r}")
    return spec


def _is_spec_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def spec_shapes(spec):
    return jax.tree.map(lambda s: s[0], spec, is_leaf=_is_spec_leaf)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number up to 64 bits."""
    seed = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def init_params(spec, key):
    """Weights from ``key``: leaf ``i`` draws from ``fold_in(key, i)``."""
    leaves, treedef = jax.tree.flatten(spec, is_leaf=_is_spec_leaf)
    out = []
    for i, (shape, init) in enumerate(leaves):
        if init == "ones":
            out.append(jnp.ones(shape, jnp.float32))
        elif init == "zeros":
            out.append(jnp.zeros(shape, jnp.float32))
        else:
            std = 0.02 if init == "embed" else 1.0 / math.sqrt(init)
            k = jax.random.fold_in(key, i)
            out.append(std * jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32))
    return jax.tree.unflatten(treedef, out)


# ------------------------------------------------------------------- AdamW


class Opt(NamedTuple):
    lr: float
    betas: Tuple[float, float]
    eps: float
    weight_decay: float
    grad_clip: float
    warmup_steps: int
    total_steps: int


def opt_from(traffic_opt: Dict[str, Any]) -> Opt:
    return Opt(
        lr=traffic_opt["lr"], betas=tuple(traffic_opt["betas"]), eps=traffic_opt["eps"],
        weight_decay=traffic_opt["weight_decay"], grad_clip=traffic_opt["grad_clip"],
        warmup_steps=traffic_opt["warmup_steps"], total_steps=traffic_opt["total_steps"],
    )


def lr_at(opt: Opt, step: int) -> float:
    """Linear warm-up to ``lr``, then linear decay to 0 at ``total_steps``."""
    if step <= opt.warmup_steps:
        return opt.lr * step / max(opt.warmup_steps, 1)
    frac = (step - opt.warmup_steps) / max(opt.total_steps - opt.warmup_steps, 1)
    return opt.lr * max(0.0, 1.0 - frac)


def leaf_norms(tree) -> jax.Array:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


class Readings(NamedTuple):
    """What a training cell compares: the loss of each of the first steps,
    the norm of each leaf of the first gradient as the optimizer takes it
    (after clipping), and the norm of each leaf's change over the steps."""

    losses: np.ndarray
    grad_norms: np.ndarray
    change_norms: np.ndarray


def make_trainer(cfg, opt: Opt, *, precision: str, blocks: int,
                 param_sharding, batch_sharding):
    """Jitted pieces of a plain AdamW trainer.

    The batch's rows are taken in ``blocks`` sequential blocks (gradients
    summed in float32) so that the activations of a large batch fit beside
    the optimizer state.  Returns ``(grad_fn, update_fn)``:
    ``grad_fn(params, batch) -> (loss, clipped grads)`` and
    ``update_fn(params, m, v, grads, step, lr) -> (params, m, v)``.
    """
    loss_of = LOSSES[cfg["family"]]
    b1, b2 = opt.betas

    def grads(params, batch):
        if blocks == 1:
            loss, g = jax.value_and_grad(loss_of)(params, batch, cfg, precision)
        else:
            def rows(x):
                # block j takes rows j, j + blocks, ...: rows from every shard
                y = x.reshape((x.shape[0] // blocks, blocks) + x.shape[1:])
                return jnp.swapaxes(y, 0, 1)

            def body(acc, one):
                loss_acc, g_acc = acc
                loss, g = jax.value_and_grad(loss_of)(params, one, cfg, precision)
                return (loss_acc + loss, jax.tree.map(jnp.add, g_acc, g)), None

            zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, params))
            (loss, g), _ = jax.lax.scan(body, zero, jax.tree.map(rows, batch))
            loss, g = loss / blocks, jax.tree.map(lambda x: x / blocks, g)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
        clip = jnp.minimum(1.0, opt.grad_clip / (gnorm + 1e-6))
        return loss, jax.tree.map(lambda x: x * clip, g)

    def update(params, m, v, g, step, lr):
        bc1 = 1.0 - b1 ** step
        bc2 = 1.0 - b2 ** step
        m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
        params = jax.tree.map(
            lambda p, m_, v_: p - lr * ((m_ / bc1) / (jnp.sqrt(v_ / bc2) + opt.eps)
                                        + opt.weight_decay * p),
            params, m, v)
        return params, m, v

    return (
        jax.jit(grads, in_shardings=(param_sharding, batch_sharding),
                out_shardings=(None, param_sharding)),
        jax.jit(update, donate_argnums=(0, 1, 2),
                in_shardings=(param_sharding,) * 4 + (None, None),
                out_shardings=(param_sharding,) * 3),
    )


def train_readings(cfg, opt: Opt, spec, key, batches, *, precision: str, blocks: int,
                   param_sharding, batch_sharding) -> Readings:
    """Run ``len(batches)`` AdamW steps from :func:`init_params` and read them."""
    init = jax.jit(lambda k: init_params(spec, k), out_shardings=param_sharding)
    grad_fn, update_fn = make_trainer(
        cfg, opt, precision=precision, blocks=blocks,
        param_sharding=param_sharding, batch_sharding=batch_sharding)
    norms = jax.jit(leaf_norms)
    change = jax.jit(lambda p, k: leaf_norms(jax.tree.map(jnp.subtract, p, init_params(spec, k))))
    params = init(key)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    for i, batch in enumerate(batches):
        step = i + 1
        loss, g = grad_fn(params, batch)
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = np.asarray(norms(g))
        params, m, v = update_fn(params, m, v, g, float(step), lr_at(opt, step))
    del m, v
    change_norms = np.asarray(change(params, key))
    return Readings(np.asarray(losses), grad_norms, change_norms)
