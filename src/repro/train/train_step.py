"""Train/serve step builders — the functions the launcher jits and the
dry-run lowers.

``make_train_step`` supports microbatch gradient accumulation (sequential
``lax.scan`` over microbatches — the standard memory/throughput trade) and
donates params+opt_state so the update is in-place at the XLA level.

Both training steps name their phases with ``jax.named_scope``, which sets
the HLO ``op_name`` of every operation and so names it in a profiler trace:
``train_forward`` (the loss; the backward pass is what AD derives from it,
``transpose(jvp(train_forward))``, and the remat recompute sits under that
transpose), ``train_optimizer`` (the AdamW update) and, in the
data-parallel step, ``train_grad_sync`` (the gradient all-reduce and the
loss mean).  A scope changes no instruction, only metadata.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.models.lm import Model

from .optimizer import OptimizerConfig, OptState, adamw_update


def make_train_step(
    model: Model,
    opt_cfg: OptimizerConfig,
    *,
    microbatches: int = 1,
) -> Callable:
    """Returns train_step(params, opt_state, batch) -> (params, opt_state, metrics)."""

    def grads_of(params, batch):
        def loss_fn(p):
            with jax.named_scope("train_forward"):
                loss, metrics = model.loss(p, batch)
            return loss, metrics

        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        return loss, metrics, grads

    def train_step(params, opt_state: OptState, batch):
        if microbatches == 1:
            loss, metrics, grads = grads_of(params, batch)
        else:
            def split(x):
                return x.reshape((microbatches, x.shape[0] // microbatches) + x.shape[1:])

            mb = jax.tree.map(split, batch)
            zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

            def body(acc, one):
                loss_acc, g_acc = acc
                loss, _, g = grads_of(params, one)
                g_acc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                return (loss_acc + loss, g_acc), None

            (loss, grads), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32), zero), mb)
            loss = loss / microbatches
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            metrics = {"xent": loss}

        with jax.named_scope("train_optimizer"):
            new_params, new_opt, opt_metrics = adamw_update(opt_cfg, grads, params, opt_state)
        out = {"loss": loss, **{k: v for k, v in metrics.items()}, **opt_metrics}
        return new_params, new_opt, out

    return train_step


def make_dp_train_step(model: Model, opt_cfg: OptimizerConfig, comm, mesh) -> Callable:
    """Pure data-parallel step whose gradient all-reduce runs through ``comm``.

    Params and optimizer state are replicated on every device of ``mesh``;
    the batch is split over the communicator's axis.  Each device takes the
    loss and gradients of its own shard, and ``comm.all_reduce`` averages
    the gradients: PCCL's planned ppermute rounds on the ``interp``
    backend, the native ``psum`` on ``xla``.  Returns the jitted
    ``step(params, opt_state, batch) -> (params, opt_state, loss)``, with
    params and optimizer state donated.
    """
    from jax.sharding import PartitionSpec as P

    axis, n = comm.axis_name, comm.n

    def per_shard_step(params, opt_state, batch):
        def loss_fn(p):
            with jax.named_scope("train_forward"):
                loss, _ = model.loss(p, batch)
            return loss

        loss, grads = jax.value_and_grad(loss_fn)(params)
        with jax.named_scope("train_grad_sync"):
            grads = jax.tree.map(lambda g: comm.all_reduce(g) / n, grads)
            loss = jax.lax.psum(loss, axis) / n
        with jax.named_scope("train_optimizer"):
            new_params, new_opt, _ = adamw_update(opt_cfg, grads, params, opt_state)
        return new_params, new_opt, loss

    return jax.jit(
        jax.shard_map(
            per_shard_step,
            mesh=mesh,
            in_specs=(P(), P(), P(axis)),
            out_specs=(P(), P(), P()),
            check_vma=False,
        ),
        donate_argnums=(0, 1),
    )


def make_eval_step(model: Model) -> Callable:
    def eval_step(params, batch):
        loss, metrics = model.loss(params, batch)
        return {"loss": loss, **metrics}

    return eval_step


def make_prefill_step(model: Model) -> Callable:
    def prefill_step(params, batch):
        return model.prefill(params, batch)

    return prefill_step


def make_serve_step(model: Model) -> Callable:
    """One decode step: (params, state, tokens (B,1)) -> (logits, state)."""

    def serve_step(params, state, tokens):
        return model.decode_step(params, state, tokens)

    return serve_step
