"""Set-up, timed step and correctness check shared by the training modes.

Set-up builds one object: the compiled step with its state.  It makes the
weights from the seed with the reference's initialiser (one jitted call),
places a pool of batches on the device, and drives the step through its
first three batches, which also compiles it.  Those steps give the readings
that decide ``correct``: each step's loss, the norm of each leaf of the
first gradient as the optimizer took it (its first moment after one step,
over ``1 - beta1``), and the norm of each leaf's change over the three
steps.  The window then goes on with the same object.

After the window, :meth:`TrainingRun.check` frees the program's state and
runs the plain reference over the same three batches.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as ref
from bench.data import batch_pool

CHECK_STEPS = 3
ZERO_GRAD_SHARE = 1e-3  # leaves under this share of the median gradient norm are not compared


class Built(NamedTuple):
    """What a mode hands the run: ``step(params, opt_state, batch) ->
    (params, opt_state, loss)`` and where parameters and batches live."""

    step: Callable
    param_sharding: Any
    batch_sharding: Any
    info: Dict[str, Any]


def reference_config(cfg: Dict[str, Any]) -> Dict[str, Any]:
    c = dict(cfg["config"], family=cfg["family"])
    if "enc_dec" in c:
        c["n_enc_layers"] = c["enc_dec"]["n_enc_layers"]
    return c


def optimizer_config(traffic: Dict[str, Any]):
    from repro.train.optimizer import OptimizerConfig

    o = traffic["optimizer"]
    return OptimizerConfig(
        lr=o["lr"], betas=tuple(o["betas"]), eps=o["eps"], weight_decay=o["weight_decay"],
        grad_clip=o["grad_clip"], warmup_steps=o["warmup_steps"],
        total_steps=o["total_steps"], schedule="linear", min_lr_ratio=0.0,
    )


def _check_layout(model, spec) -> None:
    from repro.models.module import unbox

    prog = jax.eval_shape(lambda k: unbox(model.init(k)), jax.random.PRNGKey(0))
    prog_shapes = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), prog)
    want = jax.tree.map(lambda s: (tuple(s), "float32"), ref.spec_shapes(spec),
                        is_leaf=lambda x: isinstance(x, tuple))
    if prog_shapes != want:
        raise ValueError(f"program parameters differ from the reference layout:\n"
                         f"program {prog_shapes}\nreference {want}")


def compare(prog: ref.Readings, want: ref.Readings, limits: Dict[str, float]) -> Dict[str, Dict]:
    """The numbers compared, each with its limit.

    ``loss_gap``: the largest relative gap of a step's loss.
    ``grad_norm_gap`` and ``change_norm_gap``: the worst leaf's gap between
    the program's norm and the reference's, over the larger of the
    reference's norm of that leaf and of the median leaf.  Leaves whose
    reference gradient is under a thousandth of the median leaf's move by
    round-off alone and are left out of both.
    """
    keep = want.grad_norms >= ZERO_GRAD_SHARE * np.median(want.grad_norms)

    def worst_leaf(p, r):
        p, r = np.asarray(p)[keep], np.asarray(r)[keep]
        denom = np.maximum(r, np.median(r))
        return float(np.max(np.abs(p - r) / denom))

    values = {
        "loss_gap": float(np.max(np.abs(prog.losses - want.losses) / np.abs(want.losses))),
        "grad_norm_gap": worst_leaf(prog.grad_norms, want.grad_norms),
        "change_norm_gap": worst_leaf(prog.change_norms, want.change_norms),
    }
    return {k: {"value": v, "limit": limits[k]} for k, v in values.items()}


def passed(compared: Dict[str, Dict]) -> bool:
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"] for c in compared.values())


class TrainingRun:
    """One training cell's program object, from set-up to the check."""

    def __init__(self, cfg: Dict[str, Any], traffic: Dict[str, Any], seed: int, devices,
                 build_step: Callable, model_cfg=None):
        from repro.models import build_model
        from repro.train.optimizer import init_opt_state

        from bench import harness

        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.ref_cfg = reference_config(cfg)
        self.spec = ref.param_spec(self.ref_cfg)
        model_cfg = model_cfg if model_cfg is not None else harness.model_config(cfg)
        model = build_model(model_cfg)
        _check_layout(model, self.spec)
        self.opt_cfg = optimizer_config(traffic)
        self.built = build_step(model, self.opt_cfg, devices, traffic)
        ps, bs = self.built.param_sharding, self.built.batch_sharding
        self.key = ref.seed_key(seed)
        self.init = jax.jit(lambda k: ref.init_params(self.spec, k), out_shardings=ps)
        self.params = self.init(self.key)
        self.opt_state = jax.jit(init_opt_state, out_shardings=ps)(self.params)
        c = cfg["config"]
        self.rows = traffic["batch_per_chip"] * traffic["chips"]
        enc = c.get("enc_dec", {}).get("enc_seq", 0)
        self.pool = batch_pool(seed, traffic["pool_batches"], self.rows, traffic["seq_len"],
                               c["vocab"], enc_frames=enc, d_model=c["d_model"], sharding=bs)
        if len(self.pool) <= CHECK_STEPS:
            raise ValueError("the batch pool must hold more batches than the checked steps")
        self.readings = self._first_steps()
        self.next_index = CHECK_STEPS

    def _first_steps(self) -> ref.Readings:
        norms = jax.jit(ref.leaf_norms)
        change = jax.jit(lambda p, k: ref.leaf_norms(
            jax.tree.map(jnp.subtract, p, ref.init_params(self.spec, k))))
        losses, grad_norms = [], None
        for i in range(CHECK_STEPS):
            self.params, self.opt_state, loss = self.built.step(
                self.params, self.opt_state, self.pool[i])
            losses.append(float(loss))
            if i == 0:
                mu = np.asarray(norms(self.opt_state.mu))
                grad_norms = mu / (1.0 - self.opt_cfg.betas[0])
        return ref.Readings(np.asarray(losses), grad_norms, np.asarray(change(self.params, self.key)))

    # ---------------------------------------------------------------- window
    def next_batch(self):
        batch = self.pool[self.next_index % len(self.pool)]
        self.next_index += 1
        return batch

    def step(self, batch):
        self.params, self.opt_state, loss = self.built.step(self.params, self.opt_state, batch)
        return loss

    # ----------------------------------------------------------------- check
    def free(self) -> None:
        for x in jax.tree.leaves((self.params, self.opt_state)):
            x.delete()
        self.params = self.opt_state = None

    def reference_readings(self, precision: str = "fp32") -> ref.Readings:
        with jax.default_matmul_precision("highest"):
            return ref.train_readings(
                self.ref_cfg, ref.opt_from(self.traffic["optimizer"]), self.spec, self.key,
                self.pool[:CHECK_STEPS], precision=precision, blocks=self.traffic["reference_blocks"],
                param_sharding=self.built.param_sharding,
                batch_sharding=self.built.batch_sharding)

    def check(self, limits: Dict[str, float]) -> Dict[str, Dict]:
        """Free the program's state, run the reference, compare."""
        self.free()
        return compare(self.readings, self.reference_readings(), limits)
