"""End-to-end driver: data-parallel training where the gradient all-reduce is
executed by PCCL's schedule-driven collectives (ppermute rounds) instead of
XLA's built-in psum — the paper's library, actually moving the gradients.

Runs a ~100 M-parameter dense transformer for a few hundred steps on 8 host
devices (sets the device count itself; run as a standalone script):

  PYTHONPATH=src python examples/pccl_dp_training.py --steps 300

A single ``PcclSession`` plans everything; ``session.communicator("data", n)``
returns the executable collectives (backend="interp" → ppermute rounds,
backend="xla" → the native baseline for A/B runs), and reports which
algorithm the planner chose for the gradient buffer size (paper §2.2).
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
)

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.api import PcclSession
from repro.configs import get_config
from repro.core import cost_model as cm
from repro.data.pipeline import DataConfig, SyntheticLMData
from repro.launch.cache import enable_compile_cache
from repro.models import build_model, unbox
from repro.models.module import param_count
from repro.train.optimizer import OptimizerConfig, init_opt_state
from repro.train.train_step import make_dp_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--d-model", type=int, default=512)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--backend", default="interp", choices=["interp", "xla"],
                    help="interp = PCCL ppermute schedules; xla = native psum baseline")
    args = ap.parse_args()
    enable_compile_cache()

    n_dev = len(jax.devices())
    mesh = Mesh(jax.devices(), ("data",))

    # ~100M params: d=512, 8L, vocab 32k → ≈ 60M; bump ff for ~100M
    cfg = dataclasses.replace(
        get_config("chatglm3-6b").reduced(),
        n_layers=args.layers, d_model=args.d_model, n_heads=8, n_kv_heads=2,
        head_dim=64, d_ff=4 * args.d_model, vocab=32000, dtype="float32",
    )
    model = build_model(cfg)
    params = unbox(model.init(jax.random.PRNGKey(0)))
    n_params = param_count(params)
    print(f"model: {n_params/1e6:.1f} M params on {n_dev} devices (pure DP)")

    grad_bytes = 4.0 * n_params
    session = PcclSession(cm.TPU_V5E_PHOTONIC)
    comm = session.communicator("data", n_dev, backend=args.backend)
    print(f"PCCL chose '{comm.chosen_algorithm('all_reduce', grad_bytes)}' "
          f"for the {grad_bytes/1e6:.0f} MB gradient all-reduce "
          f"(backend={args.backend})")

    opt_cfg = OptimizerConfig(lr=1e-3, total_steps=args.steps, warmup_steps=10)
    opt_state = init_opt_state(params)
    data = SyntheticLMData(cfg, DataConfig(global_batch=args.batch, seq_len=args.seq))

    # per-device loss on the local batch shard; grads averaged via the
    # schedule-driven PCCL all-reduce (ppermute rounds)
    step_fn = make_dp_train_step(model, opt_cfg, comm, mesh)

    t0 = time.perf_counter()
    for step in range(args.steps):
        batch = {k: jnp.asarray(v) for k, v in data.global_batch(step).items()}
        params, opt_state, loss = step_fn(params, opt_state, batch)
        if step % 20 == 0 or step == args.steps - 1:
            print(f"step {step:4d}  loss {float(loss):.4f}")
    dt = time.perf_counter() - t0
    toks = args.steps * args.batch * args.seq
    moved_by = ("PCCL schedule-driven ppermute rounds" if args.backend == "interp"
                else "native XLA psum (baseline)")
    print(f"trained {args.steps} steps in {dt:.1f}s ({toks/dt:.0f} tok/s) — "
          f"gradients moved by {moved_by}")


if __name__ == "__main__":
    main()
