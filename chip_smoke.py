#!/usr/bin/env python3
"""Drive PCCL's main path once on a TPU and check what comes out.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the cross-chip path on four chips

One chip: trains ``bert-base-paper`` (the paper's §6 workload) for a few
steps through :class:`repro.train.trainer.Trainer` at its published
widths, runs the Pallas kernels of the fused path compiled for the chip
against their references, and plans the gradient all-reduce on the host.

Four chips: runs every collective × algorithm the planner offers at n=4
through the ``interp`` backend's eager path and compares each with the
native ``xla`` collectives, runs the two fused comm/compute entry points
against their unfused compositions, and takes a few data-parallel
training steps with the gradient all-reduce on ``interp`` and on ``xla``.

Everything runs in this one process.  Without a TPU the script exits
non-zero and prints no result.  The last line of stdout is one JSON
object naming the device; it is printed only when every phase passed.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "bert-base-paper"
BATCH, SEQ = 8, 512
TRAIN_STEPS = 5
DP_STEPS = 3
COLLECTIVES = ("all_reduce", "reduce_scatter", "all_gather", "all_to_all")
SIZES_PER_RANK = (4 * 1024, 64 * 1024 * 1024)  # bytes of fp32 per rank
FUSED_MM = (2048, 2048, 8192)  # per-rank M, K, N of matmul + reduce-scatter
FUSED_RMS = (2048, 2048)  # per-rank rows, d of all-reduce + rmsnorm


def require(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"check failed: {what}")


def say(msg: str) -> None:
    print(msg, flush=True)


# ----------------------------------------------------------------- one chip


def train_phase() -> int:
    """Trainer at published widths, built as ``repro.launch.train`` builds it.

    Returns the parameter count (for the planning phase).
    """
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.data.pipeline import DataConfig
    from repro.models.module import param_count
    from repro.runtime.fault import FailureInjector
    from repro.train.optimizer import OptimizerConfig
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = get_config(ARCH)
    say(f"[train] {cfg.name}: d_model {cfg.d_model}, heads {cfg.n_heads}, "
        f"d_ff {cfg.d_ff}, vocab {cfg.vocab}, layers {cfg.n_layers} "
        f"(published {cfg.n_layers}, no depth cut), {cfg.dtype} activations, "
        f"{cfg.param_dtype} params + AdamW; batch {BATCH} x seq {SEQ}")
    trainer = Trainer(
        model_cfg=cfg,
        data_cfg=DataConfig(global_batch=BATCH, seq_len=SEQ),
        opt_cfg=OptimizerConfig(total_steps=TRAIN_STEPS, warmup_steps=1),
        trainer_cfg=TrainerConfig(total_steps=TRAIN_STEPS),
        failure_injector=FailureInjector(fail_at_steps=[]),
    )
    n_params = param_count(
        jax.eval_shape(trainer.model.init, jax.random.PRNGKey(0))
    )
    out = trainer.run()
    losses = [h["loss"] for h in out["history"]]
    times = [h["step_time_s"] for h in out["history"]]
    del out
    say(f"[train] params {n_params} ({n_params / 1e9:.3f} B); "
        f"losses {losses}")
    require(len(losses) == TRAIN_STEPS, f"{TRAIN_STEPS} steps ran")
    require(all(math.isfinite(v) for v in losses), "every loss is finite")
    # a random lm_head of std 0.02 over unit-variance d_model features gives
    # logits of variance 0.02² · d_model, so the first loss sits that much
    # above ln(vocab): ln V + σ²/2
    expect = math.log(cfg.vocab) + 0.5 * 0.02**2 * cfg.d_model
    require(abs(losses[0] - expect) < 0.5,
            f"step-0 loss {losses[0]:.4f} within 0.5 of {expect:.4f} "
            f"(ln {cfg.vocab} = {math.log(cfg.vocab):.4f})")
    warm = float(np.median(times[1:]))
    say(f"[train] warm step time {warm:.6f} s (median of steps 1-"
        f"{TRAIN_STEPS - 1}; host clock; not a claimed measurement)")
    return n_params


def kernel_phase() -> None:
    """The fused path's kernels, compiled for the chip, vs their references."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.matmul.ops import matmul
    from repro.kernels.matmul.ref import matmul_reference
    from repro.kernels.rmsnorm.ops import rmsnorm
    from repro.kernels.rmsnorm.ref import rmsnorm_reference

    def check(label, kernel_fn, ref_fn, args, tol):
        compiled = jax.jit(kernel_fn).lower(*args).compile()
        require("tpu_custom_call" in compiled.as_text(),
                f"{label}: compiled HLO holds a tpu_custom_call")
        got = np.asarray(compiled(*args), np.float32)
        want = np.asarray(jax.jit(ref_fn)(*args), np.float32)
        err = float(np.max(np.abs(got - want)))
        require(np.all(np.isfinite(got)), f"{label}: finite output")
        np.testing.assert_allclose(got, want, rtol=tol, atol=tol,
                                   err_msg=label)
        say(f"[kernels] {label}: tpu_custom_call, shape {got.shape}, "
            f"max |kernel - reference| {err:.3e} (tolerance {tol})")

    key = jax.random.PRNGKey(1)
    kx, kw, kg = jax.random.split(key, 3)
    x = jax.random.normal(kx, (4096, 2048), jnp.bfloat16)
    w = jax.random.normal(kw, (2048, 8192), jnp.bfloat16)
    check("matmul bf16 (4096x2048)@(2048x8192)",
          lambda a, b: matmul(a, b, use_pallas=True), matmul_reference,
          (x, w), 2e-2)
    for d in (2048, 12288):
        xr = jax.random.normal(kx, (4096, d), jnp.float32)
        g = 1.0 + 0.1 * jax.random.normal(kg, (d,), jnp.float32)
        check(f"rmsnorm fp32 (4096, {d})",
              lambda a, b: rmsnorm(a, b, use_pallas=True), rmsnorm_reference,
              (xr, g), 2e-5)


def planning_phase(n_params: int) -> None:
    from repro.api import PcclSession
    from repro.core import cost_model as cm

    grad_bytes = 4.0 * n_params
    plan = PcclSession(cm.TPU_V5E_PHOTONIC).plan("all_reduce", grad_bytes, n=4)
    require(plan.cost > 0 and math.isfinite(plan.cost), "planned cost is finite")
    say(f"[plan] all_reduce of {grad_bytes:.0f} B over n=4: algorithm "
        f"{plan.algorithm}, planned cost {plan.cost:.6e} s "
        "(cost model, not measured)")


# --------------------------------------------------------------- four chips


def collectives_phase(mesh) -> None:
    """Eager ``interp`` collectives vs the ``xla`` backend, every algorithm."""
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.api import PcclSession
    from repro.core import cost_model as cm
    from repro.core.pccl import candidate_algorithms, candidate_dims

    n = mesh.devices.size
    axis = mesh.axis_names[0]
    xla = PcclSession(cm.TPU_V5E_PHOTONIC).communicator(axis, n, backend="xla")
    rng = np.random.default_rng(0)
    for collective in COLLECTIVES:
        algos = [a for a in candidate_algorithms(collective, n, "auto")
                 if candidate_dims(a, n, None)[1]]
        ref = jax.jit(jax.shard_map(
            lambda xl, c=collective: getattr(xla, c)(xl[0])[None],
            mesh=mesh, in_specs=P(axis), out_specs=P(axis), check_vma=False,
        ))
        for nbytes in SIZES_PER_RANK:
            x = rng.standard_normal((n, nbytes // 4), dtype=np.float32)
            want = np.asarray(ref(x))
            for algo in algos:
                session = PcclSession(cm.TPU_V5E_PHOTONIC)
                comm = session.communicator(axis, n, backend="interp",
                                            algorithm=algo)
                got = np.asarray(getattr(comm, collective)(x))
                t0 = session.exec_stats().traces
                again = np.asarray(getattr(comm, collective)(x))
                retraces = session.exec_stats().traces - t0
                label = f"{collective} {algo} {nbytes} B/rank"
                require(retraces == 0, f"{label}: second call retraced "
                        f"{retraces} times")
                require(got.shape == want.shape, f"{label}: shape {got.shape}"
                        f" vs {want.shape}")
                np.testing.assert_array_equal(again, got, err_msg=label)
                if collective in ("all_gather", "all_to_all"):
                    np.testing.assert_array_equal(got, want, err_msg=label)
                    err = 0.0
                else:
                    np.testing.assert_allclose(got, want, rtol=1e-5,
                                               atol=1e-5, err_msg=label)
                    err = float(np.max(np.abs(got - want)))
                say(f"[collectives] {label}: matches xla (max |diff| "
                    f"{err:.3e}), 0 retraces on the second call")


def fused_phase(n: int) -> None:
    """Fused comm/compute entry points vs their unfused compositions."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.api import PcclSession
    from repro.comm import exec_engine
    from repro.comm.fusion import (
        fused_all_reduce_rmsnorm,
        fused_matmul_reduce_scatter,
    )
    from repro.core import cost_model as cm
    from repro.kernels.matmul.ops import matmul
    from repro.kernels.rmsnorm.ops import rmsnorm

    comm = PcclSession(cm.TPU_V5E_PHOTONIC, thread_fabric=False).communicator(
        "x", n, backend="interp", algorithm="ring"
    )
    kx, kw, kg = jax.random.split(jax.random.PRNGKey(2), 3)
    s0 = exec_engine.exec_stats()

    M, K, N = FUSED_MM
    x = jax.random.normal(kx, (n, M, K), jnp.bfloat16)
    w = jax.random.normal(kw, (K, N), jnp.bfloat16)
    got = np.asarray(fused_matmul_reduce_scatter(comm, x, w, interpret=False))
    y = matmul(x.reshape(n * M, K), w, use_pallas=True,
               interpret=False).reshape(n, M, N)
    want = np.asarray(comm.reduce_scatter(y))
    require(got.shape == (n, M // n, N), f"mm+RS shape {got.shape}")
    require(bool(np.all(np.isfinite(got.astype(np.float32)))), "mm+RS finite")
    np.testing.assert_array_equal(got, want, err_msg="fused mm+RS")
    say(f"[fused] matmul+reduce_scatter bf16 x {tuple(x.shape)} "
        f"w {tuple(w.shape)}: bit-identical to kernel-then-collective")

    rows, d = FUSED_RMS
    xr = jax.random.normal(kx, (n, rows, d), jnp.float32)
    g = 1.0 + 0.1 * jax.random.normal(kg, (d,), jnp.float32)
    got = np.asarray(fused_all_reduce_rmsnorm(comm, xr, g, interpret=False))
    # the all-reduce leaves its result sharded over the chips, and a Mosaic
    # kernel cannot be partitioned: the reference runs on one chip
    reduced = jax.device_put(comm.all_reduce(xr), jax.devices()[0])
    want = np.asarray(rmsnorm(reduced, g, use_pallas=True, interpret=False))
    require(bool(np.all(np.isfinite(got))), "AR+rmsnorm finite")
    np.testing.assert_array_equal(got, want, err_msg="fused AR+rmsnorm")
    say(f"[fused] all_reduce+rmsnorm fp32 {tuple(xr.shape)}: bit-identical "
        "to all_reduce-then-rmsnorm")

    s1 = exec_engine.exec_stats()
    fused = s1.fused_dispatches - s0.fused_dispatches
    fallback = s1.fallback_dispatches - s0.fallback_dispatches
    require(fused >= 2, f"fused_dispatches {fused} >= 2")
    require(fallback == 0, f"fallback_dispatches {fallback} == 0")
    say(f"[fused] exec_stats: fused_dispatches +{fused}, "
        f"fallback_dispatches +{fallback}")


def dp_phase(mesh) -> None:
    """Data-parallel steps with the gradient all-reduce on interp and xla."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.api import PcclSession
    from repro.configs import get_config
    from repro.core import cost_model as cm
    from repro.data.pipeline import DataConfig, SyntheticLMData
    from repro.models import build_model, unbox
    from repro.train.optimizer import OptimizerConfig, init_opt_state
    from repro.train.train_step import make_dp_train_step

    n = mesh.devices.size
    axis = mesh.axis_names[0]
    cfg = get_config(ARCH)
    say(f"[dp] {cfg.name} at published widths, layers {cfg.n_layers} "
        f"(no depth cut), pure DP over {n} chips, global batch {BATCH} x "
        f"seq {SEQ}")
    model = build_model(cfg)
    opt_cfg = OptimizerConfig(total_steps=DP_STEPS, warmup_steps=1)
    data = SyntheticLMData(cfg, DataConfig(global_batch=BATCH, seq_len=SEQ))
    replicated = NamedSharding(mesh, P())
    init_params = jax.jit(lambda k: unbox(model.init(k)),
                          out_shardings=replicated)
    init_opt = jax.jit(init_opt_state, out_shardings=replicated)

    losses = {}
    for backend in ("interp", "xla"):
        comm = PcclSession(cm.TPU_V5E_PHOTONIC).communicator(
            axis, n, backend=backend
        )
        step = make_dp_train_step(model, opt_cfg, comm, mesh)
        params = init_params(jax.random.PRNGKey(0))
        opt_state = init_opt(params)
        losses[backend], times = [], []
        for i in range(DP_STEPS):
            batch = {k: jnp.asarray(v) for k, v in data.global_batch(i).items()}
            t0 = time.perf_counter()
            params, opt_state, loss = step(params, opt_state, batch)
            losses[backend].append(float(loss))
            times.append(time.perf_counter() - t0)
        del params, opt_state
        require(all(math.isfinite(v) for v in losses[backend]),
                f"{backend}: every loss is finite")
        say(f"[dp] backend {backend}: losses {losses[backend]}; last step "
            f"{times[-1]:.6f} s (host clock; not a claimed measurement)")
    li, lx = np.array(losses["interp"]), np.array(losses["xla"])
    require(abs(li[0] - lx[0]) <= 1e-4 * abs(lx[0]),
            f"step-0 loss interp {li[0]} == xla {lx[0]}")
    require(bool(np.all(np.abs(li - lx) <= 1e-2 * np.abs(lx))),
            f"interp losses {li} stay within 1% of xla {lx}")
    say(f"[dp] interp vs xla: step-0 |diff| {abs(li[0] - lx[0]):.3e}, "
        f"max |diff| {float(np.max(np.abs(li - lx))):.3e}")


# --------------------------------------------------------------------- main


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: trainer, kernels, planning; 4: the cross-chip "
                    "path (collectives, fused entry points, DP step)")
    args = ap.parse_args()

    from repro.launch.cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {platform}); nothing ran",
              file=sys.stderr)
        return 1
    say(f"device: platform {platform}, kind {kind}, count {len(devices)}; "
        f"compile cache {cache_dir}")
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 1

    t0 = time.perf_counter()
    if args.chips == 1:
        n_params = train_phase()
        kernel_phase()
        planning_phase(n_params)
    else:
        from jax.sharding import Mesh

        mesh = Mesh(devices[: args.chips], ("x",))
        collectives_phase(mesh)
        fused_phase(args.chips)
        dp_phase(Mesh(devices[: args.chips], ("data",)))
    say(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
