"""The training steps' named scopes, read from their lowered HLO.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python tests/scopes_check.py

Lowers ``make_train_step`` and ``make_dp_train_step`` (interp backend, 4
ranks) for a small bert-base-paper and prints one JSON line per step:
which scopes its ``op_name`` metadata holds, what share of the
instructions that read a step argument fall under one, the instructions
that fall under none, and, for the DP step, the collective-permutes and
round groups under each ``pccl_*`` scope beside those the compiled
schedules have.
"""

from __future__ import annotations

import dataclasses
import json
import re
import sys
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

SCOPES = ("train_forward", "train_optimizer", "train_grad_sync", "pccl_")
# instructions that move or name values and compute nothing: entry and
# loop parameters, constants, tuples, and shard_map's sharding custom calls
PLUMBING = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast", "copy",
            "custom-call"}
CONTAINERS = {"while", "conditional", "call"}

_COMP = re.compile(r"^(ENTRY )?%?([\w.\-]+) .*\{$")
_INST = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*? ([a-z][a-z0-9\-]*)\(([^)]*)\)(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def computations(text):
    """``{computation: [(name, opcode, operands, line)]}`` and the entry's name."""
    comps, cur, entry = {}, None, None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m and not line.startswith(" "):
            cur = m.group(2)
            comps[cur] = []
            entry = cur if m.group(1) else entry
        elif line.strip() == "}":
            cur = None
        elif cur is not None and (mi := _INST.match(line)):
            operands = [o.strip().split(" ")[-1].lstrip("%") for o in mi.group(3).split(",")]
            comps[cur].append((mi.group(1), mi.group(2), [o for o in operands if o], line))
    return comps, entry


def executed(text):
    """``(opcode, op_name, reads_an_argument)`` of every instruction the
    step runs: the entry's and those of the computations that loops,
    conditionals and calls run, each named under its call site's op_name
    (the lowered HLO names a called computation's ops relative to it)."""
    comps, entry = computations(text)
    out, todo = [], [(entry, "", True)]
    while todo:
        comp, prefix, args_read = todo.pop()
        reads = {}
        for name, op, operands, line in comps[comp]:
            m = _OP_NAME.search(line)
            op_name = "/".join(p for p in (prefix, m.group(1) if m else "") if p)
            reads[name] = args_read if op == "parameter" else any(
                reads.get(o, False) for o in operands)
            called = []
            if op == "while":
                called = re.findall(r"(?:condition|body)=%?([\w.\-]+)", line)
            elif op == "conditional":
                called = re.findall(r"%([\w.\-]+)", line.split("branch_computations=")[-1])
            elif op == "call":
                called = re.findall(r"to_apply=%?([\w.\-]+)", line)
            todo.extend((c, op_name, reads[name]) for c in called)
            out.append((op, op_name, reads[name]))
    return out


def report(text):
    ops = executed(text)
    counted = [o for o in ops if o[0] not in PLUMBING | CONTAINERS and o[2]]
    unscoped = [o for o in counted if not any(s in o[1] for s in SCOPES)]
    names = {o[1] for o in ops}
    return {
        "scopes": sorted({s for s in ("jvp(train_forward)", "transpose(jvp(train_forward))",
                                      "train_optimizer", "train_grad_sync")
                          if any(f"/{s}/" in n for n in names)}
                         | {m for n in names for m in re.findall(r"pccl_\w+/group\d+", n)}),
        "instructions": len(counted),
        "covered": 1 - len(unscoped) / len(counted),
        "unscoped": sorted(Counter(f"{op} {n}" for op, n, _ in unscoped).items()),
        "permutes": Counter(m.group(0) if (m := re.search(r"pccl_\w+/group\d+", n)) else ""
                            for op, n, _ in ops if op == "collective-permute"),
    }


def small_model():
    from repro.configs import get_config
    from repro.models import build_model

    cfg = dataclasses.replace(get_config("bert-base-paper"), n_layers=2, d_model=32,
                              n_heads=2, n_kv_heads=2, d_ff=64, vocab=128)
    return build_model(cfg)


def main() -> int:
    from jax.sharding import Mesh

    from repro.api import PcclSession
    from repro.comm.exec_engine import compile_schedule
    from repro.core import cost_model as cm
    from repro.models.module import unbox
    from repro.train.optimizer import OptimizerConfig, init_opt_state
    from repro.train.train_step import make_dp_train_step, make_train_step

    model = small_model()
    params = jax.eval_shape(lambda k: unbox(model.init(k)), jax.random.PRNGKey(0))
    opt = jax.eval_shape(init_opt_state, params)

    def batch(rows):
        return {k: jax.ShapeDtypeStruct((rows, 8), jnp.int32) for k in ("tokens", "targets")}

    step = jax.jit(make_train_step(model, OptimizerConfig()), donate_argnums=(0, 1))
    text = step.lower(params, opt, batch(2)).as_text(dialect="hlo", debug_info=True)
    print(json.dumps({"step": "single", **report(text)}), flush=True)

    n = 4
    mesh = Mesh(np.array(jax.devices()[:n]), ("data",))
    comm = PcclSession(cm.TPU_V5E_PHOTONIC).communicator("data", n, backend="interp",
                                                         algorithm="auto")
    step = make_dp_train_step(model, OptimizerConfig(), comm, mesh)
    text = step.lower(params, opt, batch(2 * n)).as_text(dialect="hlo", debug_info=True)
    # the round groups each gradient leaf's all-reduce runs, as the backend plans it
    groups = Counter()
    for leaf in jax.tree.leaves(params):
        size = leaf.size + (-leaf.size) % n
        sched = comm.axis_schedule("all_reduce", size * 4)
        for i in range(len(compile_schedule(sched).groups)):
            groups[f"pccl_{sched.collective}_{sched.algorithm}/group{i}"] += 1
    print(json.dumps({"step": "dp", **report(text), "planned_groups": groups}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
