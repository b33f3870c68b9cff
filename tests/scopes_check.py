"""The training steps' named scopes, read from their lowered HLO.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python tests/scopes_check.py

Lowers ``make_train_step`` and ``make_dp_train_step`` (interp backend, 4
ranks) for a small bert-base-paper and a small whisper-small and prints
one JSON line per step: which scopes its ``op_name`` metadata holds, what
share of the instructions that read a step argument fall under one, the
instructions that fall under none, and, for the DP step, the
collective-permutes and round groups under each ``pccl_*`` scope beside
those the compiled schedules have.  For whisper-small's steps also: where
the instructions that read the encoder's and the cross-attention's
parameters fall (and how many would fall outside their scope in a build
without the scopes), how deep each of the two scopes nests in itself, and
whether the optimised HLO equals that build's, names and metadata aside.
"""

from __future__ import annotations

import contextlib
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
from check_helpers import canonical_hlo, small_model  # noqa: E402

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
                                      "train_optimizer", "train_grad_sync", *ENCDEC_SCOPES)
                          if any(f"/{s}/" in n for n in names)}
                         | {m for n in names for m in re.findall(r"pccl_\w+/group\d+", n)}),
        "instructions": len(counted),
        "covered": 1 - len(unscoped) / len(counted),
        "unscoped": sorted(Counter(f"{op} {n}" for op, n, _ in unscoped).items()),
        "permutes": Counter(m.group(0) if (m := re.search(r"pccl_\w+/group\d+", n)) else ""
                            for op, n, _ in ops if op == "collective-permute"),
    }


# what passes a value on unchanged, or a slice of it (a scan's layer of a
# stacked parameter): an instruction of these reads nothing of a parameter
# that it hands on
PASS_ON = {"get-tuple-element", "tuple", "bitcast", "copy", "reshape", "dynamic-slice", "slice",
           "custom-call", "opt-barrier", "parameter"}


def _any(taint):
    return any(map(_any, taint)) if isinstance(taint, tuple) else taint


def _join(a, b):
    if isinstance(a, tuple):
        return tuple(map(_join, a, b))
    return a or b


def param_readers(text, wanted):
    """``Counter`` of ``(opcode, op_name)`` of every instruction the step runs
    that computes with an entry parameter whose op_name (the argument's
    path, as ``params['enc_layers']['attn']['wq']``) matches the regex
    ``wanted``.  Values are followed through :data:`PASS_ON` instructions,
    tuples, loops and calls; an op_name inside a called computation is
    joined to its call site's, as in :func:`executed`."""
    comps, entry = computations(text)
    readers = Counter()
    seen = set()

    def run(comp, prefix, params):
        taint = {}
        for name, op, operands, line in comps[comp]:
            m = _OP_NAME.search(line)
            op_name = "/".join(p for p in (prefix, m.group(1) if m else "") if p)
            ins = [taint.get(o, False) for o in operands]
            if op == "parameter":
                idx = int(re.search(r"parameter\((\d+)\)", line).group(1))
                taint[name] = (bool(m and re.search(wanted, m.group(1))) if params is None
                               else params[idx])
            elif op == "tuple":
                taint[name] = tuple(ins)
            elif op == "get-tuple-element":
                t = ins[0]
                i = int(re.search(r"index=(\d+)", line).group(1))
                taint[name] = t[i] if isinstance(t, tuple) else t
            elif op == "while":
                cond, body = (re.search(rf"{k}=%?([\w.\-]+)", line).group(1)
                              for k in ("condition", "body"))
                state = ins[0]
                while True:
                    out = run(body, op_name, [state])
                    new = _join(state, out)
                    if new == state:
                        break
                    state = new
                run(cond, op_name, [state])
                taint[name] = state
            elif op == "call":
                callee = re.search(r"to_apply=%?([\w.\-]+)", line).group(1)
                taint[name] = run(callee, op_name, ins)
            elif op == "custom-call" and len(ins) > 1:
                # shard_map's shape conversion: a tuple of its operands
                taint[name] = tuple(ins)
            elif op in PASS_ON:
                taint[name] = ins[0] if ins else False
            else:
                taint[name] = False
                if any(map(_any, ins)) and (comp, name, prefix) not in seen:
                    seen.add((comp, name, prefix))
                    readers[(op, op_name)] += 1
            if line.lstrip().startswith("ROOT "):
                root = taint[name]
        return root

    run(entry, "", None)
    return readers


# the encoder-decoder's scopes, each with the parameters (a regex on the
# entry argument's path) that every instruction reading them outside the
# optimizer must compute under it
ENCDEC_SCOPES = {
    "encdec_encoder": r"^params\W+(enc_layers|ln_enc)\W",
    "encdec_cross_attention": r"^params\W+dec_layers\W+xattn\W",
}


@contextlib.contextmanager
def without_scopes(names):
    """``jax.named_scope`` opens no scope of ``names`` while this lasts."""
    real = jax.named_scope
    jax.named_scope = lambda name: contextlib.nullcontext() if name in names else real(name)
    try:
        yield
    finally:
        jax.named_scope = real


def encdec_report(lowered, lower_step):
    """Where the parameters of each encoder-decoder scope are read, how
    deep each scope nests in itself, and whether the optimised HLO equals
    that of a build without the two scopes, names and metadata aside."""
    text = lowered.as_text(dialect="hlo", debug_info=True)
    readers = {}
    for scope, wanted in ENCDEC_SCOPES.items():
        found = param_readers(text, wanted)
        readers[scope] = {
            "under": sum(n for (_, o), n in found.items() if scope in o),
            "optimizer": sum(n for (_, o), n in found.items()
                             if scope not in o and "train_optimizer" in o),
            "other": sorted(f"{op} {o}" for op, o in found
                            if scope not in o and "train_optimizer" not in o),
        }
    names = set(_OP_NAME.findall(text))
    with without_scopes(set(ENCDEC_SCOPES)):
        plain = lower_step()
    # the reader count of the build without the scopes shows that it can fail
    unscoped = plain.as_text(dialect="hlo", debug_info=True)
    return {
        "readers": readers,
        "readers_without_scopes": {
            s: sum(n for (_, o), n in param_readers(unscoped, w).items()
                   if s not in o and "train_optimizer" not in o)
            for s, w in ENCDEC_SCOPES.items()},
        "nesting": {s: max(n.count(s) for n in names) for s in ENCDEC_SCOPES},
        "hlo_equal_without_scopes": (canonical_hlo(lowered.compile().as_text())
                                     == canonical_hlo(plain.compile().as_text())),
    }


def main() -> int:
    from jax.sharding import Mesh

    from repro.api import PcclSession
    from repro.comm.exec_engine import compile_schedule
    from repro.core import cost_model as cm
    from repro.models.module import unbox
    from repro.train.optimizer import OptimizerConfig, init_opt_state
    from repro.train.train_step import make_dp_train_step, make_train_step

    n = 4
    mesh = Mesh(np.array(jax.devices()[:n]), ("data",))
    comm = PcclSession(cm.TPU_V5E_PHOTONIC).communicator("data", n, backend="interp",
                                                         algorithm="auto")
    for prefix, name in (("", "bert-base-paper"), ("whisper_", "whisper-small")):
        model = small_model(name)
        params = jax.eval_shape(lambda k: unbox(model.init(k)), jax.random.PRNGKey(0))
        opt = jax.eval_shape(init_opt_state, params)

        def batch(rows):
            if prefix:
                return {"tokens": jax.ShapeDtypeStruct((rows, 8), jnp.int32),
                        "enc_frames": jax.ShapeDtypeStruct((rows, 12, 32), jnp.float32)}
            return {k: jax.ShapeDtypeStruct((rows, 8), jnp.int32) for k in ("tokens", "targets")}

        def single():
            step = jax.jit(make_train_step(model, OptimizerConfig()), donate_argnums=(0, 1))
            return step.lower(params, opt, batch(2))

        def dp():
            step = make_dp_train_step(model, OptimizerConfig(), comm, mesh)
            return step.lower(params, opt, batch(2 * n))

        # the round groups each gradient leaf's all-reduce runs, as the backend plans it
        groups = Counter()
        for leaf in jax.tree.leaves(params):
            size = leaf.size + (-leaf.size) % n
            sched = comm.axis_schedule("all_reduce", size * 4)
            for i in range(len(compile_schedule(sched).groups)):
                groups[f"pccl_{sched.collective}_{sched.algorithm}/group{i}"] += 1
        for kind, lower in (("single", single), ("dp", dp)):
            lowered = lower()
            row = {"step": prefix + kind,
                   **report(lowered.as_text(dialect="hlo", debug_info=True))}
            if kind == "dp":
                row["planned_groups"] = groups
            if prefix:
                row.update(encdec_report(lowered, lower))
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
