"""The training steps name their phases and the PCCL engine its round groups.

``tests/scopes_check.py`` lowers both steps at a small size on four CPU
devices and reads the ``op_name`` metadata, which is what a profiler
trace of the chip names each op by (``bench/scopes.py`` reads it there).
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

# What may fall under no scope: the rotary tables, which depend on no
# parameter and which the scan's partial evaluation hoists out of the
# forward scope.  Elementwise ops only; no matmul, reduction or collective.
UNSCOPED_OPCODES = {"broadcast", "convert", "cosine", "sine", "divide", "multiply",
                    "power", "reshape"}


@pytest.fixture(scope="module")
def steps():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run([sys.executable, str(ROOT / "tests" / "scopes_check.py")],
                          cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return {r["step"]: r for r in map(json.loads, proc.stdout.strip().splitlines())}


PHASES = {"jvp(train_forward)", "transpose(jvp(train_forward))", "train_optimizer"}
ENCDEC = {"encdec_encoder", "encdec_cross_attention"}
ENCDEC_STEPS = ["whisper_single", "whisper_dp"]


@pytest.mark.parametrize("step,scopes", [
    ("single", PHASES),
    ("dp", PHASES | {"train_grad_sync"}),
    ("whisper_single", PHASES | ENCDEC),
    ("whisper_dp", PHASES | ENCDEC | {"train_grad_sync"}),
])
def test_step_carries_every_phase_scope(steps, step, scopes):
    assert scopes <= set(steps[step]["scopes"])


@pytest.mark.parametrize("step", ["single", "dp", *ENCDEC_STEPS])
def test_nearly_every_instruction_falls_under_a_scope(steps, step):
    r = steps[step]
    assert r["covered"] >= 0.98, r["unscoped"]
    opcodes = {name.split(" ")[0] for name, _ in r["unscoped"]}
    assert opcodes <= UNSCOPED_OPCODES, r["unscoped"]


def test_every_collective_permute_is_under_an_engine_round_group(steps):
    permutes = steps["dp"]["permutes"]
    assert "" not in permutes, permutes
    assert sum(permutes.values()) > 0


def test_one_round_group_scope_per_compiled_round_group(steps):
    # one scanned ppermute per round group per gradient leaf, each under
    # that group's scope
    r = steps["dp"]
    assert r["permutes"] == r["planned_groups"]
    assert {s for s in r["scopes"] if s.startswith("pccl_")} == set(r["planned_groups"])


def test_collective_scope_does_not_nest_in_itself():
    import jax
    import jax.numpy as jnp

    from repro.comm.exec_engine import collective_scope

    def f(x):
        with collective_scope("all_reduce", "ring"):
            y = x + 1
            with collective_scope("all_reduce", "ring"):
                y = y * 2
            with collective_scope("all_gather", "rhd"):
                y = y - 3
        return y

    text = jax.jit(f).lower(jnp.ones(4)).as_text(dialect="hlo", debug_info=True)
    assert "pccl_all_reduce_ring/mul" in text
    assert "pccl_all_reduce_ring/pccl_all_reduce_ring" not in text
    assert "pccl_all_reduce_ring/pccl_all_gather_rhd/sub" in text


@pytest.mark.parametrize("step", ENCDEC_STEPS)
@pytest.mark.parametrize("scope", sorted(ENCDEC))
def test_encdec_parameters_are_read_under_their_scope(steps, step, scope):
    """Every instruction that computes with the encoder's parameters
    (``enc_layers``, ``ln_enc``), or with the cross-attention's
    (``xattn``), runs under that scope, the AdamW update aside; in a build
    without the scopes the same count falls outside it."""
    r = steps[step]["readers"][scope]
    assert r["other"] == [], r["other"]
    assert r["under"] > 0 and r["optimizer"] > 0, r
    assert steps[step]["readers_without_scopes"][scope] == r["under"]


@pytest.mark.parametrize("step", ENCDEC_STEPS)
def test_encdec_scopes_do_not_nest_in_themselves(steps, step):
    assert steps[step]["nesting"] == {s: 1 for s in ENCDEC}


@pytest.mark.parametrize("step", ENCDEC_STEPS)
def test_encdec_scopes_change_no_instruction(steps, step):
    # the optimised HLO of the small step equals that of a build in which
    # the two scopes open nothing, instruction names and metadata aside
    assert steps[step]["hlo_equal_without_scopes"]


def test_encdec_dp_step_permutes_are_its_round_groups(steps):
    # all 32 gradient leaves through the engine: one ppermute per leaf and
    # round group, each under that group's scope
    r = steps["whisper_dp"]
    assert "" not in r["permutes"], r["permutes"]
    assert r["permutes"] == r["planned_groups"]
    assert sum(r["planned_groups"].values()) == 32 * len(r["planned_groups"])
