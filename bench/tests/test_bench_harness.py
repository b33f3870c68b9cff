"""The harness finds every cell's files by name, and refuses to run
without a TPU or without the program."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness, training

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return harness.benchmark()


def test_every_cell_resolves(bench):
    for w in bench["workloads"]:
        cfg, traffic = harness.config(w["config"]), harness.traffic(w["traffic"])
        assert cfg["name"] == w["config"]
        assert traffic["chips"] == w["chips"]
        assert callable(harness.mode(traffic["mode"]).setup)
        assert set(harness.limits(w["name"])) == {"loss_gap", "grad_norm_gap", "change_norm_gap"}
    for m in bench["per_layer"]:
        assert callable(harness.metric_reader(m["name"]).read)


def test_metrics_for_filters_by_cell():
    entries = [{"name": "mfu"}, {"name": "collective_ms", "workloads": ["a.dp4"]},
               {"name": "idle_share"}]
    names = lambda w: [m["name"] for m in harness.metrics_for(entries, w)]
    assert names("a.dp4") == ["mfu", "collective_ms", "idle_share"]
    assert names("a.1chip") == ["mfu", "idle_share"]


def test_metrics_name_existing_cells(bench):
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert set(m.get("workloads", [])) <= cells, m["name"]


def test_benchmark_file_shape(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and c["file"] == f"bench/configs/{c['name']}.json"
        assert c["reduced"] == harness.config(c["name"])["reduced"]
    for entry in bench["workloads"] + bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(entry["name"]), entry["name"]
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)
    assert {m["name"] for m in bench["end_to_end"]} >= {"setup_s"}
    for m in bench["per_layer"]:
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}


def test_peaks_refuse_an_unknown_device():
    assert harness.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(harness.HarnessError):
        harness.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("name", ["bert-base-paper", "whisper-small"])
def test_config_matches_registry_and_reference_layout(name):
    import jax

    from bench import reference as ref
    from repro.models import build_model

    cfg = harness.config(name)
    model_cfg = harness.model_config(cfg)
    spec = ref.param_spec(training.reference_config(cfg))
    model = build_model(model_cfg)
    training._check_layout(model, spec)
    count = sum(int(jax.numpy.prod(jax.numpy.array(s))) for s in
                jax.tree.leaves(ref.spec_shapes(spec), is_leaf=lambda x: isinstance(x, tuple)))
    assert count == cfg["params"]


def test_config_mismatch_is_refused():
    cfg = harness.config("bert-base-paper")
    bad = dict(cfg, config=dict(cfg["config"], d_model=1024))
    with pytest.raises(harness.HarnessError):
        harness.model_config(bad)


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "bert.1chip", "--seed", "2147483659",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_no_tpu_exits_nonzero_with_no_result():
    proc = _run(ROOT, {})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_benchmark_alone_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {"PYTHONPATH": ""}
    proc = _run(tmp_path, env)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
