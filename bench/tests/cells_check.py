"""Drive each cell at a small size on CPU devices, sound and broken.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python bench/tests/cells_check.py <workload>

Skips the harness's look for a chip and runs the rest of a run
(``run_cell``: set-up, window, reference check) with the cell's own mode,
traffic mix and limits, at small widths.  Prints one JSON line per case:
the sound run, each fault of ``bench/faults.py`` that the cell can have,
and the control (the reference in float8 in the program's place).
"""

from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

SMALL = dict(n_layers=2, d_model=32, n_heads=2, n_kv_heads=2, d_ff=64, vocab=128)


def small_cell(name):
    from bench import harness
    from repro.configs import get_config

    w = harness.workload(harness.benchmark(), name)
    cfg, traffic = harness.config(w["config"]), harness.traffic(w["traffic"])
    small = dict(SMALL)
    registry = get_config(cfg["registry"])
    if registry.enc_dec is not None:
        small["enc_dec"] = dataclasses.replace(registry.enc_dec, n_enc_layers=2, enc_seq=12)
    model_cfg = dataclasses.replace(registry, **small)
    as_json = {k: dataclasses.asdict(v) if dataclasses.is_dataclass(v) else v
               for k, v in small.items()}
    cfg = dict(cfg, config=dict(cfg["config"], **as_json))
    traffic = dict(traffic, batch_per_chip=2, seq_len=8, pool_batches=4,
                   reference_blocks=min(traffic["reference_blocks"], 2))
    return cfg, traffic, model_cfg


def main(name: str) -> int:
    import jax

    from bench import faults
    from bench.run import run_cell
    from bench.training import compare, passed

    cfg, traffic, model_cfg = small_cell(name)
    devices = jax.devices()
    seed = 2**33 + 5
    cases = [("sound", None)] + [(f, faults.FAULTS[f]) for f in faults.faults_for(traffic)]
    for case, wrap in cases:
        kw = {"model_cfg": model_cfg, **({"wrap": wrap} if wrap else {})}
        res = run_cell(name, seed, 0.2, False, devices, cfg=cfg, traffic=traffic, setup_kw=kw)
        print(json.dumps({"case": case, "correct": res["correct"], "compared": res["compared"]}),
              flush=True)

    from bench import harness

    mode = harness.mode(traffic["mode"])
    run = mode.setup(cfg, traffic, seed, devices, model_cfg=model_cfg)
    run.free()
    want = run.reference_readings()
    compared = compare(run.reference_readings("fp8"), want, harness.limits(name))
    print(json.dumps({"case": "control", "correct": passed(compared), "compared": compared}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
