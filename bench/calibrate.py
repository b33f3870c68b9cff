"""Readings that a cell's correctness limits are set from, in one process.

    python3 bench/calibrate.py --workload <name> --seeds <s1,s2,...>
        [--control-seeds <n>] [--fault-seeds <n>] [--faults <a,b>]

For each seed: the program's first steps through the cell's own set-up
(the same object the window would drive), then the plain reference over
the same batches, and the numbers that ``correct`` compares.  For the first
``--control-seeds`` seeds, the control: the reference computed in float8
in the program's place.  For the first ``--fault-seeds`` seeds, each fault
of ``bench/faults.py`` that the cell can have, planted under the program's
step.  Prints one JSON line per reading and a summary last: the largest
reading of the sound runs and the smallest of the control and each fault,
per number.  The benchmark's own runs do not run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import faults, harness  # noqa: E402
from bench.run import accelerator, enable_cache  # noqa: E402
from bench.training import compare  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--faults", default=None, help="comma-separated; default: all the cell can have")
    args = ap.parse_args(argv)

    bench = harness.benchmark()
    w = harness.workload(bench, args.workload)
    cfg, traffic = harness.config(w["config"]), harness.traffic(w["traffic"])
    limits = harness.limits(args.workload)
    devices = accelerator(traffic["chips"])
    enable_cache()
    mode = harness.mode(traffic["mode"])
    seeds = [int(s) for s in args.seeds.split(",")]
    fault_names = args.faults.split(",") if args.faults else faults.faults_for(traffic)

    readings = {"program": [], "control": []}
    wants = {}

    def emit(kind, seed, numbers, t0):
        vals = {k: v["value"] for k, v in numbers.items()}
        readings.setdefault(kind, []).append(vals)
        print(json.dumps({"kind": kind, "seed": seed, "numbers": vals,
                          "seconds": round(time.perf_counter() - t0, 3)}), flush=True)

    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        run = mode.setup(cfg, traffic, seed, devices)
        run.free()
        wants[seed] = run.reference_readings()
        emit("program", seed, compare(run.readings, wants[seed], limits), t0)
        if i < args.control_seeds:
            t0 = time.perf_counter()
            emit("control", seed, compare(run.reference_readings("fp8"), wants[seed], limits), t0)
        del run

    for name in fault_names:
        for seed in seeds[: args.fault_seeds]:
            t0 = time.perf_counter()
            run = mode.setup(cfg, traffic, seed, devices, wrap=faults.FAULTS[name])
            run.free()
            emit(f"fault.{name}", seed, compare(run.readings, wants[seed], limits), t0)
            del run

    summary = {}
    for kind, rows in readings.items():
        if rows:
            pick = max if kind == "program" else min
            summary[kind] = {k: pick(r[k] for r in rows) for k in rows[0]}
    print(json.dumps({"workload": args.workload, "summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
