"""Run one benchmark cell on the accelerator and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix; the mix names the mode that drives the program.  The run sets
up (weights and batches from the seed, compile, first steps), times a window
of back-to-back steps for ``--seconds``, reads the peak device memory,
checks the first steps against the plain reference, and prints one JSON
line last on standard output.  With ``--trace 1`` the window runs under the
profiler and the line carries the per-layer metrics and a breakdown; with
``--trace 0`` it carries the end-to-end metrics.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits with code 3.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

NO_ACCELERATOR = 3
BAD_CELL = 2


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class NoAccelerator(harness.HarnessError):
    """No TPU, or fewer chips than the cell asks for."""


def accelerator(chips: int):
    """The devices of the cell, or :class:`NoAccelerator`."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices


def enable_cache() -> str:
    import jax

    from repro.launch.cache import enable_compile_cache

    where = enable_compile_cache()
    # keep every program, however quick to compile, so that a run after the
    # first compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class CompileCounter:
    """Counts JAX compile events while ``active``."""

    def __init__(self):
        import jax

        self.active, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if self.active and "compile" in event:
            self.count += 1


def timed_window(run, seconds: float):
    """Back-to-back steps, each blocked on its loss, for ``seconds``."""
    import jax

    steps = failed = 0
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            with jax.profiler.TraceAnnotation("bench.next_batch"):
                batch = run.next_batch()
            with jax.profiler.TraceAnnotation("bench.step"):
                loss = run.step(batch)
            with jax.profiler.TraceAnnotation("bench.block"):
                value = float(loss)
            steps += 1
            failed += not math.isfinite(value)
            if time.perf_counter() - t0 >= seconds:
                break
    return steps, failed, time.perf_counter() - t0


def per_layer(bench, name, cfg, traffic, summary, steps, device_kind):
    from bench.flops import step_flops

    run = {"steps": steps, "chips": traffic["chips"],
           "flops_per_step": step_flops(cfg, traffic),
           "peak_flops": harness.peaks(device_kind)["bf16_flops"]}
    out = {}
    for m in harness.metrics_for(bench["per_layer"], name):
        value = harness.metric_reader(m["name"]).read(summary, run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(name: str, seed: int, seconds: float, trace: bool, devices, *,
             cfg=None, traffic=None, limits=None, setup_kw=None, keep_trace=None):
    """Set up, time, check; return the result line as a dict."""
    import jax

    bench = harness.benchmark()
    w = harness.workload(bench, name)
    cfg = cfg if cfg is not None else harness.config(w["config"])
    traffic = traffic if traffic is not None else harness.traffic(w["traffic"])
    limits = limits if limits is not None else harness.limits(name)
    mode = harness.mode(traffic["mode"])
    counter = CompileCounter()

    run = mode.setup(cfg, traffic, seed, devices, **(setup_kw or {}))
    setup_s = time.perf_counter() - T_START
    log(f"[bench] {name}: set-up {setup_s:.3f} s; first losses {run.readings.losses.tolist()}")

    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        if trace:
            # the Python tracer would time the window's own loop; the host
            # spans come from the TraceAnnotations
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(tmp, profiler_options=options)
        counter.active = True
        steps, failed, window_s = timed_window(run, seconds)
        counter.active = False
        if trace:
            jax.profiler.stop_trace()
        log(f"[bench] window: {steps} steps in {window_s:.6f} s, {failed} not finite, "
            f"{counter.count} compile events inside the window")
        used = devices[: traffic["chips"]]
        stats = [d.memory_stats() or {} for d in used]
        log(f"[bench] memory stats of device 0: {stats[0]}")
        peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
        kind = devices[0].device_kind
        device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
                  "memory_peak_bytes": peak}
        result = {"correct": False, "attempted": steps, "failed": failed}
        if trace:
            from bench.trace_reduce import read_trace

            (path,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))
            summary = read_trace(path)
            for dev, d in summary.devices.items():
                log(f"[bench] device {dev}: busy {d.busy_ns * 1e-9:.6f} s of "
                    f"{summary.window_ns * 1e-9:.6f} s, idle "
                    f"{100 * (1 - d.busy_ns / summary.window_ns):.3f} %")
            result["metrics"] = per_layer(bench, name, cfg, traffic, summary, steps, kind)
            device["busy_s"] = summary.mean("busy_ns") * 1e-9
            device["window_s"] = summary.window_ns * 1e-9
            result["device"] = device
            result["breakdown"] = {"device_ops": summary.device_ops(),
                                   "idle_gaps": summary.idle_gaps()}
            if keep_trace:
                shutil.copy(path, keep_trace)
        else:
            result["metrics"] = {
                "step_s": {"value": window_s / steps, "unit": "s"},
                "peak_hbm_gib": {"value": peak / 2**30, "unit": "GiB"},
                "setup_s": {"value": setup_s, "unit": "s"},
            }
            result["device"] = device
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)

    t0 = time.perf_counter()
    compared = run.check(limits)
    log(f"[bench] reference check {time.perf_counter() - t0:.3f} s")
    from bench.training import passed

    result["correct"] = bool(passed(compared) and failed == 0)
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced window's .xplane.pb to this path")
    args = ap.parse_args(argv)
    try:
        bench = harness.benchmark()
        w = harness.workload(bench, args.workload)
        chips = harness.traffic(w["traffic"])["chips"]
        devices = accelerator(chips)
    except harness.HarnessError as e:
        log(f"bench: {e}; nothing ran")
        return NO_ACCELERATOR if isinstance(e, NoAccelerator) else BAD_CELL
    log(f"[bench] compile cache {enable_cache()}")
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace), devices,
                      keep_trace=args.keep_trace)
    for key, c in result["compared"].items():
        log(f"compared {key} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
