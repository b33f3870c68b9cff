"""optimizer_ms: device milliseconds per step under the program's
``train_optimizer`` scope, the AdamW update (bench/scopes.py), averaged
over the cell's devices.  Nothing to read where the trace names no such op."""

from bench import scopes


def read(trace, run):
    return scopes.ms_per_step(trace, run, "optimizer")
