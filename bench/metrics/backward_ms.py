"""backward_ms: device milliseconds per step under ``transpose(jvp(train_forward))``,
the backward pass with its remat recompute (bench/scopes.py), averaged
over the cell's devices.  Nothing to read where the trace names no such op."""

from bench import scopes


def read(trace, run):
    return scopes.ms_per_step(trace, run, "backward")
