"""engine_ms: device milliseconds per step under a ``pccl_*`` scope, every op
the PCCL engine adds to a step (bench/scopes.py), averaged over the
cell's devices.  Nothing to read where the trace names no such op."""

from bench import scopes


def read(trace, run):
    return scopes.ms_per_step(trace, run, "engine")
