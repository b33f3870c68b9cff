"""engine_exposed_ms: device milliseconds per step under a ``pccl_*`` scope in
which no op outside ``pccl_*`` runs on that device (bench/scopes.py),
averaged over the cell's devices.  Nothing to read where the trace names
no ``pccl_*`` op."""

from bench import scopes


def read(trace, run):
    return scopes.ms_per_step(trace, run, "engine_exposed")
