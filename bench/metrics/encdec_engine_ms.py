"""encdec_engine_ms: device milliseconds per step under a ``pccl_*`` scope in
an encoder-decoder cell, every op the PCCL engine adds to the step
(bench/encdec_scopes.py), averaged over the cell's devices.  Nothing to
read where the trace names no such op."""

from bench import encdec_scopes


def read(trace, run):
    return encdec_scopes.ms_per_step(trace, run, "engine")
