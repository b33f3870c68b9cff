"""encoder_ms: device milliseconds per step under the encoder-decoder's
``encdec_encoder`` scope, its forward, remat recompute and backward
(bench/encdec_scopes.py), averaged over the cell's devices.  Nothing to
read where the trace names no such op."""

from bench import encdec_scopes


def read(trace, run):
    return encdec_scopes.ms_per_step(trace, run, "encoder")
