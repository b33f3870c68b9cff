"""compute_ms: device milliseconds per step covered by non-collective
operations, averaged over the cell's devices."""


def read(trace, run):
    if run["steps"] == 0:
        return None
    return trace.mean("compute_ns") * 1e-6 / run["steps"]
