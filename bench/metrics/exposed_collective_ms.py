"""exposed_collective_ms: device milliseconds per step in which a collective
runs and no compute operation does, averaged over the cell's devices.
Nothing to read where the trace holds no collective."""


def read(trace, run):
    if run["steps"] == 0 or trace.mean("collective_ns") == 0:
        return None
    return trace.mean("exposed_collective_ns") * 1e-6 / run["steps"]
