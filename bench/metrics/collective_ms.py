"""collective_ms: device milliseconds per step covered by collective
operations (async pairs from start to done), averaged over the cell's
devices.  Nothing to read where the trace holds no collective."""


def read(trace, run):
    coll = trace.mean("collective_ns")
    if run["steps"] == 0 or coll == 0:
        return None
    return coll * 1e-6 / run["steps"]
