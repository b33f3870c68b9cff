"""idle_share: share of the traced window in which no operation ran on the
device, averaged over the cell's devices, in %."""


def read(trace, run):
    return 100.0 * (1.0 - trace.mean("busy_ns") / trace.window_ns)
