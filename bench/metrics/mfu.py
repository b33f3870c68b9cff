"""mfu: model FLOPs of the traced window's steps (bench/flops.py) over the
window's length times the cell's chips times the chip's bf16 peak, in %."""


def read(trace, run):
    if run["steps"] == 0:
        return None
    window_s = trace.window_ns * 1e-9
    return 100.0 * run["flops_per_step"] * run["steps"] / (window_s * run["chips"] * run["peak_flops"])
