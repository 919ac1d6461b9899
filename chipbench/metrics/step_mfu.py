"""The engine steps' share of the chip's peak bf16 FLOP/s: the model FLOPs
of the calls dispatched in the traced window (as ``serve_mfu`` counts
them) over the device time those calls took, the union of the device's
operation intervals in the window.  Where the offered load fixes the
FLOPs of a window, this share rises only when the device does the same
steps in less time, and it bounds the rooflines of the kernels in them."""
from metrics.serve_mfu import flops


def read(run):
    if (not run.trace or not run.trace.get("busy_s") or not run.trace_host
            or run.peak is None):
        return None
    return flops(run) / (run.trace["busy_s"] * run.peak["bf16_flops"]) * 100
