"""Share of the fullest device's busy time spent in the histogram kernel
(`ops/pallas_hist.py`): summed duration of its events over busy time."""

from benchmark import trace_reduce


def read(context):
    dev = context["trace"].device(context["fullest_device"])
    hist, _ = trace_reduce.tree_build_kernels(dev)
    if not hist or not dev.busy_s:
        return None
    return 100.0 * sum(e.seconds for e in hist) / dev.busy_s
