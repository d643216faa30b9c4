"""Share of the fullest device's busy time spent in the split-search
kernel (`ops/pallas_split.py`). Of the Pallas kernels a tree build runs,
it is the one that is not a histogram kernel; until the kernels carry
names of their own that is how the trace tells them apart."""

from benchmark import trace_reduce


def read(context):
    dev = context["trace"].device(context["fullest_device"])
    _, split = trace_reduce.tree_build_kernels(dev)
    if not split or not dev.busy_s:
        return None
    return 100.0 * sum(e.seconds for e in split) / dev.busy_s
