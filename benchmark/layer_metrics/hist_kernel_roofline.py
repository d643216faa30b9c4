"""The histogram kernel's share of its roofline: the least time the chip
could take for its calls over the time they took on the fullest device.
A call's least time is the larger of its operations over peak FLOP/s and
its bytes over peak bytes/s, from `work/gbt.py` and `peaks.json` alone.
Memory-bound: with int32 bins it can reach about a quarter at most."""

from benchmark import trace_reduce
from benchmark.layer_metrics import step_mfu


def read(context):
    dev = context["trace"].device(context["fullest_device"])
    hist, _ = trace_reduce.tree_build_kernels(dev)
    took = sum(e.seconds for e in hist)
    if not took:
        return None
    work = context["work"].kernel_call_work(context["config"],
                                            context["chips"])
    least = step_mfu.least_seconds(work, context["peak"], 1)
    return 100.0 * len(hist) * least / took
