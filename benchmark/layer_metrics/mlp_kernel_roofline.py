"""The fused MLP kernel's share of its roofline: the least time the chip
could take for its calls over the time they took on the fullest device.
A call is one full-batch epoch's loss and gradient, so its least time is
the step's (`work/mlp.py::step_work` and `peaks.json` alone): the larger
of the epoch's operations over peak FLOP/s and the training matrix's
bytes, read once, over peak bytes/s. Memory-bound for a narrow net."""

from benchmark.layer_metrics import mlp_kernel_share, step_mfu


def read(context):
    _, events = mlp_kernel_share.kernel_events(context)
    took = sum(e.seconds for e in events)
    if not took:
        return None
    work = context["work"].step_work(context["config"])
    least = step_mfu.least_seconds(work, context["peak"], 1)
    return 100.0 * len(events) * least / took
