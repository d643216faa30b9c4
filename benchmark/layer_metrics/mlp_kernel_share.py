"""Share of the fullest device's busy time spent in the narrow MLP's fused
loss-and-gradient kernel (`ops/pallas_mlp.py`, one call an epoch): summed
duration of the Pallas events whose name holds the kernel's over busy
time. A program without the kernel has no such event: nothing is read."""

from benchmark import trace_reduce

KERNEL = "shifu_mlp_loss_grad"


def kernel_events(context):
    """(the fullest device, its executions of the kernel)."""
    dev = context["trace"].device(context["fullest_device"])
    return dev, [e for e in trace_reduce.pallas_events(dev)
                 if KERNEL in e.name]


def read(context):
    dev, events = kernel_events(context)
    if not events or not dev.busy_s:
        return None
    return 100.0 * sum(e.seconds for e in events) / dev.busy_s
