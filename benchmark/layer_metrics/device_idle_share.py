"""Share of the traced window in which no operation ran on the fullest
device: 1 - (union of its `XLA Ops` intervals / window)."""


def read(context):
    trace = context["trace"]
    dev = trace.device(context["fullest_device"])
    return 100.0 * (1.0 - dev.busy_s / trace.window_s)
