"""Share of the fullest device's busy time spent in the ablation of an SE
variable-selection job (`processor/sensitivity.shifu_ablate`, one
execution a job: the first layer's sums, every column wiped out of them
and every row scored again): the busy time inside its executions (events
of the device's `XLA Modules` line whose name holds `shifu_ablate`) over
all busy time. None where the window holds no such execution."""

from benchmark import trace_reduce

PROGRAM = "shifu_ablate"


def executions(context):
    """(the fullest device, its executions of the ablation program)."""
    dev = context["trace"].device(context["fullest_device"])
    return dev, [e for e in dev.modules if PROGRAM in e.name]


def read(context):
    dev, runs = executions(context)
    if not runs or not dev.busy_s:
        return None
    inside = sum(trace_reduce.overlap_s(dev.busy, e.start, e.end)
                 for e in runs)
    return 100.0 * inside / dev.busy_s
