"""An SE variable-selection job's ablation in a job call
(`shifu:train.ablate`, opened by `processor/sensitivity.sensitivity_select`
after the quick train's `shifu:train.job` has returned, beside it: the
rows' placement on the mesh, the dispatch of `shifu_ablate`, the wait on
the device and the one fetch of every column's delta), mean milliseconds
a call. None where no call holds a `shifu:train.job`; 0 where no such
span is in the window."""

from benchmark import program_spans


def read(context):
    return program_spans.phase_ms(context["trace"], "shifu:train.ablate")
