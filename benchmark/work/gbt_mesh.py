"""The `gbt_mesh` family's least work is the `gbt` family's: a tree over
the configuration's rows, whatever number of chips holds them (the
harness divides a step's least time by the chips; `kernel_call_work` takes
the rows one chip holds). The all-reduce of a level's histograms is the
mesh's own cost and no part of the algorithm's least work."""

from benchmark.work.gbt import (ROW_STATE_BYTES, kernel_call_work,  # noqa: F401
                                pass_work, step_work)
