"""The ablation program's share of its roofline: the least time the chip
could take for its executions over the time they took on the fullest
device. An execution ablates every column over every row, so its least
time is `work/varsel.py::ablate_work`'s with `peaks.json` alone: the
larger of its operations over peak FLOP/s and x's bytes, read once, over
peak bytes/s. The operations run on the vector unit, whose peak
`peaks.json` does not hold, so the share is of the bandwidth's bound.
None where the window holds no execution or the work module reckons no
ablation."""

from benchmark.layer_metrics import ablate_share, step_mfu


def read(context):
    work = getattr(context["work"], "ablate_work", None)
    _, runs = ablate_share.executions(context)
    took = sum(e.seconds for e in runs)
    if work is None or not took:
        return None
    least = step_mfu.least_seconds(work(context["config"]), context["peak"],
                                   1)
    return 100.0 * len(runs) * least / took
