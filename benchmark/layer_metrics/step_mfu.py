"""The whole step's share of the chip's peak: the least time the chips
could take for one step over the time a step took (window wall / steps).
Least time is the larger of operations / peak FLOP/s and bytes / peak
bytes/s, both from the family's work function and peaks.json alone; for a
configuration that memory bounds it is a share of the bandwidth."""


def least_seconds(work, peak, chips: int) -> float:
    return max(work["flops"] / (peak["flops_per_s"] * chips),
               work["bytes"] / (peak["hbm_bytes_per_s"] * chips))


def read(context):
    work = context["work"].step_work(context["config"])
    least = least_seconds(work, context["peak"], context["chips"])
    return 100.0 * least / (context["wall_s"] / context["steps"])
