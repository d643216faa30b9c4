"""Host side of a job call: the call's wall (the harness's `bench:call`
span) less the time the fullest device was busy inside it, mean over the
window's calls. Bagging weights, placement, result fetches."""

from benchmark import trace_reduce


def read(context):
    trace = context["trace"]
    dev = trace.device(context["fullest_device"])
    calls = [s for s in trace.spans if s.name == "bench:call"]
    if not calls:
        return None
    host = [s.seconds - trace_reduce.overlap_s(dev.busy, s.start, s.end)
            for s in calls]
    return 1e3 * sum(host) / len(host)
