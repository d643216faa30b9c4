"""The embedding path's share of its roofline: the least time the chip
could take for the lookups, their gradient's accumulation and the tables'
update of the window's steps (`work/wdl.py::table_step_work` and
`peaks.json` alone: the bytes of the distinct rows a batch touches over
the bandwidth) over the time its events took on the fullest device
(`embed_ops_share.table_events`). A dense pass over the tables reads low
here: the work counts only the rows a batch has to touch."""

from benchmark.layer_metrics import embed_ops_share, step_mfu


def read(context):
    took = sum(e.self_s for e in embed_ops_share.table_events(context))
    if not took:
        return None
    work = context["work"].table_step_work(context["config"])
    least = step_mfu.least_seconds(work, context["peak"], 1)
    return 100.0 * context["steps"] * least / took
