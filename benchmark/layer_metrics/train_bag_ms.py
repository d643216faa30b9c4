"""A random forest's draw in a job call (`shifu:train.bag`, opened by
`models/gbdt.build_rf` beside the phases `program_spans.py` lists, once a
lockstep group: the dispatch of the group's instance weights and feature
masks, drawn on the device, or the upload of host-drawn ones), mean
milliseconds a call. None where no call holds a `shifu:train.job`; 0 where
every such span is under the reducer's millisecond. The draw's device time
is the scope `bag` (`tools/trace_scopes.py`)."""

from benchmark import program_spans


def read(context):
    return program_spans.phase_ms(context["trace"], "shifu:train.bag")
