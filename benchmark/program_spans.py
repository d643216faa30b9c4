"""The program's own spans in a traced window, per job call.

Since PR 24 every `shifu_tpu.obs.trace.span("family.stage")` is a
`jax.profiler.TraceAnnotation` named `shifu:family.stage`, so a `--trace 1`
run holds them on the driving thread's line of the host plane, on the
profiler's clock, nested inside the harness's `bench:call` spans. They reach
the readers unchanged in `context["trace"].host` (`trace_reduce.read_planes`
keeps every host event of a millisecond or more; a shorter span is not seen
and its time reads as unnamed).

A training entry (`train_nn`, `build_gbt`, ...) opens one `shifu:train.job`
a call, and inside it, side by side, the phases:

| span | metric (`layer_metrics/<name>.py`), mean ms a call |
| --- | --- |
| `shifu:train.prepare` | `train_prepare_ms`: validation split, casts, bagging weights with their label fetch, eager parameter init, masks, optimizer |
| `shifu:train.place` | `train_place_ms`: uploads and the fresh carry |
| `shifu:train.program` | `train_program_ms`: the call into the jitted program until it returns: trace, lower, cache read or compile, dispatch |
| `shifu:train.fetch` | `train_fetch_ms`: device-to-host copies after the wait, result assembly |
| `shifu:train.wait` | none: the host blocked on the device; device time, not host work |
| (none) | `host_unnamed_ms`: the call's wall less all five, that is the self time of `bench:call` and of `shifu:train.job` among these spans: what no span names. A rising value says the instrumentation has rotted |

All five readers return None where no call holds a `shifu:train.job` (a
checkout from before the spans, the CPU rehearsal's empty line): the program
has nothing to read there, and the result line leaves the metric out.
"""

from benchmark import trace_reduce

CALL_SPAN = "bench:call"
JOB_SPAN = "shifu:train.job"
PHASE_PREFIX = "shifu:train."


def call_spans(trace):
    return [s for s in trace.spans if s.name == CALL_SPAN]


def spans_by_call(trace):
    """[(call, [the `shifu:train.*` spans inside it])], each span a copy
    clipped to its call with its self time set: its seconds less those of
    the spans directly inside it. None where no call holds a job span."""
    out, found = [], False
    for call in call_spans(trace):
        inside = [trace_reduce.Event(e.name, max(e.start, call.start),
                                     min(e.end, call.end))
                  for e in trace.host
                  if e.name.startswith(PHASE_PREFIX)
                  and e.end > call.start and e.start < call.end]
        found = found or any(e.name == JOB_SPAN for e in inside)
        whole = trace_reduce.Event(call.name, call.start, call.end)
        trace_reduce.set_self_times([whole] + inside)
        out.append((whole, inside))
    return out if found else None


def phase_ms(trace, name: str):
    """Milliseconds inside spans called `name`, mean over the calls."""
    calls = spans_by_call(trace)
    if calls is None:
        return None
    return 1e3 * sum(e.seconds for _, inside in calls for e in inside
                     if e.name == name) / len(calls)


def unnamed_ms(trace):
    """Milliseconds of a call that no phase span covers, mean over the
    calls: the self time of the call and of the job span inside it."""
    calls = spans_by_call(trace)
    if calls is None:
        return None
    return 1e3 * sum(call.self_s + sum(e.self_s for e in inside
                                       if e.name == JOB_SPAN)
                     for call, inside in calls) / len(calls)
