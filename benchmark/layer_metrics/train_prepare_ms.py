"""Host work of a job call before anything is placed (`shifu:train.prepare`:
validation split, casts, bagging weights with their label fetch, eager
parameter init, masks, optimizer), mean milliseconds a call."""

from benchmark import program_spans


def read(context):
    return program_spans.phase_ms(context["trace"], "shifu:train.prepare")
