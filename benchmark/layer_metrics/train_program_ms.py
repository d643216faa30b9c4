"""The call into the jitted training program until it returns to Python
(`shifu:train.program`: trace, lower, cache read or compile, dispatch), mean
milliseconds a call."""

from benchmark import program_spans


def read(context):
    return program_spans.phase_ms(context["trace"], "shifu:train.program")
