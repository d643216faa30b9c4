"""Device-to-host copies after the wait and the assembly of a job call's result
(`shifu:train.fetch`), mean milliseconds a call."""

from benchmark import program_spans


def read(context):
    return program_spans.phase_ms(context["trace"], "shifu:train.fetch")
