"""Uploads of a job call and its fresh training carry (`shifu:train.place`),
mean milliseconds a call."""

from benchmark import program_spans


def read(context):
    return program_spans.phase_ms(context["trace"], "shifu:train.place")
