"""What the program's spans do not name in a job call: the call's wall less
`shifu:train.wait` and the four host phases (`program_spans.unnamed_ms`),
mean milliseconds a call. A rising value says the spans have rotted."""

from benchmark import program_spans


def read(context):
    return program_spans.unnamed_ms(context["trace"])
