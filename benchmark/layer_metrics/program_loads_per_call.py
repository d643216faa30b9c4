"""Programs traced and lowered anew inside the window and then read back
from the persistent cache, for each job call: jax's own cache-hit events,
counted by the harness's listener. A trainer that builds fresh closures for
`jax.jit`'s static arguments on every call pays one each time."""


def read(context):
    return context["window_program_loads"] / len(context["call_spans"])
