"""Programs that went to the compiler between the window's start and end
(misses of the persistent cache, counted by the harness's own
`jax.monitoring` listener). Expected 0."""


def read(context):
    return context["window_compiles"]
