"""Executions of compiled programs on the fullest device in the window
(events of its `XLA Modules` line) for each step the window completed."""


def read(context):
    dev = context["trace"].device(context["fullest_device"])
    if not dev.modules:
        return None
    return len(dev.modules) / context["steps"]
