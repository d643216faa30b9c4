"""How far the chips of a mesh run out of step: the busiest device's busy
time less the idlest's, as a share of the traced window. Every collective
waits for the slowest chip, so a skew is time the others spend waiting.
None on one device."""


def read(context):
    trace = context["trace"]
    busy = [d.busy_s for d in trace.devices]
    if len(busy) < 2:
        return None
    return 100.0 * (max(busy) - min(busy)) / trace.window_s
