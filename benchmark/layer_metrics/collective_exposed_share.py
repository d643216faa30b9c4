"""Share of the traced window in which, on one device, only collective
operations run: the chip waits for the other chips or for the wire and
computes nothing. The device is the fullest one, unless the profiler
names more of another's executions (below).

A collective is a device event whose HLO opcode is `all-reduce`,
`all-gather`, `reduce-scatter`, `all-to-all` or `collective-permute` (the
instruction's NAME is jax's, `%psum.81`; the opcode is read from the event's
whole text), or a fusion XLA names after one. An asynchronous collective
is two events, `<opcode>-start` and `<opcode>-done`: it counts from the
start event's start to the done event's end, the done found by the start's
name among its operands. From the union of those intervals comes off what
any other operation covers; `while`, `conditional` and `call` hold the
events of their bodies and cover nothing themselves.

On the four-chip host the profiler names the events of most executions of
a program on ONE chip `region.<n>`, with no instruction text (my chip run,
PR 30: 18 to 20 of a window's 22 calls on chip 0, none on chips 1-3), so
an opcode can be read only in the executions it names in full. The device
read is the one with the largest share of its busy time inside such
executions (the fullest device where they tie: every chip of a data mesh
runs the same program on as many rows). Where even that device has
unnamed executions, the exposed time is summed over the named ones and
scaled by the device's busy seconds over theirs, which ASSUMES that every
execution in the window runs the same program; the cells of this metric
(a closed loop of equal jobs) do.

None where no execution is named in full or the named ones ran no
collective: a program on one chip, a checkout whose tree build reduces
nothing.
"""

import re

from benchmark import trace_reduce

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
CONTAINERS = ("while", "conditional", "call")
_OPCODE = re.compile(r" = .*?(?<![\w.\-%])([a-z][a-z\-]*)\(")


def opcode(hlo_text: str) -> str:
    """`%psum.81 = f32[2,1,28,64]{3,2,1,0:T(8,128)S(1)} all-reduce(%x), ...`
    -> `all-reduce`; "" where the text is no instruction."""
    m = _OPCODE.search(hlo_text)
    return m.group(1) if m else ""


def collective_of(event) -> str:
    """The collective an event belongs to, with its `-start` / `-done`
    half where it has one; "" for any other event."""
    op = opcode(event.detail)
    for kind in COLLECTIVES:
        if op in (kind, kind + "-start", kind + "-done"):
            return op
        if op == "fusion" and event.name.startswith(kind):
            return kind
    return ""


def collective_intervals(dev):
    """Merged (start, end) of the device's collectives."""
    found, starts = [], {}
    for ev in sorted(dev.ops, key=lambda e: e.start):
        kind = collective_of(ev)
        if kind.endswith("-start"):
            starts[ev.name] = ev
        elif kind.endswith("-done"):
            operand = re.search(kind + r"\(%?([\w.\-]+)", ev.detail)
            begun = starts.pop(operand.group(1), None) if operand else None
            found.append(((begun or ev).start, ev.end))
        elif kind:
            found.append((ev.start, ev.end))
    # a start whose done fell outside the window runs to its own end
    found += [(ev.start, ev.end) for ev in starts.values()]
    return trace_reduce.merge(found)


def other_intervals(dev):
    """Merged (start, end) of every operation that is no collective and
    holds no other's events."""
    return trace_reduce.merge(
        (ev.start, ev.end) for ev in dev.ops
        if not collective_of(ev) and opcode(ev.detail) not in CONTAINERS)


def named_in_full(dev):
    """(busy seconds inside the module executions whose ops carry their
    instruction text, the ops inside those executions). The executions
    the profiler does not name are missing from the device's `XLA
    Modules` line too, so the whole is the device's busy time."""
    busy, ops = 0.0, []
    for run in dev.modules:
        inside = [ev for ev in dev.ops
                  if ev.start >= run.start and ev.end <= run.end]
        if inside and all(" = " in ev.detail for ev in inside):
            busy += trace_reduce.overlap_s(dev.busy, run.start, run.end)
            ops += inside
    return busy, ops


def exposed_seconds(ops) -> float:
    held = trace_reduce.Device(0, ops=ops)
    others = other_intervals(held)
    return sum((e - s) - trace_reduce.overlap_s(others, s, e)
               for s, e in collective_intervals(held))


def read(context):
    trace = context["trace"]
    fullest = trace.device(context["fullest_device"])
    # max keeps the first of equals: the fullest device
    dev, named_busy, ops = max(
        ((d, *named_in_full(d)) for d in [fullest] + [
            d for d in trace.devices if d is not fullest]),
        key=lambda found: found[1] / found[0].busy_s if found[1] else 0.0)
    if not named_busy or not collective_intervals(
            trace_reduce.Device(0, ops=ops)):
        return None
    return 100.0 * exposed_seconds(ops) * (dev.busy_s / named_busy) \
        / trace.window_s
