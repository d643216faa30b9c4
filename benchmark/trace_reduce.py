"""From a profiler trace (`.xplane.pb`) to the few things metrics read.

Only `jax.profiler.ProfileData` is used. A TPU trace has one plane per
chip, `/device:TPU:<id>`, whose line `XLA Ops` holds one event per executed
HLO instruction, named by the instruction's whole text (`%fusion.243 =
s32[...] fusion(...)`; a `while` holds the events of its body, so sums are
of self time) and whose line `XLA Modules` holds one event per execution of
a compiled program; the host plane's line of the thread that drives the
window holds the harness's own spans (`jax.profiler.TraceAnnotation`, names
starting `bench:`) and jax's own host events, nested, on the same clock.
All times here are seconds from the window's start.
"""

import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"
HOST_EVENT_MIN_NS = 1_000_000       # shorter host events label no gap


@dataclass
class Event:
    name: str
    start: float
    end: float
    self_s: float = 0.0
    detail: str = ""

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclass
class Device:
    index: int
    ops: list = field(default_factory=list)
    modules: list = field(default_factory=list)
    busy: list = field(default_factory=list)     # merged (start, end)

    @property
    def busy_s(self) -> float:
        return sum(e - s for s, e in self.busy)


@dataclass
class Reduced:
    window_s: float
    devices: list
    spans: list                                   # harness spans, Events
    host: list = field(default_factory=list)      # their thread's events

    def device(self, index: int) -> Device:
        for d in self.devices:
            if d.index == index:
                return d
        raise KeyError(f"no plane of device {index} in the trace")


def merge(intervals):
    """Union of (start, end) pairs as sorted, disjoint pairs."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def overlap_s(merged, lo: float, hi: float) -> float:
    return sum(e - s for s, e in clip(merged, lo, hi))


def set_self_times(events):
    """Self time of events that nest on one line: an event's seconds less
    those of the events directly inside it."""
    stack = []
    for ev in sorted(events, key=lambda e: (e.start, -e.end)):
        ev.self_s = ev.seconds
        while stack and stack[-1].end <= ev.start:
            stack.pop()
        if stack and ev.end <= stack[-1].end:
            stack[-1].self_s -= ev.seconds
        stack.append(ev)
    return events


def op_sums(events):
    """{name: summed self seconds}, most first."""
    sums = {}
    for ev in events:
        sums[ev.name] = sums.get(ev.name, 0.0) + ev.self_s
    return dict(sorted(sums.items(), key=lambda kv: -kv[1]))


def gaps(busy, window_s: float):
    """The idle stretches of a window, given its merged busy intervals."""
    out, at = [], 0.0
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if window_s > at:
        out.append((at, window_s))
    return out


def innermost_segments(events):
    """[(start, end, label)] over the time some event of one thread is
    open; the label is the innermost harness span open then, followed by
    the innermost other event inside it: `call>lower_sharding_computation`."""
    edges = sorted({t for e in events for t in (e.start, e.end)})
    order = sorted(events, key=lambda e: (e.start, -e.end))
    out, stack, nxt = [], [], 0
    for lo, hi in zip(edges[:-1], edges[1:]):
        while nxt < len(order) and order[nxt].start <= lo:
            stack.append(order[nxt])
            nxt += 1
        stack = [e for e in stack if e.end > lo]
        if not stack:
            continue
        spans = [e for e in stack if e.name.startswith(SPAN_PREFIX)
                 and e.name != WINDOW_SPAN]
        label = spans[-1].name[len(SPAN_PREFIX):] if spans else "outside"
        if not stack[-1].name.startswith(SPAN_PREFIX):
            label += ">" + stack[-1].name
        out.append((lo, hi, label))
    return out


def label_gaps(idle, events, top: int = 10):
    """Idle seconds by what the host was doing (innermost_segments of the
    driving thread's events), most first: [[label, seconds], ...]."""
    sums, segments, at = {}, innermost_segments(events), 0
    for lo, hi in idle:
        while at < len(segments) and segments[at][1] <= lo:
            at += 1
        k, covered = at, 0.0
        while k < len(segments) and segments[k][0] < hi:
            a, b, label = segments[k]
            part = min(b, hi) - max(a, lo)
            sums[label] = sums.get(label, 0.0) + part
            covered += part
            k += 1
        if hi - lo > covered:
            sums["outside"] = sums.get("outside", 0.0) + (hi - lo - covered)
    return [[k, v] for k, v in sorted(sums.items(), key=lambda kv: -kv[1])][:top]


def short_name(hlo_text: str) -> str:
    """`%fusion.243 = s32[...] fusion(...)` -> `fusion.243`."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def reduce_planes(planes):
    """`planes`: [(plane name, [(line name, [(name, start_ns, dur_ns,
    detail)])])] -> Reduced. The window is the `bench:window` span."""
    host_ns, window = [], None
    for pname, lines in planes:
        if DEVICE_PLANE.match(pname):
            continue
        for _, events in lines:
            if not any(n == WINDOW_SPAN for n, _, _, _ in events):
                continue
            host_ns = [(n, s, s + d) for n, s, d, _ in events]
            window = next((s, e) for n, s, e in host_ns if n == WINDOW_SPAN)
    if window is None:
        raise ValueError("the trace holds no bench:window span")
    t0, window_s = window[0], (window[1] - window[0]) / 1e9
    sec = lambda ns: (ns - t0) / 1e9  # noqa: E731
    host = [Event(n, max(sec(s), 0.0), min(sec(e), window_s))
            for n, s, e in host_ns if sec(e) > 0 and sec(s) < window_s]
    spans = [e for e in host if e.name.startswith(SPAN_PREFIX)]
    devices = []
    for pname, lines in planes:
        m = DEVICE_PLANE.match(pname)
        if not m:
            continue
        dev = Device(int(m.group(1)))
        for lname, events in lines:
            evs = [Event(short_name(n), max(sec(s), 0.0),
                         min(sec(s + d), window_s), detail=detail)
                   for n, s, d, detail in events
                   if sec(s + d) > 0 and sec(s) < window_s]
            if lname == OPS_LINE:
                dev.ops = set_self_times(evs)
            elif lname == MODULES_LINE:
                dev.modules = evs
        dev.busy = merge((e.start, e.end) for e in dev.ops)
        devices.append(dev)
    if not devices:
        raise ValueError("the trace holds no device plane")
    return Reduced(window_s, sorted(devices, key=lambda d: d.index), spans,
                   host)


def read_planes(path: str):
    """The planes of an .xplane.pb as plain tuples (see reduce_planes);
    host lines keep the harness's spans and events of a millisecond or
    more."""
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        on_device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if on_device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [(e.name, int(e.start_ns), int(e.duration_ns),
                       e.name if on_device else "")
                      for e in line.events
                      if on_device or e.name.startswith(SPAN_PREFIX)
                      or e.duration_ns >= HOST_EVENT_MIN_NS]
            if events:
                lines.append((line.name, events))
        if lines:
            planes.append((plane.name, lines))
    return planes


def reduce_file(path: str) -> Reduced:
    return reduce_planes(read_planes(path))


PALLAS_CALL = "tpu_custom_call"
HIST_KERNEL = "_level_histograms"


def pallas_events(dev: Device):
    """The device's Pallas kernel executions: events whose HLO text is a
    `tpu_custom_call`. No `pallas_call` of the
    program passes `name=` today, so XLA names each after the jit it sits
    in (`_level_histograms_pallas.37`, `closed_call.72`, `build_tree.11`)."""
    return [e for e in dev.ops if PALLAS_CALL in e.detail]


def is_hist_kernel(event: Event) -> bool:
    """The histogram kernels are the ones jitted as
    `_level_histograms_pallas` / `_level_histograms_fused`."""
    return HIST_KERNEL in event.name


def tree_build_kernels(dev: Device):
    """(histogram kernel events, the other Pallas events): of the kernels
    a tree build runs, the other one is the split search."""
    kernels = pallas_events(dev)
    hist = [e for e in kernels if is_hist_kernel(e)]
    return hist, [e for e in kernels if not is_hist_kernel(e)]
