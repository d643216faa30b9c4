"""Program spans: one span, two sinks.

`span("family.stage", **attrs)` is a context manager around a piece of
host work. It is always a `jax.profiler.TraceAnnotation` named
`shifu:family.stage`: whenever a profiler session is open (`shifu
--profile`, `benchmark/run.py --trace 1`, an operator's
`jax.profiler.start_trace`) the span lands in that session's
`.xplane.pb`, on the calling thread's line of the host plane, on the
profiler's own clock beside the device planes, its attrs as stats of
the event. With no session open the annotation costs about a
microsecond and records nothing. Neither importing this module nor
entering a span initialises a jax backend.

With `SHIFU_TPU_TRACE=1` the same enter/exit also records the span
(wall start, duration, thread, parentage via a thread-local stack)
into a per-process bounded ring buffer; with the knob unset the ring,
its lock and the clock are never touched and no file is written.
`record_span` backfills a span from timestamps a layer already
measured (the scheduler's `ready_t`/`start_t`, the serving plane's
batch splits, the `input.*` stage timers); a profiler session cannot
take an event after the fact, so those reach the ring buffer only.

A `train.job` span is the one span that always leaves something
behind: when it closes, one job record (`job_records()`): its attrs, its
start since the process started, its seconds, and what jax built
inside it. `profiling`'s listeners hear jax's own build events
(`jax.monitoring`: a function traced, a jaxpr lowered, an executable
read back from the persistent cache or compiled) on the thread that
builds, and a context variable names the job open there; an event with
no job open is booked to `outside_builds()`. The process's first record
is kept for good, the newest 64 in a deque. With `SHIFU_TPU_TRACE=1`
each build stage is also a ring-buffer span `train.build` under the
span open on that thread (attrs `stage`, `fun`).

Per step, `trace_run` (entered by `cli.main` around every command):

- generates the run_id that also names the `maybe_profile` device
  trace (`tmp/profile/<run_id>/`), which holds the step's `span()`s
  itself; `shifu trace ls` pairs it with the ring buffer's export,
  the only place the `record_span` families show;
- exports this process's spans to `<trace_dir>/spans.<pid>.jsonl` via
  `resilience.atomic_write` (first line is a clock record carrying the
  host's offset to the coordinator clock);
- on the coordinator (the process that *created* the trace dir — it
  publishes `SHIFU_TPU_TRACE_DIR` so DAG subprocess nodes and remote
  hosts land their span files in the same workspace), merges every
  `spans.*.jsonl` into one Chrome-trace-event JSON at
  `tmp/trace/<run_id>.trace.json`, ordering events by offset-corrected
  clocks — open it in ui.perfetto.dev;
- attaches the `trace` summary block (`profiling.TRACE_FIELDS`) to the
  step's steps.jsonl record.

Export runs through `fault_point("obs.export")` and is wrapped so a
trace-plane failure can never fail the step it was watching.

Span names are *registered*: every literal must be a `family.stage`
from SPAN_FAMILIES below, and every registry entry must be referenced
somewhere — the `unregistered-span` lint rule enforces both ways, so
the vocabulary in traces stays enumerable (dashboards and the watchdog
can switch on it).
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import functools
import glob
import json
import logging
import os
import re
import threading
import time
import zlib
from typing import Dict, List, Optional, Tuple

from shifu_tpu import profiling
from shifu_tpu.analysis.lockcheck import make_lock
from shifu_tpu.config.environment import knob_bool, knob_int, knob_str

log = logging.getLogger(__name__)

# the span-name vocabulary: family → stages. The `unregistered-span`
# lint rule holds call sites and this table together both ways (an
# unknown "family.stage" literal is a finding; so is a registered
# stage no scanned file ever emits).
SPAN_FAMILIES: Dict[str, Tuple[str, ...]] = {
    # the per-command root span trace_run opens
    "run": ("step",),
    # DAG scheduler: one node span per scheduled node (parent = run),
    # with queue (ready→dispatch) and run (dispatch→done) children
    "dag": ("node", "queue", "run"),
    # input pipeline stage timers, re-emitted as spans of the step
    "input": ("host_parse", "host_assemble", "h2d"),
    # serving plane: one request span with the submit_timed splits as
    # children, plus one flush span per formed batch
    "serve": ("request", "queue", "pad", "h2d", "device", "d2h",
              "flush"),
    # model fleet: one warm span per (re-)warm of a registry model
    # into residency, one evict span per LRU eviction back to host,
    # one swap span per in-place param hot-swap into resident
    # executables (the refresh loop's zero-recompile promotion)
    "fleet": ("warm", "evict", "swap"),
    # watched collectives (barrier/allgather/init distinguished by the
    # `tag` attr so watchdog dumps can cite the open span)
    "dist": ("collective",),
    # async checkpoint writer seams
    "ckpt": ("stage", "publish"),
    # the health plane's monitor loop: one window span per ingested
    # drift window, one evaluate span per SLO pass
    "watch": ("window", "evaluate"),
    # drift-triggered refresh: one run span per breach-scheduled
    # retrain→guardrail→promote cycle, one guardrail span per
    # challenger-vs-incumbent eval decision, one rollback span per
    # registry rollback + live re-swap
    "refresh": ("run", "guardrail", "rollback"),
    # live promotion: one run span per staged shadow→canary→promoted
    # cycle, one decide span per live-arm comparison, one rollback
    # span per canary breach (registry rollback + arm teardown)
    "canary": ("run", "decide", "rollback"),
    # shadow plane: one score span per mirrored request the side
    # thread replays against the challenger arm (discarded response)
    "shadow": ("score",),
    # the trainers' host side, one job span per call of a public
    # training entry (train_nn, the WDL/MTL resident trainers,
    # build_gbt, build_gbt_bagged, build_rf) with its phases nested
    # inside on the calling thread: prepare (host work before anything
    # is placed), shuffle (mini-batch mode only: the rows put in the
    # job seed's order, padded and cut into batches, on the host for
    # host inputs and on the device for device inputs), place (uploads
    # and the fresh carry), bag (build_rf only: the dispatch of a
    # lockstep group's instance weights and feature masks, drawn on
    # the device, or the upload of host-drawn ones), program (the
    # call into the jitted program until it returns to Python: trace,
    # lower, cache read or compile, dispatch), wait (the first
    # blocking read of its results: the host waiting on the device),
    # fetch (the remaining device→host copies and result assembly);
    # build (ring buffer only, backfilled from jax's own events: one
    # stage of building a program, `stage` = trace|lower|load|compile
    # of function `fun`, under the span open on the building thread)
    "train": ("job", "prepare", "shuffle", "place", "bag", "program",
              "wait", "fetch", "build"),
    # the one sanctioned device→host sync, data/pipeline.host_fetch
    "host": ("sync",),
}

# every span's name in a profiler trace starts with this
ANNOTATION_PREFIX = "shifu:"

# the `jax.named_scope`s inside the device programs (metadata only, no
# run-time cost): a compiled op's `op_name` is its path of scopes, and
# a profiler trace keeps it with every device event. The trainers'
# epoch step (`train_bags_carry`): forward_loss (the value_and_grad of
# the loss; jax marks its backward ops `transpose(jvp(..))` itself),
# update (optimizer update and apply), validate (the validation
# metric), select (best-epoch and early-stop bookkeeping), and inside
# them one `layer<i>` a layer of `models/nn.forward`. Wide-and-deep
# (`models/wdl.forward`, inside forward_loss and validate): embed (the
# embedding lookup, and in the backward pass its gradient's
# accumulation into the table), wide (the wide table's lookup and the
# dense linear term), deep (the MLP over [dense ‖ embeddings]); inside
# update, table_update (the optimizer's pass over the two tables). A
# boosting round
# (`models/gbdt.py`): gradients, hist (level histograms and sibling
# subtraction), split (best splits and their fold into the tree),
# route (rows to their child nodes), leaf (final leaf values, the
# per-row leaf gather and the prediction update); inside hist, on a
# data mesh only, allreduce (the level's one psum of the chips' local
# histograms). A random forest's draw (`models/rf_draw.py`: `bags`, `masks`):
# bag (a group's Poisson instance weights and feature subsets).
DEVICE_SCOPES = ("forward_loss", "update", "validate", "select",
                 "embed", "wide", "deep", "table_update",
                 "gradients", "hist", "split", "route", "leaf",
                 "allreduce", "bag")
_LAYER_SCOPE = re.compile(r"layer\d+")
_WORD = re.compile(r"[A-Za-z_]\w*")


def device_scopes(op_name: str) -> Tuple[str, ...]:
    """The program scopes of an HLO `op_name`, outermost first:
    `jit(train_bags_carry)/vmap()/while/body/forward_loss/
    transpose(jvp(layer1))/dot_general` → `("forward_loss", "layer1")`;
    `()` where none is registered. The last component is the primitive;
    jitted helpers are skipped, and what jax or XLA wraps around a scope
    is looked through (`vmap(route)`, `reshape;split`)."""
    found = []
    for part in op_name.split("/")[:-1]:
        if part.startswith(("jit(", "pjit(")):
            continue
        found += [w for w in _WORD.findall(part)
                  if w in DEVICE_SCOPES or _LAYER_SCOPE.fullmatch(w)]
    return tuple(found)


def span_registered(name: str) -> bool:
    """True when `name` is a declared `family.stage` (the lint rule's
    membership test)."""
    family, _, stage = name.partition(".")
    return stage in SPAN_FAMILIES.get(family, ())


# wall = monotonic + offset, computed once so retro spans recorded from
# monotonic timestamps land on the same clock as live spans
_MONO_OFFSET = time.time() - time.monotonic()


def wall(t_mono: float) -> float:
    """Convert a `time.monotonic()` timestamp to wall-clock seconds."""
    return t_mono + _MONO_OFFSET


_tls = threading.local()


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


class Tracer:
    """Per-process bounded span ring buffer. Thread-safe; overflow
    drops the OLDEST span (ring semantics) and counts the drop."""

    def __init__(self, run_id: str, trace_dir: str, coordinator: bool,
                 cap: int, clock_offset_s: float = 0.0):
        self.run_id = run_id
        self.trace_dir = trace_dir
        self.coordinator = coordinator
        self.clock_offset_s = float(clock_offset_s)
        self.root_id: Optional[str] = None
        self._cap = max(int(cap), 1)
        self._lock = make_lock("obs.trace")
        self._spans: collections.deque = collections.deque()
        self._dropped = 0
        self._total = 0
        self._next = 0
        self._child_s: Dict[str, float] = collections.defaultdict(float)
        self._open: Dict[str, tuple] = {}

    def new_id(self) -> str:
        with self._lock:
            self._next += 1
            return f"{os.getpid()}:{self._next}"

    def opened(self, sid: str, name: str, t0_mono: float) -> None:
        with self._lock:
            self._open[sid] = (name, t0_mono,
                               threading.current_thread().name)

    def closed(self, sid: str, name: str, parent: Optional[str],
               t0_mono: float, t1_mono: float, attrs: Dict,
               track: Optional[str] = None) -> None:
        rec = {"id": sid, "parent": parent, "name": name,
               "ts": wall(t0_mono), "dur": max(t1_mono - t0_mono, 0.0),
               "pid": os.getpid(),
               "tid": threading.get_ident(),
               "thread": threading.current_thread().name}
        if track is not None:
            rec["tid"] = zlib.crc32(track.encode()) & 0x7FFFFFFF
            rec["thread"] = track
        if attrs:
            rec["args"] = attrs
        with self._lock:
            self._open.pop(sid, None)
            self._total += 1
            if parent is not None:
                self._child_s[parent] += rec["dur"]
            if len(self._spans) >= self._cap:
                self._spans.popleft()
                self._dropped += 1
            self._spans.append(rec)

    def adopt(self, parent: str, children) -> None:
        """Make `parent` the parent of spans that were recorded before
        it: a backfilled span whose children closed, and were recorded,
        first. The children are among the newest records."""
        want = set(children)
        with self._lock:
            for rec in reversed(self._spans):
                if not want:
                    break
                if rec["id"] in want:
                    want.discard(rec["id"])
                    if rec["parent"] is not None:
                        self._child_s[rec["parent"]] -= rec["dur"]
                    rec["parent"] = parent
                    self._child_s[parent] += rec["dur"]

    def spans(self) -> List[dict]:
        with self._lock:
            return list(self._spans)

    def open_snapshot(self) -> List[dict]:
        now = time.monotonic()
        with self._lock:
            return [{"name": name, "age_s": round(now - t0, 3),
                     "thread": thread}
                    for name, t0, thread in self._open.values()]

    def summary(self) -> Dict:
        """The steps.jsonl `trace` block, keyed by TRACE_FIELDS."""
        with self._lock:
            retained = list(self._spans)
            total, dropped = self._total, self._dropped
            child = dict(self._child_s)
        self_s: Dict[str, float] = collections.defaultdict(float)
        for rec in retained:
            self_s[rec["name"]] += max(
                rec["dur"] - child.get(rec["id"], 0.0), 0.0)
        top = [{"name": n, "self_s": round(s, 6)}
               for n, s in sorted(self_s.items(),
                                  key=lambda kv: -kv[1])[:3]]
        return dict(zip(profiling.TRACE_FIELDS, (total, dropped, top)))

    def export(self) -> Optional[str]:
        """Write this process's span file; on the coordinator, merge
        every host's file into the run's .trace.json. Raises on
        failure — trace_run absorbs it (the step must not fail)."""
        from shifu_tpu import resilience
        resilience.fault_point("obs.export")
        os.makedirs(self.trace_dir, exist_ok=True)
        path = os.path.join(self.trace_dir,
                            f"spans.{os.getpid()}.jsonl")
        with resilience.atomic_write(path, "w") as f:
            f.write(json.dumps(
                {"clock": {"pid": os.getpid(),
                           "offset_s": self.clock_offset_s,
                           "exported_at": round(time.time(), 3)}}) + "\n")
            for rec in self.spans():
                f.write(json.dumps(rec) + "\n")
        if not self.coordinator:
            return None
        out = os.path.join(os.path.dirname(self.trace_dir),
                           f"{self.run_id}.trace.json")
        merge_trace(self.trace_dir, out)
        return out


def _annotation(name: str, attrs: Dict):
    """The span as the profiler sees it. jax is imported by the first
    span, not with this module (`shifu top` and the DAG parent open
    none and stay light); a later call pays a dictionary lookup. No
    backend is touched either way."""
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(ANNOTATION_PREFIX + name, **attrs)


class _Span:
    """A span that also records into the ring buffer."""
    __slots__ = ("_tr", "_ann", "name", "attrs", "id", "parent", "_t0")

    def __init__(self, tr: Tracer, name: str, attrs: Dict):
        self._tr = tr
        self._ann = _annotation(name, attrs)
        self.name = name
        self.attrs = attrs
        self.id = ""
        self.parent: Optional[str] = None

    def __enter__(self):
        tr = self._tr
        st = _stack()
        self.parent = st[-1] if st else tr.root_id
        self.id = tr.new_id()
        st.append(self.id)
        self._ann.__enter__()
        self._t0 = time.monotonic()
        tr.opened(self.id, self.name, self._t0)
        return self

    def __exit__(self, et, ev, tb):
        t1 = time.monotonic()
        self._ann.__exit__(et, ev, tb)
        st = _stack()
        if st and st[-1] == self.id:
            st.pop()
        if et is not None:
            self.attrs = dict(self.attrs, error=repr(ev))
        self._tr.closed(self.id, self.name, self.parent, self._t0, t1,
                        self.attrs)
        return False


# ---------------------------------------------------------------------------
# job records: what a `train.job` took, and what jax built inside it
# ---------------------------------------------------------------------------

_JOB_SPAN = "train.job"
# the stages of building a program, as `profiling`'s listeners book
# them: a function traced, its jaxpr lowered to MLIR, the executable
# read back from the persistent cache, or compiled
BUILD_STAGES = ("trace", "lower", "load", "compile")
_KEPT_JOBS = 64
_TOP_FUNCTIONS = 8


class Builds:
    """What jax built for one job, or outside every job: self seconds
    by stage, programs counted, and the same seconds by function. A
    job's is written by the thread that opened the job and by no other;
    `outside` under `_jobs_lock`."""
    __slots__ = ("seconds", "counts", "by_fun")

    def __init__(self):
        self.seconds = [0.0, 0.0, 0.0, 0.0]
        self.counts = [0, 0, 0, 0]
        self.by_fun: Dict[str, List[float]] = {}

    def book(self, stage: int, fun: str, self_s: float) -> None:
        """One build event: `self_s` of `stage` for `fun`."""
        self.seconds[stage] += self_s
        self.counts[stage] += 1
        row = self.by_fun.get(fun)
        if row is None:
            row = self.by_fun[fun] = [0.0, 0.0, 0.0, 0.0]
        row[stage] += self_s

    def as_dict(self) -> Dict:
        """The `builds` block, keyed by `profiling.BUILD_FIELDS`."""
        fields = profiling.BUILD_FIELDS
        top = sorted(self.by_fun.items(),
                     key=lambda kv: -sum(kv[1]))[:_TOP_FUNCTIONS]
        functions = [dict(zip(("fun",) + fields[:4],
                              [fun] + [round(v, 6) for v in row]))
                     for fun, row in top]
        # a program traced is one that was lowered: jax also reports
        # the trace of a jitted helper inside another function's, and
        # the re-trace of an eager primitive whose program it then
        # finds in memory; their seconds count, and they are no program
        _, traced, loaded, compiled = self.counts
        return dict(zip(fields, [round(v, 6) for v in self.seconds]
                        + [traced, loaded, compiled, functions]))


# the job open on this thread (jax raises its build events on the
# thread that builds); a thread started inside a job starts with none
_OPEN_JOB: contextvars.ContextVar = contextvars.ContextVar(
    "shifu_tpu_open_job", default=None)
_jobs_lock = make_lock("obs.jobs")
_first_job: Optional[dict] = None
_jobs: collections.deque = collections.deque(maxlen=_KEPT_JOBS)
_outside = Builds()
_NO_BUILDS = _outside.as_dict()     # what a job that built nothing holds
_listening = False


@functools.lru_cache(maxsize=1)
def _process_start_mono() -> Optional[float]:
    """`time.monotonic()` at the moment the OS started this process:
    field 22 of `/proc/self/stat` (clock ticks since boot) against the
    boot clock; None where there is no such file. Read once."""
    try:
        with open("/proc/self/stat", "rb") as f:
            after_comm = f.read().rsplit(b")", 1)[1].split()
        since_boot = int(after_comm[19]) / os.sysconf("SC_CLK_TCK")
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - since_boot
        return time.monotonic() - age
    except (OSError, ValueError, IndexError, AttributeError):
        return None


def _listen() -> None:
    """The first job of a process makes sure jax's build events are
    heard (`cli.main` has done it already; `train_nn` called from the
    benchmark, a notebook or `varselect` has not)."""
    global _listening
    _listening = True
    try:
        profiling.register_build_listeners()
    except Exception as e:  # noqa: BLE001 — the account never fails a job
        log.warning("build listeners unavailable: %s", e)


class _Job:
    """`train.job`: the span itself (`_inner`: the profiler annotation,
    or the ring-buffer span around it) and the job record it leaves."""
    __slots__ = ("_inner", "attrs", "builds", "_token", "_t0")

    def __init__(self, inner, attrs: Dict):
        self._inner = inner
        self.attrs = attrs
        self.builds: Optional[Builds] = None

    def __enter__(self):
        if not _listening:
            _listen()
        self._token = _OPEN_JOB.set(self)
        self._t0 = time.monotonic()
        self._inner.__enter__()
        return self

    def __exit__(self, et, ev, tb):
        global _first_job
        self._inner.__exit__(et, ev, tb)
        t1 = time.monotonic()
        _OPEN_JOB.reset(self._token)
        born = _process_start_mono()
        builds = dict(_NO_BUILDS, functions=[]) if self.builds is None \
            else self.builds.as_dict()
        rec = dict(zip(profiling.JOB_FIELDS, (
            self.attrs,
            None if born is None else round(self._t0 - born, 6),
            round(t1 - self._t0, 6), builds)))
        with _jobs_lock:
            if _first_job is None:
                _first_job = rec
            _jobs.append(rec)
        return False


def book_build(stage: int, fun: str, t0_wall: float, t1_wall: float,
               self_s: float, children=()) -> Optional[str]:
    """One build event of jax's, from `profiling`'s listener on the
    building thread: booked to the job open there, else to `outside`;
    under `SHIFU_TPU_TRACE=1` also a `train.build` span from the
    event's own start and end, the build spans that closed inside it
    (`children`) its children. Returns that span's id, or None."""
    job = _OPEN_JOB.get()
    if job is not None:
        if job.builds is None:
            job.builds = Builds()
        job.builds.book(stage, fun, self_s)
    else:
        with _jobs_lock:
            _outside.book(stage, fun, self_s)
    if not active():
        return None
    return record_span("train.build", t0_wall - _MONO_OFFSET,
                       t1_wall - _MONO_OFFSET, children=children,
                       stage=BUILD_STAGES[stage], fun=fun)


def job_records() -> List[dict]:
    """The job records of this process, each keyed by
    `profiling.JOB_FIELDS`: the first job's, kept for good, then the
    newest 64 that are not it, oldest first."""
    with _jobs_lock:
        first, newest = _first_job, list(_jobs)
    if first is None:
        return []
    return [first] + [r for r in newest if r is not first]


def outside_builds() -> Dict:
    """What jax built with no job open on the building thread."""
    with _jobs_lock:
        return _outside.as_dict()


class _Run:
    __slots__ = ("root", "step", "run_id", "enabled", "tracer")

    def __init__(self, root, step, run_id, enabled, tracer):
        self.root = root
        self.step = step
        self.run_id = run_id
        self.enabled = enabled
        self.tracer = tracer


_RUN: Optional[_Run] = None


def active() -> bool:
    """True when a trace run is recording (the cheap guard layers use
    before computing span attributes)."""
    run = _RUN
    return run is not None and run.enabled


def span(name: str, **attrs):
    """A span around a `with` block: always a profiler annotation
    (`shifu:<name>`, seen by whatever profiler session is open), and a
    ring-buffer record besides while a `trace_run` with
    `SHIFU_TPU_TRACE=1` is active."""
    run = _RUN
    if run is None or not run.enabled:
        inner = _annotation(name, attrs)
    else:
        inner = _Span(run.tracer, name, attrs)
    if name == _JOB_SPAN:
        return _Job(inner, attrs)
    return inner


def record_span(name: str, t0_mono: float, t1_mono: float,
                parent: Optional[str] = None,
                track: Optional[str] = None, children=(),
                **attrs) -> Optional[str]:
    """Backfill one span from monotonic timestamps a layer already
    measured, into the ring buffer only (a profiler session takes no
    event after the fact). `parent` defaults to the calling thread's
    open span (or the run root); `track` groups the event onto a named
    synthetic Perfetto track instead of the recording thread's;
    `children` are ids of spans recorded before this one that lie
    inside it, and take it as their parent. Returns the span id (for
    parenting children), or None when tracing is off."""
    run = _RUN
    if run is None or not run.enabled:
        return None
    tr = run.tracer
    if parent is None:
        st = _stack()
        parent = st[-1] if st else tr.root_id
    sid = tr.new_id()
    if children:
        tr.adopt(sid, children)
    tr.closed(sid, name, parent, t0_mono, t1_mono, attrs, track=track)
    return sid


def open_spans() -> List[dict]:
    """Currently open spans (name, age, thread) — what the collective
    watchdog cites when a deadline fires."""
    run = _RUN
    if run is None or not run.enabled:
        return []
    return run.tracer.open_snapshot()


def current_run_id(step: Optional[str] = None) -> str:
    """The active trace run's id, or a fresh one for an untraced step —
    either way the id `maybe_profile` names its output after, so the
    profiler trace and the ring buffer's export pair up under tmp/."""
    run = _RUN
    if run is not None:
        return run.run_id
    return f"{step or 'run'}-{int(time.time())}-{os.getpid()}"


@contextlib.contextmanager
def trace_run(root: str, step: str):
    """Per-command trace scope: start the tracer (when enabled), open
    the `run.step` root span, and at exit attach the TRACE_FIELDS
    summary to the step record and export/merge the span files."""
    global _RUN
    if _RUN is not None:        # nested command in-process: passthrough
        yield None
        return
    if not knob_bool("SHIFU_TPU_TRACE"):
        yield None
        return
    env_dir = knob_str("SHIFU_TPU_TRACE_DIR")
    coordinator = not env_dir
    if env_dir:
        tdir = env_dir
        run_id = os.path.basename(os.path.normpath(tdir)) \
            or f"{step}-{os.getpid()}"
    else:
        run_id = f"{step}-{int(time.time())}-{os.getpid()}"
        tdir = os.path.join(root, "tmp", "trace", run_id)
        # subprocess DAG nodes / forked hosts inherit the workspace so
        # their span files join this run's merge
        os.environ["SHIFU_TPU_TRACE_DIR"] = tdir
    tracer = Tracer(run_id=run_id, trace_dir=tdir,
                    coordinator=coordinator,
                    cap=knob_int("SHIFU_TPU_TRACE_BUF"))
    run = _Run(root, step, run_id, True, tracer)
    _RUN = run
    root_span = span("run.step", step=step)
    root_span.__enter__()
    tracer.root_id = root_span.id
    try:
        yield run
    finally:
        root_span.__exit__(None, None, None)
        try:
            profiling.set_step_extra("trace", tracer.summary())
        except Exception as e:  # noqa: BLE001 — never fail the step
            log.warning("trace summary failed: %s", e)
        try:
            out = tracer.export()
            if out:
                log.info("merged trace written to %s (open in "
                         "ui.perfetto.dev)", out)
        except Exception as e:  # noqa: BLE001 — never fail the step
            log.warning("trace export failed (step unaffected): %s", e)
        if coordinator:
            os.environ.pop("SHIFU_TPU_TRACE_DIR", None)
        _RUN = None


# ---------------------------------------------------------------------------
# merge + discovery
# ---------------------------------------------------------------------------

def merge_trace(trace_dir: str, out_path: str) -> Dict:
    """Merge every `spans.*.jsonl` under `trace_dir` into one
    Chrome-trace-event JSON at `out_path`, subtracting each file's
    recorded clock offset so cross-host spans order correctly."""
    from shifu_tpu import resilience
    events: List[dict] = []
    for path in sorted(glob.glob(os.path.join(trace_dir,
                                              "spans.*.jsonl"))):
        offset = 0.0
        with open(path, encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if "clock" in rec:
                    offset = float(rec["clock"].get("offset_s", 0.0))
                    continue
                args = dict(rec.get("args", {}))
                args["id"] = rec.get("id")
                if rec.get("parent") is not None:
                    args["parent"] = rec["parent"]
                events.append({
                    "name": rec["name"],
                    "cat": rec["name"].split(".", 1)[0],
                    "ph": "X",
                    "ts": int((rec["ts"] - offset) * 1e6),
                    "dur": max(int(rec["dur"] * 1e6), 1),
                    "pid": rec.get("pid", 0),
                    "tid": rec.get("tid", 0),
                    "args": args,
                })
    events.sort(key=lambda e: e["ts"])
    doc = {"traceEvents": events, "displayTimeUnit": "ms"}
    with resilience.atomic_write(out_path, "w") as f:
        json.dump(doc, f)
    return doc


def trace_ls(root: str) -> List[dict]:
    """Discoverable run artifacts under `<root>/tmp`: one row per
    run_id pairing the merged span trace (tmp/trace/) with the
    maybe_profile device trace (tmp/profile/) that shares its name."""
    trace_dir = os.path.join(root, "tmp", "trace")
    profile_dir = os.path.join(root, "tmp", "profile")
    runs: Dict[str, dict] = {}

    def _row(run_id: str) -> dict:
        return runs.setdefault(run_id, {"run_id": run_id, "trace": None,
                                        "span_files": 0, "profile": None})

    for path in sorted(glob.glob(os.path.join(trace_dir,
                                              "*.trace.json"))):
        rid = os.path.basename(path)[:-len(".trace.json")]
        _row(rid)["trace"] = path
    for d in sorted(glob.glob(os.path.join(trace_dir, "*"))):
        if os.path.isdir(d):
            _row(os.path.basename(d))["span_files"] = len(
                glob.glob(os.path.join(d, "spans.*.jsonl")))
    for d in sorted(glob.glob(os.path.join(profile_dir, "*"))):
        if os.path.isdir(d):
            _row(os.path.basename(d))["profile"] = d
    return [runs[k] for k in sorted(runs)]
