"""Step metrics + profiler traces.

The reference's observability is per-iteration master log lines
(`NNMaster.doCompute:309`), Hadoop/Pig counters
(`EvalModelProcessor.java:473,1114-1165`), and a progress file tailed
to the console (`TrainModelProcessor.java:1468-1489` TailThread).
SURVEY.md §5 prescribes the TPU replacement: structured per-step
metrics plus `jax.profiler` traces.

- every CLI command (= every processor run) appends one JSON line to
  `tmp/metrics/steps.jsonl`: step, wall seconds, rc, backend, device
  count, and device memory stats (peak HBM bytes when the backend
  reports them);
- `shifu --profile <cmd>` additionally captures a `jax.profiler` trace
  under `tmp/profile/<step>-<timestamp>/` — openable in TensorBoard /
  Perfetto for op-level TPU timing;
- `enable_compile_cache()` (called once by `cli.main` for every
  device command) turns on jax's persistent compilation cache — at
  `JAX_COMPILATION_CACHE_DIR` when the environment sets it, else
  `SHIFU_TPU_COMPILE_CACHE_DIR` (`0`/`off` disables), else the fixed
  `<checkout>/.jax_cache` — and registers the build listeners, so
  restart / resume / supervise / grid-search paths stop re-paying XLA
  compiles and `steps.jsonl` shows that they did;
- `register_build_listeners()` (once a process: from
  `enable_compile_cache()` or from the first `train.job` span,
  whichever comes first) hears what jax reports of building a program
  (`jax.monitoring`) and books each stage's self seconds to the stage
  timers and so to `steps.jsonl`: `trace_s` (jax tracing the
  program's Python), `lower_s` (jaxpr to MLIR), `compile_cache_read_s`
  (a build request the persistent cache answered: key, read,
  deserialise) and `compile_s` (one it did not: the COMPILER, with the
  failed lookup and the write), beside the counts `compile_cache_hits`
  / `compile_cache_misses` (a miss is a program compiled AND written to
  the cache). So a warm run reads `compile_s` 0 and a read time that
  grows with the program. The same events are booked to the job that
  caused them (`obs.trace.job_records()`: by job and by function), and
  the step's record carries the process's first job under `first_job`
  (`JOB_FIELDS`, `BUILD_FIELDS`). A thread inside
  `background_compiles()` counts under `background_*` instead: a build
  hosted next to a serving fleet (the refresh retrain, the watch
  loop's drift pass) compiles its own programs for the first time, and
  those are not the serving path recompiling;
- `SHIFU_TPU_COMPILE_CACHE_SHARED` names a cluster-shared cache dir (a
  mounted path or a `scheme://` URL; a `scheme://`
  SHIFU_TPU_COMPILE_CACHE_DIR auto-routes here too): entries pull into
  the local staging dir at enable time and new local entries push back
  at process exit, each committed via `resilience.atomic_write` — an
  elastic restart on a DIFFERENT host (or a grown mesh's fresh hosts)
  reuses the fleet's compiles instead of re-paying XLA.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import json
import logging
import os
import threading
import time
from typing import Dict, Optional, Tuple

log = logging.getLogger("shifu_tpu")

_DISABLED_VALUES = ("0", "off", "none", "disabled", "false", "no")
_CACHE_MAX_BYTES = 16 << 30   # LRU bound; its real job is the file lock
_build_listeners_on = False
_cache_push_registered: Optional[tuple] = None

# enrichments queued by deeper layers (e.g. the train processor's
# roofline block) for the step record step_metrics is currently
# building — the same drain-at-exit pattern as the stage timers
_step_extras: Dict = {}


def set_step_extra(key: str, value) -> None:
    """Attach one key to the step_metrics record being recorded (the
    processor layer knows the roofline; cli.py owns the record)."""
    _step_extras[key] = value


# whether the current thread / context is a background build
_background: contextvars.ContextVar = contextvars.ContextVar(
    "shifu_tpu_background_compiles", default=False)


@contextlib.contextmanager
def background_compiles():
    """Count the compile events THIS thread raises inside the block
    under `background_compile_s` / `background_compile_cache_hits` /
    `background_compile_cache_misses` instead of the bare counters.

    The bare counters are what the zero-recompile gates read
    (`compile_cache_misses` over a steady serving window must be 0). A
    process that serves AND builds — `shifu watch` with a refresh
    controller next to a live fleet — compiles the build's programs the
    first time it runs them at a window's row count; that is not the
    serving path recompiling, and other threads (the batchers, a swap
    outside the block) keep counting bare. jax raises its monitoring
    events on the compiling thread, so a context variable is enough."""
    token = _background.set(True)
    try:
        yield
    finally:
        _background.reset(token)


# jax's timed build events (`jax.monitoring`, raised on the thread
# that builds) and the stage each is booked as, an index into
# `obs.trace.BUILD_STAGES`. The third wraps the persistent cache's
# lookup as well as the compiler: it is `load`, whole, where the cache
# answered, which `_CACHE_READ_EVENT`, raised inside it, says; else
# `compile`, whole. So a warm run's `compile` is 0, not the cache's
# bookkeeping.
_TRACE, _LOWER, _LOAD, _COMPILE = range(4)
_BUILD_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": _TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": _LOWER,
    "/jax/core/compile/backend_compile_duration": _COMPILE,
}
_CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
# build events that closed on this thread and that no enclosing event
# has claimed yet: (start, seconds, ring-buffer span id)
_UNCLAIMED_MAX = 1 << 14
_build_tls = threading.local()


def _book_stage_time(stage: int, secs: float) -> None:
    """A build stage's self seconds into the step's stage timers."""
    from shifu_tpu.data import pipeline as pipe
    # literal keys: tools/check_steps_schema.py enumerates them
    if _background.get():
        if stage == _TRACE:
            pipe.add_stage_time("background_trace_s", secs)
        elif stage == _LOWER:
            pipe.add_stage_time("background_lower_s", secs)
        elif stage == _LOAD:
            pipe.add_stage_time("background_compile_cache_read_s", secs)
        else:
            pipe.add_stage_time("background_compile_s", secs)
    elif stage == _TRACE:
        pipe.add_stage_time("trace_s", secs)
    elif stage == _LOWER:
        pipe.add_stage_time("lower_s", secs)
    elif stage == _LOAD:
        pipe.add_stage_time("compile_cache_read_s", secs)
    else:
        pipe.add_stage_time("compile_s", secs)


def _program_name(fun_name: str) -> str:
    """Tracing names the function (`f`), lowering and the compiler the
    module (`jit(f)`, `pmap(f)`): one name for the three."""
    if fun_name.endswith(")") and "(" in fun_name:
        return fun_name[fun_name.index("(") + 1:-1]
    return fun_name


def _on_cache_read(event: str, secs: float, **kw) -> None:  # noqa: ARG001
    """The persistent cache answered a build request on this thread:
    raised inside the `backend_compile_duration` span that closes next."""
    if event == _CACHE_READ_EVENT:
        _build_tls.read_back = True


def _on_build_span(event: str, start: float, end: float,
                   fun_name: str = "", **kw) -> None:  # noqa: ARG001
    """One of jax's three timed build stages closed on this thread.
    They nest (a jitted helper traced inside another function's trace
    or lowering closes first, with its own event), so an event is
    booked with its self time: its seconds less those of the events
    that started after it did, which are the ones inside it."""
    stage = _BUILD_EVENTS.get(event)
    if stage is None:
        return
    tls = _build_tls
    closed = getattr(tls, "closed", None)
    if closed is None:
        closed = tls.closed = collections.deque(maxlen=_UNCLAIMED_MAX)
    secs = max(end - start, 0.0)
    inside_s, children = 0.0, []
    while closed and closed[-1][0] >= start:
        _, child_s, child_id = closed.pop()
        inside_s += child_s
        if child_id is not None:
            children.append(child_id)
    if stage == _COMPILE:
        if getattr(tls, "read_back", False):
            stage = _LOAD       # the cache answered: no compiler ran
        tls.read_back = False
    self_s = max(secs - inside_s, 0.0)
    _book_stage_time(stage, self_s)
    from shifu_tpu.obs import trace as obs_trace
    span_id = obs_trace.book_build(stage, _program_name(fun_name), start,
                                   end, self_s, children)
    closed.append((start, secs, span_id))


def register_build_listeners() -> None:
    """Hear jax's build events, once a process (idempotent; called by
    `enable_compile_cache` and by the first `train.job` span, whichever
    comes first): the three timed stages and the cache's read-back go
    to the stage timers (`trace_s`, `lower_s`, `compile_cache_read_s`,
    `compile_s`) and to the account of the job open on the building
    thread (`obs.trace.book_build`); the cache's hits and misses to
    `compile_cache_hits` / `compile_cache_misses`. A jax build without
    these events raises here and the caller carries on without."""
    global _build_listeners_on
    if _build_listeners_on:
        return
    _build_listeners_on = True
    import jax
    from shifu_tpu.data import pipeline as pipe

    def _on_event(event: str, **kw) -> None:  # noqa: ARG001 — jax API
        # literal keys: tools/check_steps_schema.py enumerates them
        if event.endswith("/cache_hits"):
            if _background.get():
                pipe.add_stage_count("background_compile_cache_hits", 1)
            else:
                pipe.add_stage_count("compile_cache_hits", 1)
        elif event.endswith("/cache_misses"):
            if _background.get():
                pipe.add_stage_count("background_compile_cache_misses", 1)
            else:
                pipe.add_stage_count("compile_cache_misses", 1)

    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_cache_read)
    jax.monitoring.register_event_time_span_listener(_on_build_span)


def _cache_listing(path: str) -> Dict[str, int]:
    """name → size for regular files directly under a local or
    scheme:// directory (compile-cache entries are a flat namespace of
    hash-named files). Missing dir = empty; dot-prefixed names (remote
    atomic-write temps) are skipped."""
    from shifu_tpu.data import fs as fs_mod
    out: Dict[str, int] = {}
    if fs_mod.has_scheme(path):
        fsys, p = fs_mod._fs_and_path(path)
        if not fsys.exists(p):
            return out
        for info in fsys.ls(p, detail=True):
            name = str(info["name"]).rstrip("/").rsplit("/", 1)[-1]
            if info.get("type") == "file" and not name.startswith("."):
                out[name] = int(info.get("size") or 0)
    elif os.path.isdir(path):
        for name in os.listdir(path):
            fp = os.path.join(path, name)
            if os.path.isfile(fp) and not name.startswith("."):
                out[name] = os.path.getsize(fp)
    return out


def _cache_read(dirpath: str, name: str) -> bytes:
    from shifu_tpu.data import fs as fs_mod
    if fs_mod.has_scheme(dirpath):
        fsys, p = fs_mod._fs_and_path(dirpath)
        with fsys.open(f"{p.rstrip('/')}/{name}", "rb") as f:
            return f.read()
    with open(os.path.join(dirpath, name), "rb") as f:
        return f.read()


def sync_compile_cache(local_dir: str, shared_dir: str,
                       pull: bool = True, push: bool = True
                       ) -> Tuple[int, int]:
    """Diff-copy compile-cache entries between this host's local
    staging dir and the cluster-shared one (`pull`: shared→local
    entries the local dir lacks; `push`: local→shared the reverse).
    Every copy commits through `resilience.atomic_write`, so hosts
    racing to push the same key are benign — last complete rename wins
    and readers never observe a torn entry. Returns (pulled, pushed);
    never raises — the shared cache is an optimization."""
    from shifu_tpu.resilience import atomic_write
    pulled = pushed = 0
    try:
        local = _cache_listing(local_dir)
        shared = _cache_listing(shared_dir)
        if pull:
            for name in shared.keys() - local.keys():
                data = _cache_read(shared_dir, name)
                with atomic_write(os.path.join(local_dir, name), "wb") as f:
                    f.write(data)
                pulled += 1
        if push:
            from shifu_tpu.data import fs as fs_mod
            join = (lambda n: f"{shared_dir.rstrip('/')}/{n}") \
                if fs_mod.has_scheme(shared_dir) \
                else (lambda n: os.path.join(shared_dir, n))
            if not fs_mod.has_scheme(shared_dir):
                os.makedirs(shared_dir, exist_ok=True)
            for name in local.keys() - shared.keys():
                data = _cache_read(local_dir, name)
                with atomic_write(join(name), "wb") as f:
                    f.write(data)
                pushed += 1
        if pulled or pushed:
            log.info("shared compile cache %s: pulled %d, pushed %d "
                     "entr%s", shared_dir, pulled, pushed,
                     "y" if pulled + pushed == 1 else "ies")
    except Exception as e:  # noqa: BLE001 — cache is an optimization
        log.warning("shared compile-cache sync with %s failed: %s",
                    shared_dir, e)
    return pulled, pushed


def _register_cache_push(local_dir: str, shared_dir: str) -> None:
    """Push entries compiled this run to the shared dir at process
    exit (idempotent; one registration per process)."""
    global _cache_push_registered
    if _cache_push_registered:
        return
    import atexit
    atexit.register(sync_compile_cache, local_dir, shared_dir,
                    pull=False, push=True)
    _cache_push_registered = (local_dir, shared_dir)


def default_cache_dir() -> str:
    """The one place the compile cache lives when nobody places it:
    `<checkout>/.jax_cache` (git-ignored). The directory is part of the
    cache key's lookup, so it must not move between runs — never under
    a model set, the temp dir, a pid or a timestamp."""
    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Turn on jax's persistent compilation cache and the compile-time
    counters. Where the cache lives, in order:

    1. `JAX_COMPILATION_CACHE_DIR` in the environment places it from
       outside and nothing in this program overrides it — not
       `SHIFU_TPU_COMPILE_CACHE_DIR`, not a launcher — nor bounds it;
    2. else `SHIFU_TPU_COMPILE_CACHE_DIR` (`0`/`off`/`none` disables;
       a `scheme://` value names the SHARED cache, see below);
    3. else `default_cache_dir()`, one fixed path in the checkout.

    `SHIFU_TPU_COMPILE_CACHE_SHARED` (or a scheme:// value in 2) is
    mirrored into that local directory at start and published back at
    exit. Returns the active cache dir or None when disabled. Never
    raises — a cache failure must not take down a run."""
    try:
        register_build_listeners()
    except Exception as e:  # noqa: BLE001 — metrics must never fail a run
        log.warning("compile-time listeners unavailable: %s", e)
    try:
        import jax
        from shifu_tpu.config.environment import knob_float, knob_str
        from shifu_tpu.data import fs as fs_mod
        explicit = knob_str("SHIFU_TPU_COMPILE_CACHE_DIR")
        shared = knob_str("SHIFU_TPU_COMPILE_CACHE_SHARED")
        if explicit is not None and fs_mod.has_scheme(explicit):
            # a scheme:// cache dir is the shared cache: jax compiles
            # against the local directory and entries sync to the URL
            shared, explicit = shared or explicit, None
        cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        placed = bool(cache_dir)
        if not placed:
            if explicit is not None and \
                    explicit.strip().lower() in _DISABLED_VALUES:
                return None
            cache_dir = explicit or default_cache_dir()
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        if not placed and \
                "JAX_COMPILATION_CACHE_MAX_SIZE" not in os.environ:
            # a directory this program chose is shared by its own
            # processes (DAG siblings, a fleet). jax writes an entry
            # with a plain write_bytes and reads it unlocked UNLESS the
            # cache is size-bounded — only then does every get/put take
            # the directory's file lock. Unbounded, a reader can load a
            # half-written executable (seen: a worker aborting inside
            # XLA). A directory placed from outside is not ours to
            # bound, evict from or lock: whoever placed it sets
            # JAX_COMPILATION_CACHE_MAX_SIZE too if processes share it.
            jax.config.update("jax_compilation_cache_max_size",
                              _CACHE_MAX_BYTES)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          float(knob_float("SHIFU_TPU_COMPILE_CACHE_MIN_S")))
        log.info("persistent compilation cache at %s", cache_dir)
        if shared is not None and \
                shared.strip().lower() not in _DISABLED_VALUES:
            sync_compile_cache(cache_dir, shared, pull=True, push=False)
            _register_cache_push(cache_dir, shared)
        return cache_dir
    except Exception as e:  # noqa: BLE001 — cache is an optimization
        log.warning("persistent compilation cache unavailable: %s", e)
        return None


def device_stats() -> Dict:
    """Backend + device count + memory stats (peak HBM) when the
    runtime exposes them (TPU does; CPU returns none).

    Reports only when THIS process enumerated its devices
    (`mesh.devices_enumerated`): metrics run after every command,
    including pure file operations (`init`, `save`) and the DAG/combo
    parents, and asking jax for devices there would create a backend —
    on one chip that takes it away from the children."""
    out: Dict = {}
    try:
        from shifu_tpu.parallel import mesh as mesh_mod
        if not mesh_mod.devices_enumerated():
            return out   # no backend of ours — nothing to report
        import jax
        devs = mesh_mod.leased_devices()
        out["backend"] = jax.default_backend()
        out["deviceKind"] = devs[0].device_kind
        out["deviceCount"] = len(devs)
        st = devs[0].memory_stats()
        if st:
            for src, dst in (("peak_bytes_in_use", "peakBytesInUse"),
                             ("bytes_in_use", "bytesInUse"),
                             ("bytes_limit", "bytesLimit")):
                if src in st:
                    out[dst] = int(st[src])
    except Exception as e:  # noqa: BLE001 — metrics must never fail a run
        out["error"] = str(e)
    return out


@contextlib.contextmanager
def step_metrics(root: str, step: str, extra: Optional[Dict] = None):
    """Record one step's structured metrics to tmp/metrics/steps.jsonl.
    Yields a dict the caller may enrich (e.g. rows=, rc=). Each record
    also carries the input-pipeline stage timers (host_parse_s,
    host_assemble_s, h2d_s, device_step_s, input_stall_s — see
    data/pipeline.py) and any resilience retry counters accrued while
    the step ran."""
    rec: Dict = {"step": step, "startedAt": round(time.time(), 3)}
    if extra:
        rec.update(extra)
    _step_extras.clear()   # the interval belongs to THIS step
    from shifu_tpu.obs import trace as obs_trace
    jobs_before = bool(obs_trace.job_records())
    try:
        # the interval belongs to THIS step: drop whatever an earlier
        # caller in the same process left behind
        from shifu_tpu.data.pipeline import drain_stage_timers
        drain_stage_timers()
        from shifu_tpu import resilience
        resilience.retry_stats(reset=True)
        resilience.drain_events()
    except Exception as e:  # noqa: BLE001 — metrics must never fail a run
        from shifu_tpu.resilience import absorbed
        absorbed("metrics.pre-drain", e)
    t0 = time.time()
    try:
        yield rec
    finally:
        rec["wallSeconds"] = round(time.time() - t0, 3)
        rec.update(device_stats())
        if _step_extras:
            rec.update(_step_extras)
            _step_extras.clear()
        if not jobs_before:
            # the process's first job, where this step ran it: what the
            # step's first program cost to build (JOB_FIELDS)
            jobs = obs_trace.job_records()
            if jobs:
                rec["first_job"] = jobs[0]
        try:
            from shifu_tpu.data.pipeline import drain_stage_timers
            stages = drain_stage_timers()
            if stages:
                rec["inputPipeline"] = stages
            from shifu_tpu import resilience
            retries = resilience.retry_stats(reset=True)
            if retries:
                rec["retries"] = retries
            # watchdog stack dumps + supervised-restart records accrued
            # while the step ran (each also lands as its own durable
            # steps.jsonl line the moment it happens)
            events = resilience.drain_events()
            if events:
                rec["events"] = events
                restarts = [e.get("restart", 0) for e in events
                            if e.get("event") == "restart"]
                if restarts:
                    rec["restarts"] = max(restarts)
            if resilience.preempt_requested():
                rec["preempted"] = True
        except Exception as e:  # noqa: BLE001 — metrics must never fail a run
            from shifu_tpu.resilience import absorbed
            absorbed("metrics.enrich", e)
        try:
            mdir = os.path.join(root, "tmp", "metrics")
            os.makedirs(mdir, exist_ok=True)
            with open(os.path.join(mdir, "steps.jsonl"), "a") as f:
                f.write(json.dumps(rec) + "\n")
        except OSError as e:
            log.warning("metrics: could not write steps.jsonl: %s", e)
        try:
            # mirror the finished record into the persistent metrics
            # store (no-op unless SHIFU_TPU_METRICS=1)
            from shifu_tpu.obs.health import store as health_store
            health_store.flush_step_record(root, rec)
        except Exception as e:  # noqa: BLE001 — metrics must never fail a run
            log.warning("metrics store flush failed (absorbed): %s", e)


# ---------------------------------------------------------------------------
# roofline accounting (ROADMAP item 2: close the MXU gap)
# ---------------------------------------------------------------------------
#
# Analytic per-row FLOPs and bytes-moved are derived from the model
# spec alone, so the same numbers describe every backend; utilization
# divides measured throughput by the peaks of the device the run was
# on, looked up in DEVICE_PEAKS. processor/train.py attaches one
# `roofline` block to the train step's steps.jsonl record, and
# tools/check_steps_schema.py pins README docs to ROOFLINE_FIELDS.

# Published per-chip peaks keyed by jax's `device_kind`. Source: Google
# Cloud TPU documentation, "TPU v5e" system architecture — 197 TFLOP/s
# bf16 MXU peak, 16 GB HBM at 819 GB/s per chip (v5e reports itself as
# "TPU v5 lite"). The MXU's only published figure is the bf16 one; f32
# operands at default precision run as bf16 passes under the same
# ceiling, so one number serves every compute dtype. A device that is
# not in this table has NO peak: its utilization fields are null —
# never another chip's number.
DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    "TPU v5e": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def device_peaks(device_kind: Optional[str] = None
                 ) -> Optional[Dict[str, float]]:
    """DEVICE_PEAKS entry for `device_kind`, default the kind of the
    first device this process may use — asked of the runtime through
    the lease seam, so a run on a chip in the table cannot lose its
    peaks to a path that never enumerated devices. Only a process that
    did device work asks for a roofline; a scheduler parent never
    does. None when the kind is not in the table."""
    if device_kind is None:
        from shifu_tpu.parallel import mesh as mesh_mod
        device_kind = mesh_mod.leased_devices()[0].device_kind
    return DEVICE_PEAKS.get(device_kind)


ROOFLINE_FIELDS = ("family", "compute_dtype", "flops_per_row",
                   "bytes_per_row", "rows_per_s", "flops_per_s",
                   "bytes_per_s", "arith_intensity", "ridge_intensity",
                   "mxu_util", "hbm_util", "bound")

# the FleetService summary schema: serve/fleet.py builds its
# stats()["fleet"] block from exactly these keys — resident model
# count, LRU evictions, total re-warm seconds, the low-priority shed
# fraction, per-priority-class p99 latency, and the hot-swap counters.
# tools/check_steps_schema.py pins README docs to this tuple the same
# way it pins ROOFLINE_FIELDS.
FLEET_FIELDS = ("models_resident", "evictions", "rewarm_s",
                "shed_rate", "p99_ms_by_class", "swaps", "swap_s")

# the pipeline DAG scheduler's record schema: a scheduled step attaches
# one `dag` block to its steps.jsonl record — DAG_SUMMARY_FIELDS are
# the block's top-level keys, DAG_FIELDS the schema of each entry in
# its `nodes` list. pipeline/scheduler.py builds every per-node record
# from DAG_FIELDS, and tools/check_steps_schema.py pins README docs to
# both tuples the same way it pins ROOFLINE_FIELDS. `devices` is the
# size of the device slice the node held (0 for host/cached nodes,
# null when the scheduler ran in legacy timeshared mode);
# `total_devices` is the pool the slice allocator leased from (null in
# timeshared mode), `max_concurrent` the peak number of device nodes
# running at once, and `occupancy` is slice-weighted under slicing
# (Σ run_s·devices / wall·total_devices).
DAG_FIELDS = ("node", "state", "deps", "queue_s", "run_s", "devices",
              "critical_path")
DAG_SUMMARY_FIELDS = ("workers", "total_devices", "wall_s",
                      "critical_path_s", "occupancy", "max_concurrent",
                      "failed", "nodes")

# the span tracer's per-step summary block: obs/trace.py attaches one
# `trace` block (built from exactly this tuple) to the steps.jsonl
# record of every traced step — total spans recorded, ring-buffer
# drops, and the top-3 span names by accumulated self time.
# tools/check_steps_schema.py pins README docs to this tuple the same
# way it pins ROOFLINE_FIELDS.
TRACE_FIELDS = ("span_count", "dropped_spans", "top_self")

# the job record's schema: every closed `train.job` span leaves one
# (`obs.trace.job_records()`, built from exactly these tuples), and a
# step's steps.jsonl record carries the process's first under
# `first_job` when that job ran inside the step. JOB_FIELDS: the
# span's attrs, the job's start in seconds since the OS started the
# process (null where there is no /proc), its seconds, and `builds`.
# BUILD_FIELDS: the self seconds jax spent inside the job tracing,
# lowering, reading executables back from the persistent cache and
# compiling; the programs traced (and lowered: a helper traced inside
# another function is part of that program, and the re-trace of an
# eager primitive whose program is found in memory is none), read back
# and compiled; and the (at most eight) functions that took most of
# those seconds, each `fun` with its own four. Pinned in README by
# tools/check_steps_schema.py like ROOFLINE_FIELDS.
JOB_FIELDS = ("attrs", "start_s", "seconds", "builds")
BUILD_FIELDS = ("trace_s", "lower_s", "load_s", "compile_s", "traced",
                "loaded", "compiled", "functions")

# the metrics store's point schema: every line of tmp/metrics/
# metrics.jsonl is built from exactly this tuple
# (obs/health/store.py:_point) — when the point was taken, the metric
# name, its value (a number, or the count/sum/min/max/last dict for
# `rollup` points), the point kind (counter|gauge|event|rollup), and
# the flat tag map (step, run_id, feature, ...). Pinned in README by
# tools/check_steps_schema.py like ROOFLINE_FIELDS.
METRIC_FIELDS = ("ts", "name", "value", "kind", "tags")

# the SLO evaluator's record schema: obs/health/slo.py builds every
# evaluation/transition record from exactly this tuple — the rule
# name, the store metric it reads, ok|warn|breach after hysteresis,
# the aggregated value observed, the two thresholds, and the read
# window. Pinned in README by tools/check_steps_schema.py.
HEALTH_FIELDS = ("slo", "metric", "state", "value", "warn", "breach",
                 "window_s")


def mlp_row_costs(input_dim: int, hidden_dims, n_out: int = 1,
                  train: bool = True, dtype_bytes: int = 4):
    """Analytic (flops, bytes) per data row for an MLP (NN family).

    FLOPs: 2·d_in·d_out per matmul, and a train step costs ~3× forward
    (forward, activation-grad, and weight-grad matmuls). Bytes: every
    activation is written once and read once (2× each layer width) in
    the compute dtype, doubled again for the backward pass; per-row
    weight traffic amortizes across the batch and is excluded.
    """
    dims = [int(input_dim)] + [int(d) for d in hidden_dims] + [int(n_out)]
    mm = sum(2 * a * b for a, b in zip(dims, dims[1:]))
    flops = (3 if train else 1) * mm
    bytes_ = 2 * dtype_bytes * sum(dims) * (2 if train else 1)
    return float(flops), float(bytes_)


def wdl_row_costs(dense_dim: int, n_cat: int, embed_size: int,
                  hidden_dims, train: bool = True, dtype_bytes: int = 4):
    """WDL = deep MLP over [dense ‖ embeddings] + wide linear logit.
    Embedding rows are gathered per example (read fwd, read+write in
    the backward scatter).

    A model of what a straightforward implementation moves, per row.
    The benchmark's `benchmark/work/wdl.py` counts the same MLP
    operations (3 · 2 · Σ d_in·d_out a training row; the 2 · (dense +
    n_cat) wide adds here are left out there) but fewer bytes, on
    purpose: it is a lower bound on ANY implementation, so activations
    stay on the chip, a batch reads each DISTINCT id's row once however
    often the id repeats, and the optimizer touches those rows only;
    this function charges every lookup its row and every layer its
    activations."""
    deep_in = int(dense_dim) + int(n_cat) * int(embed_size)
    flops, bytes_ = mlp_row_costs(deep_in, hidden_dims, 1, train,
                                  dtype_bytes)
    flops += 2 * (int(dense_dim) + int(n_cat))
    bytes_ += dtype_bytes * int(n_cat) * int(embed_size) * \
        (3 if train else 1)
    return float(flops), float(bytes_)


def tree_row_costs(n_cols: int, n_bins: int, max_depth: int,
                   n_trees: int = 1, subtract: bool = True,
                   phase: str = "build"):
    """GBT/RF per-row costs, by phase.

    phase="build" — level building: each level contracts a node
    one-hot (slots×R) against a gradient-weighted bin one-hot
    (R×C·n_bins) on the MXU, twice (grad + hess); sibling subtraction
    halves the slots actually built below the root. Bytes: the int32
    bin row (or f32 value row on the fused path) plus grad/hess are
    re-read per level.

    phase="infer" — the fused ensemble-inference kernel
    (ops/pallas_trees): in-register binning compares every value
    against its cut row, the one-hot feature contraction computes
    every packed node's routed bin on the MXU (S = n_trees · padded
    node slots), and the breadth-first walk runs max_depth select
    steps over the (T, N, row) view. Bytes: the raw f32 value row in,
    one f32 score out — the node block and cuts stay VMEM-resident
    across the whole row tile.
    """
    if phase == "infer":
        s = n_trees * (2 ** (int(max_depth) + 1) - 1)
        flops = (int(n_cols) * max(int(n_bins) - 2, 1)   # binning
                 + 2 * int(n_cols) * s                   # routed bins
                 + 4 * s                                 # broadcasts
                 + 3 * int(max_depth) * s)               # select walk
        bytes_ = 4 * int(n_cols) + 4
        return float(flops), float(bytes_)
    flops = 0.0
    for d in range(int(max_depth)):
        slots = 2 ** d
        if subtract and d > 0:
            slots /= 2
        flops += 2 * 2 * slots * int(n_cols) * int(n_bins)
    bytes_ = int(max_depth) * (4 * int(n_cols) + 8)
    return float(flops * n_trees), float(bytes_ * n_trees)


def roofline(family: str, flops_per_row: float, bytes_per_row: float,
             rows_per_s: float, compute_dtype: str = "float32",
             device_kind: Optional[str] = None,
             peak_flops: Optional[float] = None,
             peak_bytes_per_s: Optional[float] = None) -> Dict:
    """Combine analytic per-row costs with a measured rows/s into the
    `roofline` block (steps.jsonl): achieved flops_per_s /
    bytes_per_s, arithmetic intensity vs the ridge point, and MXU/HBM
    utilization that say whether the shape is compute- or
    bandwidth-bound. Peaks come from `device_peaks(device_kind)` — the
    run's own device by default — unless given explicitly; without a
    peak, `ridge_intensity`, `mxu_util`, `hbm_util` and `bound` are
    None (a CPU run has no TPU roofline)."""
    if peak_flops is None or peak_bytes_per_s is None:
        peaks = device_peaks(device_kind) or {}
        peak_flops = peak_flops or peaks.get("flops_per_s")
        peak_bytes_per_s = peak_bytes_per_s or peaks.get("hbm_bytes_per_s")
    rows = max(float(rows_per_s), 0.0)
    fps = float(flops_per_row) * rows
    bps = float(bytes_per_row) * rows
    ai = float(flops_per_row) / bytes_per_row if bytes_per_row else 0.0
    known = bool(peak_flops and peak_bytes_per_s)
    ridge = peak_flops / peak_bytes_per_s if known else None
    return {"family": family,
            "compute_dtype": str(compute_dtype),
            "flops_per_row": float(flops_per_row),
            "bytes_per_row": float(bytes_per_row),
            "rows_per_s": round(rows, 3),
            "flops_per_s": round(fps, 3),
            "bytes_per_s": round(bps, 3),
            "arith_intensity": round(ai, 4),
            "ridge_intensity": round(ridge, 4) if known else None,
            "mxu_util": round(fps / peak_flops, 4) if known else None,
            "hbm_util": round(bps / peak_bytes_per_s, 4) if known else None,
            "bound": (("compute" if ai >= ridge else "memory")
                      if known else None)}


@contextlib.contextmanager
def maybe_profile(root: str, step: str, enabled: bool):
    """jax.profiler trace around a step when --profile is set. The
    trace (`tmp/profile/<run_id>/`) contains the step's program spans
    itself: every `obs.trace.span` is a profiler annotation
    (`shifu:train.program`, ...) on the host plane, on the same clock
    as the device planes. The output dir is named by the tracer's
    run_id, so `shifu trace ls` pairs it with the ring buffer's export
    (`tmp/trace/<run_id>.trace.json`, written under
    `SHIFU_TPU_TRACE=1`), which adds the `record_span` families."""
    if not enabled:
        yield None
        return
    import jax
    from shifu_tpu.obs import trace as obs_trace
    out = os.path.join(root, "tmp", "profile",
                       obs_trace.current_run_id(step))
    os.makedirs(out, exist_ok=True)
    jax.profiler.start_trace(out)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
        log.info("profiler trace written to %s (open with TensorBoard "
                 "or ui.perfetto.dev)", out)
