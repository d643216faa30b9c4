"""Async host input pipeline — overlap parse/assembly with compute.

Every streaming step iterates host-side chunks (pandas/pyarrow parse in
`data/reader.iter_raw_table`, or mmap materialization + bag-weight
generation in `train/streaming`) and, before this module, did so ON the
critical path: the device sat idle while the host parsed chunk k+1.
The reference hides the same latency in the Hadoop substrate (mappers
parse splits while Guagua masters aggregate); the TPU rebuild hides it
with a bounded-queue background prefetcher.

Two entry points:

- ``prefetch(iterable)`` — order-preserving, thread-backed prefetch of
  an arbitrary chunk iterator. One reader thread pulls from the source
  (``next()`` calls are inherently sequential) into a bounded queue of
  ``depth`` chunks; the consumer yields them in the exact source order,
  so outputs are byte-identical to the sequential path.
- ``map_prefetch(fn, items)`` — apply an assembly function to a KNOWN
  list of work items with a thread pool, yielding results in order with
  at most ``depth`` assemblies in flight. This is what the streaming
  trainer uses: ``fn`` does the numpy-only host half (mmap reads,
  ``ascontiguousarray``, padding, Philox bag weights) while the
  consumer thread keeps all JAX device placement to itself —
  ``jax.make_array_from_process_local_data``/``device_put`` are not
  thread-safe across the multi-host coordination layer.
- ``map_stream(fn, iterable)`` — `map_prefetch` for an UNSIZED source:
  a producer thread pulls chunks sequentially (``next()`` time counts
  as parse) and farms ``fn`` out to the assembly pool, with results
  yielded in order. This is the eval scorer's shape — `iter_raw_table`
  streams an unknown number of chunks, each needing a pandas/numpy
  matrix build (`_build_eval_dataset`) before the device scores it.

Knobs (both read per call, so tests can flip them):

- ``SHIFU_TPU_PREFETCH_DEPTH``   (default 2) — max chunks buffered
  ahead of the consumer; ``0`` disables the background thread.
- ``SHIFU_TPU_PREFETCH_WORKERS`` (default 2) — assembly threads for
  ``map_prefetch``; ``0`` disables and restores the exact sequential
  code path (no thread, no queue — today's behavior).

Fault injection: the ``pipeline.fetch`` site fires once per chunk
inside the producer (``SHIFU_TPU_FAULT=pipeline.fetch:oserror:2``
breaks the 2nd fetch). An injected — or organic — producer error is
carried across the queue and re-raised in the consumer; the worker
thread exits and the queue is drained, never left blocking.

Observability: every stage accrues wall time into a process-wide
thread-safe accumulator — ``host_parse_s`` (producer time in
``next()``), ``host_assemble_s`` (map_prefetch worker time), ``h2d_s``
and ``device_step_s`` (reported by the streaming trainer), and
``input_stall_s`` (consumer time spent WAITING on the pipeline — the
number that should collapse when overlap works). The overlap layer
adds ``ckpt_save_s`` (full checkpoint serialize+publish wall time) vs
``ckpt_stall_s`` (what the step loop actually waited — staging only
under ``SHIFU_TPU_CKPT_ASYNC=1``), ``host_sync_s`` (deliberate
``host_fetch`` waits), and the compile-cache counters ``compile_s`` /
``compile_cache_hits`` / ``compile_cache_misses`` fed by
``profiling.enable_compile_cache``. ``profiling.step_metrics`` drains
the accumulator into the step's ``tmp/metrics/steps.jsonl`` line under
``inputPipeline``. On the synchronous fallback paths the full fetch
time counts as both parse and stall — by definition all of it sits on
the critical path.
"""

from __future__ import annotations

import collections
import logging
import queue
import threading
import time
from typing import Callable, Dict, Iterable, Iterator, Sequence, TypeVar

from shifu_tpu.analysis.lockcheck import make_lock
from shifu_tpu.config.environment import knob_bool, knob_int, knob_is_set
from shifu_tpu.obs import trace as obs_trace
from shifu_tpu.resilience import fault_point

log = logging.getLogger("shifu_tpu")

T = TypeVar("T")
U = TypeVar("U")

FETCH_SITE = "pipeline.fetch"


def prefetch_depth() -> int:
    """SHIFU_TPU_PREFETCH_DEPTH (chunks buffered ahead; 0 = off)."""
    return max(knob_int("SHIFU_TPU_PREFETCH_DEPTH"), 0)


def prefetch_workers() -> int:
    """SHIFU_TPU_PREFETCH_WORKERS (assembly threads; 0 = off)."""
    return max(knob_int("SHIFU_TPU_PREFETCH_WORKERS"), 0)


def h2d_double_buffer() -> bool:
    """Whether the streaming trainer places chunk N+1 on device AFTER
    dispatching chunk N's update (so the `jax.device_put` host cost
    overlaps device compute) instead of before it. An explicitly set
    `SHIFU_TPU_H2D_DOUBLE_BUFFER` wins on any backend (tests exercise
    the overlap path on CPU); unset, the overlap is enabled only where
    the runtime actually has an async transfer engine — on the cpu
    backend `device_put` degenerates to a copy on the calling thread,
    so the reorder would buy nothing."""
    if knob_is_set("SHIFU_TPU_H2D_DOUBLE_BUFFER"):
        return knob_bool("SHIFU_TPU_H2D_DOUBLE_BUFFER")
    import jax
    return jax.default_backend() != "cpu"


# ---------------------------------------------------------------------------
# per-stage wall-time accumulator (drained into steps.jsonl)
# ---------------------------------------------------------------------------

_timers_lock = make_lock("pipeline.timers")
_timers: collections.Counter = collections.Counter()


def add_stage_time(stage: str, seconds: float) -> None:
    """Accrue wall seconds for a pipeline stage (thread-safe)."""
    with _timers_lock:
        _timers[stage] += seconds


def add_stage_count(stage: str, n: int = 1) -> None:
    with _timers_lock:
        _timers[stage] += n


def peek_stage_timers() -> Dict[str, float]:
    """Snapshot the accumulated stage timers without clearing them."""
    with _timers_lock:
        return {k: round(float(v), 6) for k, v in _timers.items()}


def drain_stage_timers() -> Dict[str, float]:
    """Snapshot AND clear — each steps.jsonl record owns its interval."""
    with _timers_lock:
        out = {k: round(float(v), 6) for k, v in _timers.items()}
        _timers.clear()
    return out


def host_fetch(x):
    """The ONE sanctioned device→host sync in hot paths: block on `x`,
    return it as a numpy array, and accrue the wait into the
    ``host_sync_s`` stage timer — plus a ``host_syncs`` occurrence
    counter — so an intentional sync shows up in ``steps.jsonl``
    instead of hiding as generic slowness. The lint
    rule ``host-sync-in-hot-loop`` flags raw ``np.asarray``/``float``/
    ``.item()`` on device values inside loops; routing a *deliberate*
    per-chunk or per-epoch fetch through here keeps the loop clean and
    the cost measured."""
    import numpy as np
    t0 = time.perf_counter()
    with obs_trace.span("host.sync"):
        out = np.asarray(x)
    add_stage_time("host_sync_s", time.perf_counter() - t0)
    add_stage_count("host_syncs")
    return out


# ---------------------------------------------------------------------------
# prefetch(iterable) — ordered background fetch of a chunk iterator
# ---------------------------------------------------------------------------

class _Done:
    pass


class _Raised:
    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


_DONE = _Done()


def _sync_fetch(iterable: Iterable[T], site: str) -> Iterator[T]:
    """Sequential fallback — the pre-pipeline code path, plus the fault
    seam and timers (all fetch time is stall time here by definition)."""
    it = iter(iterable)
    while True:
        t0 = time.monotonic()
        try:
            fault_point(site)
            item = next(it)
        except StopIteration:
            return
        finally:
            dt = time.monotonic() - t0
            add_stage_time("host_parse_s", dt)
            add_stage_time("input_stall_s", dt)
        obs_trace.record_span("input.host_parse", t0, t0 + dt)
        add_stage_count("chunks")
        yield item


def prefetch(iterable: Iterable[T], depth: int | None = None,
             site: str = FETCH_SITE) -> Iterator[T]:
    """Order-preserving background prefetch of `iterable`.

    A daemon reader thread stays at most `depth` chunks ahead
    (bounded ``queue.Queue``), so memory is capped at depth+1 live
    chunks while chunk k+1's parse overlaps the consumer's work on
    chunk k. Yield order is exactly the source order. Closing the
    generator early (or a consumer error) shuts the reader down
    cleanly; a producer error re-raises in the consumer."""
    if depth is None:
        depth = prefetch_depth()
    if depth <= 0 or prefetch_workers() <= 0:
        yield from _sync_fetch(iterable, site)
        return

    q: queue.Queue = queue.Queue(maxsize=depth)
    stop = threading.Event()

    def _offer(item) -> bool:
        """put() that gives up when the consumer has gone away — the
        worker must never block forever on a full queue."""
        while not stop.is_set():
            try:
                q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _produce() -> None:
        it = iter(iterable)
        while not stop.is_set():
            t0 = time.monotonic()
            try:
                fault_point(site)
                item = next(it)
            except StopIteration:
                _offer(_DONE)
                return
            except BaseException as e:  # noqa: BLE001 — carried across
                _offer(_Raised(e))
                return
            t1 = time.monotonic()
            add_stage_time("host_parse_s", t1 - t0)
            obs_trace.record_span("input.host_parse", t0, t1)
            if not _offer(item):
                return

    worker = threading.Thread(target=_produce, daemon=True,
                              name="shifu-prefetch")
    worker.start()
    try:
        while True:
            t0 = time.monotonic()
            item = q.get()
            add_stage_time("input_stall_s", time.monotonic() - t0)
            if item is _DONE:
                return
            if isinstance(item, _Raised):
                raise item.exc
            add_stage_count("chunks")
            yield item
    finally:
        stop.set()
        while True:  # unblock a producer waiting on a full queue
            try:
                q.get_nowait()
            except queue.Empty:
                break
        worker.join(timeout=5.0)


# ---------------------------------------------------------------------------
# map_prefetch(fn, items) — ordered background assembly of known work
# ---------------------------------------------------------------------------

def map_prefetch(fn: Callable[[T], U], items: Sequence[T],
                 depth: int | None = None, workers: int | None = None,
                 site: str = FETCH_SITE,
                 stage: str = "host_assemble_s") -> Iterator[U]:
    """Yield ``fn(item)`` for each item IN ORDER, computing up to
    `depth` items ahead on `workers` threads. With ``workers=0`` (or
    ``depth=0``) this is a plain sequential map — the exact
    pre-pipeline behavior. `fn` must be thread-safe and must not touch
    JAX device APIs (numpy only); the caller keeps device placement on
    its own thread. A worker error re-raises at the failed item's
    position in the yield order; later submissions are cancelled."""
    items = list(items)
    if depth is None:
        depth = prefetch_depth()
    if workers is None:
        workers = prefetch_workers()

    def _timed(item: T) -> U:
        t0 = time.monotonic()
        try:
            fault_point(site)
            return fn(item)
        finally:
            t1 = time.monotonic()
            add_stage_time(stage, t1 - t0)
            if stage == "host_assemble_s":
                obs_trace.record_span("input.host_assemble", t0, t1)

    if depth <= 0 or workers <= 0 or not items:
        for item in items:
            t0 = time.monotonic()
            try:
                out = _timed(item)
            finally:
                # synchronous: assembly time IS stall time
                add_stage_time("input_stall_s", time.monotonic() - t0)
            add_stage_count("chunks")
            yield out
        return

    from concurrent.futures import ThreadPoolExecutor

    pending: collections.deque = collections.deque()
    ex = ThreadPoolExecutor(max_workers=min(workers, depth),
                            thread_name_prefix="shifu-pipeline")
    try:
        idx = 0
        while idx < min(depth, len(items)):
            pending.append(ex.submit(_timed, items[idx]))
            idx += 1
        while pending:
            fut = pending.popleft()
            t0 = time.monotonic()
            try:
                out = fut.result()
            finally:
                add_stage_time("input_stall_s", time.monotonic() - t0)
            if idx < len(items):
                pending.append(ex.submit(_timed, items[idx]))
                idx += 1
            add_stage_count("chunks")
            yield out
    finally:
        for fut in pending:
            fut.cancel()
        # running assemblies finish on their own; nothing ever blocks
        # on the consumer, so shutdown cannot deadlock
        ex.shutdown(wait=False)


# ---------------------------------------------------------------------------
# map_stream(fn, iterable) — ordered background assembly of a stream
# ---------------------------------------------------------------------------

def map_stream(fn: Callable[[T], U], iterable: Iterable[T],
               depth: int | None = None, workers: int | None = None,
               site: str = FETCH_SITE,
               stage: str = "host_assemble_s") -> Iterator[U]:
    """`map_prefetch` over an UNSIZED source: yield ``fn(item)`` for
    each item of `iterable` IN ORDER, with a producer thread pulling
    ``next()`` sequentially and up to `depth` assemblies in flight on
    `workers` pool threads. ``next()`` wall time accrues to
    ``host_parse_s`` and ``fn`` time to `stage`, exactly like
    prefetch + map_prefetch. With ``workers=0`` or ``depth=0`` this is
    a plain sequential map (the pre-pipeline code path). `fn` must be
    thread-safe and numpy/pandas-only — the caller keeps JAX device
    work on its own thread. Producer and worker errors re-raise at the
    failed item's position in the yield order; closing the generator
    early shuts everything down without blocking."""
    if depth is None:
        depth = prefetch_depth()
    if workers is None:
        workers = prefetch_workers()

    if depth <= 0 or workers <= 0:
        for item in _sync_fetch(iterable, site):
            t0 = time.monotonic()
            try:
                out = fn(item)
            finally:
                dt = time.monotonic() - t0
                add_stage_time(stage, dt)
                # synchronous: assembly time IS stall time
                add_stage_time("input_stall_s", dt)
                if stage == "host_assemble_s":
                    obs_trace.record_span("input.host_assemble", t0,
                                          t0 + dt)
            yield out
        return

    from concurrent.futures import ThreadPoolExecutor

    def _timed(item: T) -> U:
        t0 = time.monotonic()
        try:
            return fn(item)
        finally:
            t1 = time.monotonic()
            add_stage_time(stage, t1 - t0)
            if stage == "host_assemble_s":
                obs_trace.record_span("input.host_assemble", t0, t1)

    # futures travel through a bounded queue so the producer stays at
    # most `depth` chunks ahead of the consumer (same memory cap as
    # prefetch: depth+1 live chunks)
    q: queue.Queue = queue.Queue(maxsize=depth)
    stop = threading.Event()
    ex = ThreadPoolExecutor(max_workers=min(workers, depth),
                            thread_name_prefix="shifu-pipeline")

    def _offer(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _produce() -> None:
        it = iter(iterable)
        while not stop.is_set():
            t0 = time.monotonic()
            try:
                fault_point(site)
                item = next(it)
            except StopIteration:
                _offer(_DONE)
                return
            except BaseException as e:  # noqa: BLE001 — carried across
                _offer(_Raised(e))
                return
            t1 = time.monotonic()
            add_stage_time("host_parse_s", t1 - t0)
            obs_trace.record_span("input.host_parse", t0, t1)
            if not _offer(ex.submit(_timed, item)):
                return

    producer = threading.Thread(target=_produce, daemon=True,
                                name="shifu-map-stream")
    producer.start()
    try:
        while True:
            t0 = time.monotonic()
            got = q.get()
            if got is _DONE:
                add_stage_time("input_stall_s", time.monotonic() - t0)
                return
            if isinstance(got, _Raised):
                add_stage_time("input_stall_s", time.monotonic() - t0)
                raise got.exc
            try:
                out = got.result()
            finally:
                add_stage_time("input_stall_s", time.monotonic() - t0)
            add_stage_count("chunks")
            yield out
    finally:
        stop.set()
        while True:  # unblock a producer waiting on a full queue
            try:
                got = q.get_nowait()
                if hasattr(got, "cancel"):
                    got.cancel()
            except queue.Empty:
                break
        producer.join(timeout=5.0)
        ex.shutdown(wait=False)
