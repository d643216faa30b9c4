"""Multi-host initialization + sharded ingestion.

The reference scales out by adding YARN containers, each reading its
own HDFS split (`ShifuInputFormat`, `CombineInputFormat`). Here
multi-host scale-out is `jax.distributed.initialize` (DCN between
hosts, ICI within), and each process reads a disjoint subset of the
part files (`read_raw_table(file_shard=(process_index, process_count))`)
before placing its rows into the global row-sharded array via
`jax.make_array_from_process_local_data`.

Hang-proofing: every blocking collective (`writer_barrier`,
`single_writer`'s release barrier, `global_row_array`) runs under a
watchdog when ``SHIFU_TPU_BARRIER_TIMEOUT_S`` is set — the collective
itself moves to a daemon thread (a blocked C call cannot be
interrupted) while the caller polls a deadline and the shared abort
marker (`resilience.check_abort`). On deadline expiry the watchdog
dumps every Python thread's stack to stderr + ``steps.jsonl`` and
raises `DistTimeout`; on a peer's abort marker it raises `DistAborted`
carrying the peer's original error. `single_writer` publishes that
marker when its body raises, so one host's exception becomes a clean
same-error abort on every host instead of a pod-wide deadlock. The
watchdog also polls the PREEMPT marker (`resilience.publish_preempt`):
a SIGTERM'd peer's broadcast sets this host's preempt flag so both
take the epoch-boundary checkpoint-and-exit(75) path together, and if
the collective stays blocked past SHIFU_TPU_PREEMPT_GRACE_S the peer
is gone and `Preempted` raises directly — cluster-wide preemption
consensus. `initialize` itself runs under the same watchdog with its
own deadline (SHIFU_TPU_INIT_TIMEOUT_S + margin). Fault sites
``dist.init``, ``dist.barrier``, ``dist.allgather``,
``dist.allreduce_tree``, ``dist.preempt_marker`` make all of this
testable single-process.

Pod-scale data plane (SHIFU_TPU_DATA_SHARD): `data_shard()` decides
whether the stats/norm/PSI/correlation/eval readers split the input
across hosts; `allgather_obj` / `allreduce_tree` / `broadcast_tree`
are the watched host-object collectives their partial-result merges
run through — same watchdog/poison/preempt machinery as the barriers,
so a host dying mid-merge surfaces as DistTimeout/DistAborted on the
survivors instead of a hang. `merge_keyed_striped` is the
bounded-memory merge protocol on top: per-chunk contributions
exchange one file-stripe per round and fold in global chunk order, so
>RAM datasets never materialize every host's whole contribution list.
Streaming collectives (those with per-chunk work between rounds) run
on the longer `stream_timeout_s` deadline instead of the barrier's.
"""

from __future__ import annotations

import logging
import threading
import time
from contextlib import contextmanager
from typing import Callable, Optional

import jax
import numpy as np

from shifu_tpu.analysis.lockcheck import make_lock
from shifu_tpu.config.environment import knob_float, knob_int, knob_str
from shifu_tpu.obs import trace as obs_trace
from shifu_tpu.resilience import absorbed, fault_point

log = logging.getLogger("shifu_tpu")


class DistTimeout(TimeoutError):
    """A collective did not complete within SHIFU_TPU_BARRIER_TIMEOUT_S
    — a peer host likely died or fell far behind."""


class DistAborted(RuntimeError):
    """A peer host published an abort marker while this host waited at
    a collective; the message carries the peer's original error."""


def barrier_timeout_s() -> Optional[float]:
    """SHIFU_TPU_BARRIER_TIMEOUT_S as seconds, or None (no deadline —
    the pre-watchdog behavior: block forever)."""
    v = knob_float("SHIFU_TPU_BARRIER_TIMEOUT_S")
    return v if v is not None and v > 0 else None


# collectives currently blocked inside _watched, so a watchdog timeout
# can say WHICH barriers the process was stuck in (threaded pipelines
# can have several in flight) — guarded by the instrumented-lock shim
_inflight_lock = make_lock("dist.inflight")
_inflight: dict = {}
_inflight_seq = 0


def inflight_collectives() -> dict:
    """tag -> seconds-in-flight for every collective some thread is
    blocked on right now."""
    with _inflight_lock:
        now = time.monotonic()
        return {k: round(now - v, 3) for k, v in _inflight.items()}


def _my_index() -> int:
    try:
        return jax.process_index()
    except Exception:  # noqa: BLE001 — no backend yet
        return -1


def _abort_error(tag: str, ab: dict) -> "DistAborted":
    return DistAborted(
        f"peer process {ab.get('process')} aborted at "
        f"{ab.get('site')!r}: {ab.get('error')} — this host stops with "
        f"the same error instead of hanging at {tag!r}")


def _observe_preempt(tag: str) -> bool:
    """Join a peer's broadcast preemption: when a preempt marker from
    ANOTHER process exists, set this process's preempt flag so its
    epoch loop takes the same checkpoint-and-exit(75) path at the next
    boundary. Returns True when a peer marker is present."""
    from shifu_tpu import resilience
    pm = resilience.check_preempt_marker()
    if not pm or pm.get("process") == _my_index():
        return False
    if not resilience.preempt_requested():
        log.warning(
            "peer process %s published a preemption notice (%s) while "
            "this host waited at %r — joining the cluster-wide "
            "checkpoint-and-exit(rc=%d) at the next epoch boundary",
            pm.get("process"), pm.get("note", ""), tag,
            resilience.PREEMPT_RC)
        resilience.request_preempt()
    return True


def _watched(tag: str, fn: Callable, timeout_s: Optional[float] = None):
    """Run a blocking collective on a daemon thread while this thread
    polls (a) completion, (b) the shared abort AND preempt markers,
    (c) the deadline — `timeout_s` when given (dist.init's own knob),
    else SHIFU_TPU_BARRIER_TIMEOUT_S. Exceptions from the collective
    re-raise here; an expired deadline dumps all thread stacks and
    raises `DistTimeout`; a peer's abort marker raises `DistAborted`.
    A peer's PREEMPT marker first just sets the local preempt flag
    (the collective normally completes — the preempting host finishes
    its epoch before exiting); if the collective is still blocked
    SHIFU_TPU_PREEMPT_GRACE_S later, the peer is gone and this raises
    `Preempted` directly so the host still exits rc 75, not a timeout.
    With no timeout set the deadline check is off but marker polling
    still runs — a poisoned barrier never needs the timeout to fail
    cleanly."""
    from shifu_tpu import resilience
    timeout = barrier_timeout_s() if timeout_s is None else timeout_s
    box: dict = {}
    done = threading.Event()

    def _call() -> None:
        try:
            box["value"] = fn()
        except BaseException as e:  # noqa: BLE001 — carried across
            box["error"] = e
        finally:
            done.set()

    t = threading.Thread(target=_call, daemon=True,
                         name=f"shifu-collective-{tag}")
    global _inflight_seq
    with _inflight_lock:
        _inflight_seq += 1
        key = f"{tag}#{_inflight_seq}"
        _inflight[key] = time.monotonic()
    # open span covering the blocked wait, so a watchdog dump (which
    # cites obs.trace.open_spans) names the stuck collective
    sp = obs_trace.span("dist.collective", tag=tag)
    sp.__enter__()
    t.start()
    try:
        deadline = None if timeout is None else time.monotonic() + timeout
        grace = knob_float("SHIFU_TPU_PREEMPT_GRACE_S")
        last_abort_check = 0.0
        preempt_seen_at = None
        while not done.wait(0.1):
            now = time.monotonic()
            if now - last_abort_check >= 0.5:
                last_abort_check = now
                ab = resilience.check_abort()
                if ab and ab.get("process") != _my_index():
                    raise _abort_error(tag, ab)
                if _observe_preempt(tag):
                    if preempt_seen_at is None:
                        preempt_seen_at = now
                    elif grace is not None and \
                            now - preempt_seen_at > grace:
                        raise resilience.Preempted(
                            f"peer preemption consensus: collective "
                            f"{tag!r} still blocked "
                            f"{now - preempt_seen_at:.1f}s after a "
                            "peer's preempt marker — the peer has "
                            "exited; stopping with the same rc")
            if deadline is not None and now > deadline:
                stuck = inflight_collectives()
                resilience.dump_thread_stacks(
                    f"collective {tag!r} timed out after "
                    f"SHIFU_TPU_BARRIER_TIMEOUT_S={timeout}s "
                    f"(in flight: {stuck})")
                raise DistTimeout(
                    f"collective {tag!r} did not complete within "
                    f"SHIFU_TPU_BARRIER_TIMEOUT_S={timeout}s — a peer "
                    "host likely died or fell behind; in-flight "
                    f"collectives: {stuck}; thread stacks dumped to "
                    "stderr and steps.jsonl")
        if "error" in box:
            raise box["error"]
        # a collective can complete before the first 0.5s poll tick —
        # one final check so even fast collectives observe a peer's
        # preemption and set the local flag for the next boundary
        _observe_preempt(tag)
        return box.get("value")
    finally:
        sp.__exit__(None, None, None)
        with _inflight_lock:
            _inflight.pop(key, None)


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> None:
    """Bring up the multi-host runtime. No-op when single-process or
    already initialized. Env fallbacks: SHIFU_TPU_COORDINATOR,
    SHIFU_TPU_NUM_PROCESSES, SHIFU_TPU_PROCESS_ID (on Cloud TPU these
    resolve automatically from the metadata server).

    SHIFU_TPU_INIT_TIMEOUT_S bounds the coordinator handshake (default:
    JAX's own, ~300s) — a wrong coordinator address or a dead peer then
    surfaces as a clear error naming the address instead of an
    indefinite hang."""
    fault_point("dist.init")
    coordinator_address = coordinator_address or \
        knob_str("SHIFU_TPU_COORDINATOR")
    if num_processes is None:
        num_processes = knob_int("SHIFU_TPU_NUM_PROCESSES")
    if process_id is None:
        process_id = knob_int("SHIFU_TPU_PROCESS_ID")
    if num_processes in (None, 1) and coordinator_address is None:
        return
    kwargs = {}
    timeout_s = knob_float("SHIFU_TPU_INIT_TIMEOUT_S")
    if timeout_s:
        kwargs["initialization_timeout"] = int(timeout_s)
    # the handshake runs under the collective watchdog with its OWN
    # deadline (the init knob + margin, so jax's native timeout error
    # wins when it works) — jax builds whose initialization_timeout
    # does not cover every internal wait can otherwise still hang a
    # pod bring-up forever
    watchdog_s = (timeout_s + 30.0) if timeout_s else None
    try:
        _watched("dist.init", lambda: jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id, **kwargs), timeout_s=watchdog_s)
    except (DistTimeout, DistAborted):
        raise    # already self-describing, with stacks dumped
    except Exception as e:
        raise RuntimeError(
            f"distributed initialize failed (coordinator="
            f"{coordinator_address!r}, num_processes={num_processes}, "
            f"process_id={process_id}"
            + (f", timeout={timeout_s}s" if timeout_s else "")
            + f"): {e} — check SHIFU_TPU_COORDINATOR reachability and "
            "that every process was launched; set "
            "SHIFU_TPU_INIT_TIMEOUT_S to bound the wait") from e
    log.info("distributed: process %d/%d, %d global devices",
             jax.process_index(), jax.process_count(), jax.device_count())


def process_shard() -> tuple:
    """(index, count) for sharded file reads in this process."""
    return jax.process_index(), jax.process_count()


def _multi_process() -> bool:
    """Whether shared-storage writes need the single-writer guard —
    decided WITHOUT initializing a backend when none is up yet.
    `jax.process_index()` lazily creates the default backend, and for
    pure file operations (ColumnConfig writes from `shifu init`) that
    means probing — and possibly hanging on — an unreachable
    accelerator the command never needed.

    More than one process exists only after `jax.distributed` was
    initialized (`initialize()` above does it for every device command
    of a multi-host run), and `jax.distributed.is_initialized()`
    answers that without touching a backend. A FILE-ONLY command on a
    TPU pod (no distributed init) is thus treated as single-process and
    writes identical content from every host without the guard, which
    beats hanging every laptop/CI `init` on an unreachable
    accelerator."""
    return jax.distributed.is_initialized() and jax.process_count() > 1


def is_writer() -> bool:
    """True on the single process allowed to write shared-storage
    outputs (ColumnConfig.json, EvalScore.csv, normalized layouts, …).
    In a multi-host pod every process computes identical results, but
    N concurrent ``open(path, 'w')`` on the same shared file can
    interleave or truncate each other — same guard the streaming
    trainer's checkpoint save uses."""
    return not _multi_process() or jax.process_index() == 0


def writer_barrier(tag: str) -> None:
    """Block until every process reaches this point — hosts must not
    read a shared output file the writer is still producing. No-op
    single-process. Under the watchdog (`_watched`) the wait is
    bounded by SHIFU_TPU_BARRIER_TIMEOUT_S and poisoned by a peer's
    abort marker — a dead or failed peer surfaces as `DistTimeout` /
    `DistAborted` instead of a hang."""
    fault_point("dist.barrier")
    if _multi_process() and jax.process_count() > 1:
        from jax.experimental import multihost_utils
        _watched(tag, lambda: multihost_utils.sync_global_devices(tag))
        # the barrier itself released: a peer may still have published
        # an abort or preemption between our poll ticks — one last
        # check so every host leaves with the same verdict
        from shifu_tpu import resilience
        ab = resilience.check_abort()
        if ab and ab.get("process") != _my_index():
            raise _abort_error(tag, ab)
        _observe_preempt(tag)


@contextmanager
def single_writer(tag: str):
    """`with dist.single_writer("psi") as w:` — yields True on the one
    process allowed to write (process 0), and releases a barrier on
    exit EVEN WHEN THE WRITER RAISES: hosts >= 1 are already parked at
    the barrier, and an unreleased barrier turns one host's error into
    a pod-wide hang (the error itself still propagates on the
    writer). A raising participant first publishes an abort marker so
    blocked peers poison out with the same error (`DistAborted`)
    rather than waiting for the timeout."""
    # the background checkpoint publisher also writes as process 0: a
    # single-writer scope must not overlap an in-flight publish into
    # the same tree (rmtree/os.replace races), so join it first
    try:
        from shifu_tpu.train import checkpoint as _ckpt
        _ckpt.flush_saves(reraise=False)
    except Exception as e:  # pragma: no cover — optional import cycle
        absorbed("dist.ckpt-flush", e)
    try:
        yield is_writer()
    except BaseException as e:
        if _multi_process() and jax.process_count() > 1:
            from shifu_tpu import resilience
            resilience.publish_abort(tag, e, process=_my_index())
        raise
    finally:
        writer_barrier(tag)


def global_row_array(mesh, local_rows: np.ndarray, spec=None):
    """Assemble a process-local row block into the global sharded
    array (each host contributes its file shard's rows). `spec`
    overrides the default rows-on-"data" PartitionSpec. Multi-process,
    the assembly is a collective (every host must call it with the
    same shapes) and runs under the watchdog."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    if spec is None:
        spec = P("data", *([None] * (local_rows.ndim - 1)))
    sharding = NamedSharding(mesh, spec)
    fault_point("dist.allgather")

    def _make():
        return jax.make_array_from_process_local_data(sharding, local_rows)

    if _multi_process() and jax.process_count() > 1:
        return _watched("global_row_array", _make)
    return _make()


# ---------------------------------------------------------------------------
# pod-scale data plane: shard decision + watched host-object collectives
# ---------------------------------------------------------------------------

def data_shard() -> Optional[tuple]:
    """(index, count) when the pod-scale data shard is active, else
    None. Active means: SHIFU_TPU_DATA_SHARD is not "0", a multi-host
    runtime is up, and there is more than one process — the sharded
    readers then stream disjoint row ranges and merge partials through
    the watched collectives below. "0" forces today's replicated-read
    behavior exactly; "auto" (default) and "1" shard whenever the pod
    has peers to shard across. Anything else raises — a typo ("ture")
    or an attempted shard count ("2") silently enabling sharding would
    be indistinguishable from the operator's intent."""
    mode = (knob_str("SHIFU_TPU_DATA_SHARD") or "auto").strip().lower()
    if mode in ("0", "off", "false", "no"):
        return None
    if mode not in ("auto", "1", "on", "true", "yes"):
        raise ValueError(
            f"SHIFU_TPU_DATA_SHARD={mode!r}: want auto (shard when the "
            "pod has peers), 1/on/true/yes (same, asserted) or "
            "0/off/false/no (replicated reads) — the shard count always "
            "comes from jax.process_count()")
    if not _multi_process():
        return None
    count = jax.process_count()
    if count <= 1:
        return None
    return jax.process_index(), count


def stream_timeout_s() -> Optional[float]:
    """Watchdog deadline for the STREAMING data-plane collectives
    (`reader.bcast`, the striped partial merges): between two of these
    a peer legitimately does chunk-sized work — parsing a part file,
    normalizing and writing a chunk's mmaps — so the barrier deadline
    (sized for "everyone arrives together") fires spuriously on a slow
    chunk. SHIFU_TPU_STREAM_TIMEOUT_S when set; else 10× the barrier
    timeout (the peer is provably alive and making per-chunk progress;
    abort/preempt markers still poll at the same cadence); else None."""
    v = knob_float("SHIFU_TPU_STREAM_TIMEOUT_S")
    if v is not None and v > 0:
        return v
    bt = barrier_timeout_s()
    return bt * 10.0 if bt is not None else None


def _exchange_bytes(tag: str, payload: bytes,
                    timeout_s: Optional[float] = None):
    """All-gather one variable-length byte string per process, watched.
    Two fixed-shape collectives: lengths first, then the payloads
    padded to the longest — `process_allgather` needs every process to
    contribute the same shape."""
    from jax.experimental import multihost_utils

    def _gather():
        lens = np.asarray(multihost_utils.process_allgather(
            np.asarray([len(payload)], np.int64))).reshape(-1)
        width = max(int(lens.max()), 1)
        buf = np.zeros(width, np.uint8)
        if payload:
            buf[:len(payload)] = np.frombuffer(payload, np.uint8)
        mat = np.asarray(multihost_utils.process_allgather(buf)) \
            .reshape(len(lens), -1)
        return [mat[p, :int(lens[p])].tobytes() for p in range(len(lens))]

    return _watched(tag, _gather, timeout_s=timeout_s)


def allgather_obj(tag: str, obj, timeout_s: Optional[float] = None):
    """Watched all-gather of one picklable host object per process;
    returns the objects in process order (so a fold over the result is
    deterministic). Single-process: ``[obj]``. This is the primitive
    under every data-plane partial merge; the ``dist.allreduce_tree``
    fault site makes it drillable (oserror/timeout/kill/preempt).
    `timeout_s` overrides the barrier deadline — streaming callers pass
    `stream_timeout_s()` because a peer does per-chunk work between
    their collectives."""
    fault_point("dist.allreduce_tree")
    if not (_multi_process() and jax.process_count() > 1):
        return [obj]
    import pickle
    t0 = time.monotonic()
    payloads = _exchange_bytes(tag, pickle.dumps(obj, protocol=4),
                               timeout_s=timeout_s)
    out = [pickle.loads(p) for p in payloads]
    from shifu_tpu.data import pipeline as _pipe
    _pipe.add_stage_time("dist_merge_s", time.monotonic() - t0)
    _pipe.add_stage_count("dist_merges")
    return out


def _tree_add(a, b):
    if a is None:
        return b
    if b is None:
        return a
    if isinstance(a, dict):
        return {k: _tree_add(a.get(k), b.get(k))
                for k in {**a, **b}}
    if isinstance(a, (list, tuple)):
        return type(a)(_tree_add(x, y) for x, y in zip(a, b))
    return a + b


def allreduce_tree(tag: str, tree):
    """Sum per-host partial sufficient statistics across the pod: a
    watched all-gather of the host trees (dict/list/tuple structure,
    ndarray/number leaves, None = identity) folded in ascending process
    order. Exact for integer leaves (bin counts, confusion cells);
    float leaves must be float64 host accumulators whose sum order the
    caller has already made deterministic — for bitwise parity with
    the sequential path, exchange per-chunk contributions via
    `allgather_obj` and replay them in chunk order instead."""
    parts = allgather_obj(tag, tree)
    acc = parts[0]
    for p in parts[1:]:
        acc = _tree_add(acc, p)
    return acc


def merge_keyed_striped(tag: str, shard: tuple, n_files: int, items,
                        fold, acc=None, extra_fn=None):
    """Bounded-memory ordered-replay merge for the sharded streaming
    passes. `items` yields ``(key, contribution)`` with key =
    ``(file_idx, chunk_idx)`` ascending over THIS host's files
    (``file_idx % count == index``, `iter_raw_table_keyed` ownership).
    Files merge in stripes of `count` (stripe ``s`` covers files
    ``[s·count, (s+1)·count)`` — exactly one file per host per round,
    so parsing stays parallel): each round all-gathers only that
    stripe's per-chunk contributions and folds them in ascending key
    order. Stripes partition the file list contiguously, so the fold
    visits every chunk in the sequential pass's exact order — bitwise
    replay — while each host holds one stripe of contributions instead
    of the whole table (the difference between bounded memory and a
    multi-GB pickle per merge on >RAM datasets).

    ``fold(acc, key, contribution, extra) -> acc``; `extra_fn` (host
    metadata such as the column layout, re-sent every round — a host
    may see its first chunk late) merges to the first non-None in
    (round, process) order. Returns ``(acc, extra)``. Runs on the
    stream deadline (`stream_timeout_s`): hosts parse a file between
    rounds, which the barrier deadline does not budget for."""
    idx, count = shard
    n_stripes = max(-(-n_files // count), 1)
    timeout = stream_timeout_s()
    it = iter(items)
    nxt = next(it, None)
    extra = None
    for s in range(n_stripes):
        batch = []
        while nxt is not None and nxt[0][0] // count == s:
            batch.append(nxt)
            nxt = next(it, None)
        parts = allgather_obj(f"{tag}.stripe{s}",
                              (batch, extra_fn() if extra_fn else None),
                              timeout_s=timeout)
        if extra is None:
            extra = next((e for _b, e in parts if e is not None), None)
        for key, c in sorted((kc for b, _e in parts for kc in b),
                             key=lambda kc: kc[0]):
            acc = fold(acc, key, c, extra)
    if nxt is not None:
        raise RuntimeError(
            f"merge {tag!r}: host {idx} produced chunk key {nxt[0]} "
            f"beyond the declared {n_files}-file range — the file list "
            "changed mid-run?")
    return acc, extra


def broadcast_tree(tag: str, tree):
    """Watched `broadcast_one_to_all`: process 0's pytree of arrays to
    every process (all processes must supply matching shapes/dtypes).
    Single-process: returns ``tree`` unchanged."""
    if not (_multi_process() and jax.process_count() > 1):
        return tree
    from jax.experimental import multihost_utils
    return _watched(
        tag, lambda: multihost_utils.broadcast_one_to_all(tree))
