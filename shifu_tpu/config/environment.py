"""Global key-value config tier: `$SHIFU_HOME/conf/shifuconfig`.

The reference loads a properties file chain into a process-global
`Environment` at JVM start (`util/Environment.java:95-111`): in order
`$SHIFU_HOME/conf/shifuconfig`, `$SHIFU_HOME/conf/shifu.config`,
`$SHIFU_HOME/shifu.config`, `/etc/shifuconfig`, `~/.shifuconfig` —
each later file overriding earlier ones — and CLI `-Dkey=value`
overrides the lot (`ShifuCLI.cleanArgs:468-492`).

Here the same tiers land in `os.environ`, which is what every knob in
this codebase already reads. Layering, lowest to highest precedence:

    shifuconfig file chain  <  pre-existing process environment  <  -D

(The process environment outranks the files so that
`SHIFU_TPU_HIST=xla shifu_tpu train ...` keeps working regardless of
what a site-wide /etc/shifuconfig says; `-D` is applied by the CLI
*after* this loader and clobbers unconditionally, matching the
reference's override order.)
"""

from __future__ import annotations

import logging
import os
from typing import Dict, List, NamedTuple, Optional

log = logging.getLogger(__name__)


def _parse_properties(path: str) -> Dict[str, str]:
    """Minimal java-properties reader: `k=v` / `k:v` lines, `#`/`!`
    comments, blank lines skipped. No line continuations or unicode
    escapes — shifuconfig files in the wild are plain `key=value`."""
    out: Dict[str, str] = {}
    with open(path, encoding="utf-8") as f:
        for raw in f:
            line = raw.strip()
            if not line or line[0] in "#!":
                continue
            # java.util.Properties: the FIRST '=' or ':' terminates the
            # key (so 'opts: -Ddir=/tmp' keys on 'opts', not the '=')
            cuts = [i for i in (line.find("="), line.find(":")) if i >= 0]
            if cuts:
                i = min(cuts)
                out[line[:i].strip()] = line[i + 1:].strip()
            else:
                log.warning("shifuconfig %s: ignoring malformed line %r",
                            path, line)
    return out


def config_file_chain(shifu_home: Optional[str] = None) -> List[str]:
    """The reference's file precedence chain, earliest-loaded first
    (later files override earlier ones, `Environment.loadShifuConfig`)."""
    home = shifu_home if shifu_home is not None \
        else os.environ.get("SHIFU_HOME", "")
    chain = []
    if home:
        chain += [os.path.join(home, "conf", "shifuconfig"),
                  os.path.join(home, "conf", "shifu.config"),
                  os.path.join(home, "shifu.config")]
    chain.append(os.path.join(os.sep, "etc", "shifuconfig"))
    chain.append(os.path.join(os.path.expanduser("~"), ".shifuconfig"))
    return chain


def load_shifuconfig(shifu_home: Optional[str] = None) -> Dict[str, str]:
    """Merge the shifuconfig tier into `os.environ` (without clobbering
    keys the environment already defines) and return the merged
    file-level key-values. Called once at CLI start, before `-D`
    overrides are applied."""
    merged: Dict[str, str] = {}
    for path in config_file_chain(shifu_home):
        try:
            if os.path.isfile(path):
                merged.update(_parse_properties(path))
        except OSError as e:
            log.warning("could not read shifuconfig %s: %s", path, e)
    for k, v in merged.items():
        os.environ.setdefault(k, v)
    return merged


# ---------------------------------------------------------------------------
# central knob registry
# ---------------------------------------------------------------------------
#
# Every SHIFU_TPU_* environment knob the codebase reads is DECLARED here
# with its type, documented default and one-line doc. The static
# analyzer (`python -m shifu_tpu.analysis`) enforces the contract both
# ways: an os.environ/getenv read of an undeclared SHIFU_TPU_* name is a
# lint finding (`undeclared-knob`), and a declared knob no scanned file
# references is a dead registry entry. `shifu knobs` prints this table
# with current values; `python -m shifu_tpu.analysis --knobs-md`
# renders it as markdown (KNOBS.md).
#
# `default=None` means "unset = auto/off" — the reading site owns the
# contextual fallback (e.g. SHIFU_TPU_MESH_DEVICES unset = all devices).

class Knob(NamedTuple):
    name: str
    type: str            # int | float | str | bool | flag
    default: object      # documented default; None = unset (auto/off)
    doc: str


KNOBS: "Dict[str, Knob]" = {}


def _declare(name: str, type_: str, default, doc: str) -> None:
    KNOBS[name] = Knob(name, type_, default, doc)


# --- resilience / retries / faults ---
_declare("SHIFU_TPU_RETRY_ATTEMPTS", "int", 4,
         "max attempts per retried remote-I/O call")
_declare("SHIFU_TPU_RETRY_BASE_S", "float", 0.05,
         "first retry backoff delay (seconds)")
_declare("SHIFU_TPU_RETRY_MAX_S", "float", 2.0,
         "retry backoff cap (seconds)")
_declare("SHIFU_TPU_FAULT", "str", None,
         "deterministic fault spec <site>:<kind>:<nth>[;...]")
_declare("SHIFU_TPU_RESUME", "flag", "0",
         "1 = skip steps whose completion manifest matches inputs")
_declare("SHIFU_TPU_DAG_WORKERS", "int", 2,
         "pipeline DAG scheduler, timeshared mode: concurrent "
         "device-using nodes (host-only nodes are admitted "
         "immediately; sliced mode admits by device-slice leases)")
_declare("SHIFU_TPU_DAG_SLICE", "str", "auto",
         "DAG device-slice leases: auto = lease disjoint slices to "
         "concurrent device nodes when the pool holds >1 device, "
         "1 = force slicing, 0 = legacy timeshared admission")
_declare("SHIFU_TPU_DAG_DEVICES", "int", None,
         "device pool size the DAG slice allocator leases from "
         "(None = probe the runtime via parallel.mesh; set it on "
         "hardware so scheduling never probes a flaky accelerator)")
_declare("SHIFU_TPU_DAG_DEMAND_CAP", "int", None,
         "cap every DAG node's effective device demand (demand "
         "override — A/B runs force equal-sized meshes with it)")
_declare("SHIFU_TPU_MAX_RESTARTS", "int", 0,
         "supervised in-process restarts around the train step")
_declare("SHIFU_TPU_ABORT_DIR", "str", None,
         "abort-marker directory override (normally set by step_guard)")
_declare("SHIFU_TPU_LOCKCHECK", "flag", "0",
         "1 = instrumented locks record acquisition order and fail "
         "the run on a lock-order cycle (analysis.lockcheck)")
# --- checkpoint / overlap / compile cache ---
_declare("SHIFU_TPU_CKPT_ASYNC", "flag", "1",
         "1 = background checkpoint writer (snapshot on-thread, "
         "serialize+publish off-thread); 0 = fully synchronous saves")
_declare("SHIFU_TPU_H2D_DOUBLE_BUFFER", "flag", "1",
         "1 = place chunk N+1 on device while chunk N computes "
         "(auto-disabled on the cpu backend unless set explicitly)")
_declare("SHIFU_TPU_COMPILE_CACHE_DIR", "str", None,
         "persistent XLA compilation cache dir when "
         "JAX_COMPILATION_CACHE_DIR is NOT set (that one, set from "
         "outside, always wins); unset = the fixed <checkout>/.jax_cache, "
         "0/off/none = disabled")
_declare("SHIFU_TPU_COMPILE_CACHE_MIN_S", "float", 0.0,
         "minimum compile seconds before a kernel is cached "
         "(jax_persistent_cache_min_compile_time_secs)")
_declare("SHIFU_TPU_COMPILE_CACHE_SHARED", "str", None,
         "shared (possibly scheme://) compile-cache dir mirrored into "
         "the local cache at startup and published back with atomic "
         "single-writer-safe commits; a scheme:// "
         "SHIFU_TPU_COMPILE_CACHE_DIR routes here automatically")
# --- distributed runtime ---
_declare("SHIFU_TPU_COORDINATOR", "str", None,
         "coordinator address for jax.distributed.initialize")
_declare("SHIFU_TPU_NUM_PROCESSES", "int", None,
         "process count for multi-host init (None = auto)")
_declare("SHIFU_TPU_PROCESS_ID", "int", None,
         "this process's index for multi-host init (None = auto)")
_declare("SHIFU_TPU_INIT_TIMEOUT_S", "float", None,
         "bound on the jax.distributed coordinator handshake")
_declare("SHIFU_TPU_BARRIER_TIMEOUT_S", "float", None,
         "collective watchdog deadline; unset = block forever")
_declare("SHIFU_TPU_STREAM_TIMEOUT_S", "float", None,
         "watchdog deadline for streaming data-plane collectives "
         "(reader.bcast, striped partial merges) where a peer does "
         "chunk-sized work between rounds; unset = 10x the barrier "
         "timeout")
_declare("SHIFU_TPU_MESH_DEVICES", "int", None,
         "cap the device count in the default mesh (None = all)")
_declare("SHIFU_TPU_DEVICE_SLICE", "str", None,
         "comma-separated device ids leased to THIS process by the "
         "DAG scheduler; parallel.mesh.leased_devices filters every "
         "mesh build to the slice (exported by run_dag, not hand-set)")
_declare("SHIFU_TPU_MESH_MODEL", "int", 1,
         "devices on the 'model' mesh axis (WDL/MTL table sharding)")
_declare("SHIFU_TPU_MESH_RULES", "str", None,
         "logical→physical axis overrides 'logical=axis[,...]' "
         "(empty axis = replicate); unset = rows=data, hidden/vocab/"
         "task=model")
_declare("SHIFU_TPU_PREEMPT_GRACE_S", "float", 15.0,
         "after observing a peer's preempt marker inside a watched "
         "collective, seconds to wait for the collective before "
         "raising Preempted (rc 75) directly")
# --- input pipeline ---
_declare("SHIFU_TPU_PREFETCH_DEPTH", "int", 2,
         "chunks buffered ahead of the consumer; 0 = sequential")
_declare("SHIFU_TPU_PREFETCH_WORKERS", "int", 2,
         "host-assembly threads for map_prefetch; 0 = sequential")
_declare("SHIFU_TPU_NATIVE_READER", "bool", "1",
         "use the native C fast reader when the .so is present")
_declare("SHIFU_TPU_DATA_SHARD", "str", "auto",
         "pod-scale data shard: auto/1 = split stats/norm/psi/"
         "correlation/eval reads across hosts, 0 = replicated reads; "
         "other values raise. Sharded reads always use the pandas "
         "parser, so bitwise parity vs an unsharded run needs "
         "SHIFU_TPU_NATIVE_READER=0 on the unsharded side")
# --- streaming chunk triggers ---
_declare("SHIFU_TPU_STATS_CHUNK_ROWS", "int", None,
         "explicit stats streaming chunk rows; 0 forces resident")
_declare("SHIFU_TPU_STATS_STREAM_BYTES", "int", 2 * 1024 ** 3,
         "raw-bytes threshold that auto-triggers streaming stats")
_declare("SHIFU_TPU_NORM_CHUNK_ROWS", "int", None,
         "explicit norm streaming chunk rows; 0 forces resident")
_declare("SHIFU_TPU_NORM_STREAM_BYTES", "int", 2 * 1024 ** 3,
         "raw-bytes threshold that auto-triggers streaming norm")
_declare("SHIFU_TPU_EVAL_CHUNK_ROWS", "int", None,
         "explicit eval streaming chunk rows; 0 forces resident")
_declare("SHIFU_TPU_EVAL_STREAM_BYTES", "int", 2 * 1024 ** 3,
         "raw-bytes threshold that auto-triggers streaming eval")
_declare("SHIFU_TPU_ANALYSIS_CHUNK_ROWS", "int", None,
         "explicit analysis-step chunk rows; 0 forces resident")
_declare("SHIFU_TPU_ANALYSIS_STREAM_BYTES", "int", 2 * 1024 ** 3,
         "raw-bytes threshold that auto-triggers sampled analysis")
_declare("SHIFU_TPU_ANALYSIS_MAX_ROWS", "int", 2_000_000,
         "row cap for the sampled analysis frame (varselect)")
# --- device compute ---
_declare("SHIFU_TPU_HIST", "str", "auto",
         "histogram kernel route: auto | pallas | xla")
_declare("SHIFU_TPU_HIST_PRECISION", "str", None,
         "'highest' switches the pallas histogram to f32-exact")
_declare("SHIFU_TPU_HIST_SUBTRACT", "bool", "1",
         "sibling-subtraction trick in GBT histogram builds")
_declare("SHIFU_TPU_HIST_VMEM_MB", "int", 64,
         "VMEM budget for pallas histogram tiling — the tiles are "
         "derived from it AND the kernels are compiled with it as "
         "their VMEM limit")
_declare("SHIFU_TPU_GBT_SCAN_GROUP", "int", 0,
         "trees per lax.scan group in GBT build; 0 = no grouping")
_declare("SHIFU_TPU_NN_COMPUTE", "str", "float32",
         "NN forward/backward compute dtype (float32 | bfloat16)")
_declare("SHIFU_TPU_COMPUTE_DTYPE", "str", None,
         "default compute dtype for NN/WDL/MTL forward+backward "
         "(float32 | bfloat16); params/optimizer state stay f32 and "
         "matmuls accumulate in f32. Per-model train params and "
         "SHIFU_TPU_NN_COMPUTE override it")
_declare("SHIFU_TPU_HIST_FUSED", "bool", "0",
         "1 = GBT level builds bin numeric values inside the histogram "
         "kernel (no materialized bin-index matrix); needs FusedBins "
         "inputs from gbdt.make_fused_inputs")
_declare("SHIFU_TPU_SCORE_FUSED", "str", "auto",
         "fused normalize+first-matmul scoring kernel route: "
         "auto | pallas | xla")
_declare("SHIFU_TPU_SPLIT_FUSED", "str", "auto",
         "fused GBT split-search kernel route (cumsum+gain+argmax in "
         "one pallas kernel): auto | pallas | xla")
_declare("SHIFU_TPU_TREE_FUSED", "str", "auto",
         "fused GBT/RF ensemble-inference kernel route (in-register "
         "binning + whole-ensemble breadth-first walk + convert in "
         "one pallas kernel): auto | pallas | xla")
_declare("SHIFU_TPU_TREE_VMEM_MB", "int", 64,
         "VMEM budget for the fused tree-inference kernel's row/tree "
         "tiling (pallas_trees._derive_tiles); also its compiled "
         "VMEM limit")
_declare("SHIFU_TPU_GBT_RESIDENT_STATE", "str", "auto",
         "streaming GBT row-state tier: 1 keeps node/pred/grad/hess as "
         "device arrays (zero host syncs per level, one per round), 0 "
         "forces the host-numpy state path, auto picks by the "
         "SHIFU_TPU_GBT_STATE_BUDGET_MB fit")
_declare("SHIFU_TPU_GBT_STATE_BUDGET_MB", "int", 2048,
         "HBM budget for resident streaming-GBT row state; auto mode "
         "goes resident when ~24 B/train row + ~12 B/val row fits")
# --- serving plane ---
_declare("SHIFU_TPU_SERVE_BUCKETS", "str", "1,8,64,512",
         "padded-row shape-bucket ladder for the serving plane and "
         "chunked eval scoring (comma-separated ascending row counts; "
         "ragged batches pad up to the nearest bucket, sizes beyond "
         "the top bucket pad to its next doubling)")
_declare("SHIFU_TPU_SERVE_MAX_DELAY_MS", "float", 2.0,
         "micro-batcher admission deadline: a queued request waits at "
         "most this long for co-riders before its batch is scored")
_declare("SHIFU_TPU_SERVE_QUEUE_DEPTH", "int", 1024,
         "bounded admission-queue depth for the scorer service; a "
         "full queue rejects submits instead of buffering unbounded")
_declare("SHIFU_TPU_SERVE_PORT", "int", 8488,
         "HTTP/JSON listener port for `shifu serve` (0 = ephemeral)")
_declare("SHIFU_TPU_EVAL_PAD_BUCKETS", "bool", "1",
         "1 = chunked eval scoring pads ragged chunks up to the "
         "SHIFU_TPU_SERVE_BUCKETS ladder so the final short chunk "
         "reuses an already-compiled executable instead of compiling "
         "its own")
# --- model fleet (registry + multi-tenant serving) ---
_declare("SHIFU_TPU_REGISTRY_KEEP", "int", 3,
         "registry gc retention: versions kept per model (the HEAD "
         "version is always kept regardless)")
_declare("SHIFU_TPU_FLEET_HBM_MB", "int", 4096,
         "device-HBM budget for resident fleet models (manifest param "
         "bytes + bucket-ladder working set per model); exceeding it "
         "LRU-evicts the coldest resident model back to host")
_declare("SHIFU_TPU_FLEET_SLO_P99_MS", "float", 50.0,
         "high-priority p99 latency SLO (ms): admission sheds "
         "low-priority load at 429 above it, and the SLO autotuner "
         "steers each model's admission deadline toward it")
_declare("SHIFU_TPU_FLEET_SHED_WINDOW", "int", 64,
         "recent high-priority request latencies the fleet admission "
         "controller computes its rolling p99 over")
_declare("SHIFU_TPU_CKPT_SLOTS", "int", 1,
         "staged async checkpoint writes allowed in flight; >1 lets "
         "very short save intervals overlap serializes instead of "
         "joining the previous write at each save")
# --- remote fs ---
_declare("SHIFU_TPU_FS_CACHE_TYPE", "str", "readahead",
         "fsspec cache_type hint for remote streaming opens "
         "(readahead | bytes | block | none)")
_declare("SHIFU_TPU_FS_BLOCK_SIZE", "int", 4 * 1024 * 1024,
         "fsspec block_size hint (bytes) for remote streaming opens; "
         "0 = leave the filesystem default")
# --- export ---
_declare("SHIFU_TPU_UME_EXPORTER", "str", None,
         "pkg.module:Class hook for `export -t ume` bundles")
# --- observability / trace plane ---
_declare("SHIFU_TPU_TRACE", "flag", "0",
         "1 = also record host spans (obs.trace) into the ring buffer "
         "and export a merged Chrome-trace JSON per step; unset/0 = a "
         "span is a jax.profiler annotation only (seen by an open "
         "profiler session, ~1 us otherwise)")
_declare("SHIFU_TPU_TRACE_BUF", "int", 4096,
         "span ring-buffer capacity per process; overflow drops the "
         "oldest span and counts it in the steps.jsonl trace block")
_declare("SHIFU_TPU_TRACE_DIR", "str", None,
         "trace workspace for this run's span files; normally unset "
         "(the coordinator derives tmp/trace/<run_id> and exports it "
         "so DAG subprocess nodes land their spans in the same merge)")
# --- observability / health plane ---
_declare("SHIFU_TPU_METRICS", "flag", "0",
         "1 = persist metric points to tmp/metrics/metrics.jsonl "
         "(step snapshots, drift, SLO health); unset/0 = no files "
         "written (reads still work)")
_declare("SHIFU_TPU_METRICS_ROLLUP", "int", 4 * 1024 * 1024,
         "metrics.jsonl size (bytes) that triggers rollup compaction "
         "(older half aggregated, recent half kept raw, atomic "
         "rewrite); 0 = never compact")
_declare("SHIFU_TPU_METRICS_FLUSH_S", "float", 30.0,
         "period of the serving plane's background metrics flush "
         "(serve.* gauges from ScorerService.stats)")
_declare("SHIFU_TPU_WATCH_INTERVAL_S", "float", 30.0,
         "tick period of the `shifu watch --monitor-only` loop")
_declare("SHIFU_TPU_SLO_FILE", "str", None,
         "path to slo.json; unset = <model set>/slo.json when present, "
         "else the built-in default guardrails (obs/health/slo.py)")
_declare("SHIFU_TPU_DRIFT_THRESHOLD", "float", 0.2,
         "per-feature PSI above which a window emits a `drift` event "
         "(0.2 = the conventional 'significant shift' cutoff)")
_declare("SHIFU_TPU_ALERT_WEBHOOK", "str", None,
         "URL the webhook alert sink POSTs SLO transition records to; "
         "unset = sink disabled")
_declare("SHIFU_TPU_ALERT_WEBHOOK_TIMEOUT_S", "float", 3.0,
         "per-attempt connect+read timeout of the webhook alert POST "
         "(bounded so a dead webhook can never stall a watch tick; "
         "retried with resilience backoff, then absorbed)")
_declare("SHIFU_TPU_REFRESH_WINDOW_ROWS", "int", 100_000,
         "max drifted-window rows the refresh controller keeps (newest "
         "kept) as the incremental-training window a breach retrains "
         "on")
_declare("SHIFU_TPU_REFRESH_TOLERANCE", "float", 0.005,
         "eval-guardrail tolerance: a challenger whose guardrail "
         "metric (AUC) is below incumbent - tolerance is HELD, not "
         "promoted; within-tolerance or better promotes")
_declare("SHIFU_TPU_REFRESH_COOLDOWN_S", "float", 900.0,
         "min seconds between breach-scheduled refreshes; breaches "
         "during an in-flight refresh or inside the cooldown are "
         "coalesced (counted, visible in `shifu health`), so a "
         "flapping PSI signal cannot stack retrains")
_declare("SHIFU_TPU_INGEST_SEGMENT_ROWS", "int", 4096,
         "rows a row-log partition buffers before its open segment "
         "seals into an immutable seg-*.rows file (data/ingest.py; "
         "smaller = lower latency to readers, more segment files)")
_declare("SHIFU_TPU_INGEST_SEGMENT_AGE_S", "float", 30.0,
         "max seconds a non-empty open row-log segment may buffer "
         "before the next append seals it regardless of row count, "
         "bounding how stale a slow trickle can keep readers")
_declare("SHIFU_TPU_SHADOW_PCT", "float", 0.0,
         "fraction of live requests mirrored to a challenger arm "
         "during the shadow phase (response discarded, latency + "
         "score sketch recorded per arm); 0 = shadow plane off "
         "unless a canary run sets it live")
_declare("SHIFU_TPU_CANARY_PCT", "float", 0.05,
         "fraction of live requests the canary phase routes to the "
         "challenger arm (deterministic per-request assignment; the "
         "rest stay on the incumbent primary)")
_declare("SHIFU_TPU_SHADOW_QUEUE", "int", 64,
         "bounded depth of the shadow mirror queue; a full queue "
         "DROPS the mirror (drop-counted) instead of slowing the "
         "primary request path")
_declare("SHIFU_TPU_CANARY_MIN_REQUESTS", "int", 32,
         "min scored requests PER ARM before a canary phase may "
         "decide (shadow → canary and canary → verdict both wait "
         "for this much live evidence)")
_declare("SHIFU_TPU_CANARY_WINDOW_S", "float", 60.0,
         "max seconds a canary phase waits for its per-arm request "
         "quorum; expiry without quorum rolls the challenger back "
         "(no evidence ⇒ no promotion)")
_declare("SHIFU_TPU_CANARY_PSI_MAX", "float", 0.25,
         "max score-distribution PSI between the incumbent and "
         "challenger arms a live verdict may promote through "
         "(above = the challenger scores a different population)")
_declare("SHIFU_TPU_CANARY_P99_FACTOR", "float", 1.5,
         "max challenger-arm p99 as a multiple of the incumbent "
         "arm's p99 during canary; above = SLO breach, automatic "
         "rollback")
_declare("SHIFU_TPU_FLEET_REFRESH_BUDGET", "int", 1,
         "max tenant refreshes a fleet drift tick may schedule — a "
         "breach storm (N tenants drifting at once) defers the rest "
         "to later ticks instead of launching N concurrent retrains")
_declare("SHIFU_TPU_INGEST_WINDOW_ROWS", "int", 65_536,
         "max rows one `shifu watch --ingest` tick consumes from the "
         "row log per read_window (the drift window size cap; the "
         "rest stays committed for the next tick)")


# ---------------------------------------------------------------------------
# Java-style property keys (shifuconfig compatibility surface)
# ---------------------------------------------------------------------------
# The reference reads dotted `shifu.*` properties from shifuconfig /
# -D system properties (util/Environment.java); a few of those keys are
# honored here verbatim for drop-in compatibility. Every such key MUST
# be declared in this map — the `java-property-key` lint rule rejects
# ad-hoc `shifu.*` string literals outside config/ so the legacy
# surface cannot silently sprawl (same philosophy as KNOBS above).
JAVA_PROPS: Dict[str, str] = {
    "shifu.analysis.chunkRows":
        "chunk size override for the exact streaming analysis passes",
    "shifu.eval.chunkRows": "chunk size override for streaming eval",
    "shifu.norm.chunkRows": "chunk size override for streaming norm",
    "shifu.precision.type": "output float precision for norm records",
    "shifu.stats.chunkRows": "chunk size override for streaming stats",
    "shifu.varsel.reuse.model":
        "true = reuse the trained probe model across varselect steps",
}


def _require(name: str) -> Knob:
    k = KNOBS.get(name)
    if k is None:
        raise KeyError(
            f"{name} is not declared in the knob registry "
            "(shifu_tpu/config/environment.py) — declare it there; the "
            "static analyzer rejects undeclared SHIFU_TPU_* reads")
    return k


def knob_raw(name: str) -> Optional[str]:
    """The raw environment string for a DECLARED knob, or None when
    unset. The one sanctioned os.environ read for SHIFU_TPU_* names."""
    _require(name)
    return os.environ.get(name)


def knob_is_set(name: str) -> bool:
    v = knob_raw(name)
    return v is not None and v.strip() != ""


def knob_int(name: str, default: Optional[int] = None) -> Optional[int]:
    """Declared knob as int; a malformed value falls back to the
    registry default (matching the historical _env_int semantics —
    a typo'd knob must not crash a multi-day run)."""
    k = _require(name)
    raw = os.environ.get(name)
    fallback = default if default is not None else k.default
    if raw is None or raw.strip() == "":
        return fallback
    try:
        return int(float(raw))
    except ValueError:
        log.warning("ignoring malformed %s=%r (want int); using %r",
                    name, raw, fallback)
        return fallback


def knob_float(name: str,
               default: Optional[float] = None) -> Optional[float]:
    k = _require(name)
    raw = os.environ.get(name)
    fallback = default if default is not None else k.default
    if raw is None or raw.strip() == "":
        return fallback
    try:
        return float(raw)
    except ValueError:
        log.warning("ignoring malformed %s=%r (want float); using %r",
                    name, raw, fallback)
        return fallback


def knob_str(name: str, default: Optional[str] = None) -> Optional[str]:
    k = _require(name)
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default if default is not None else k.default
    return raw


def knob_bool(name: str, default: Optional[bool] = None) -> bool:
    """bool/flag knobs: "0"/"false"/"no"/"off" (any case) are False,
    anything else set is True; unset uses the registry default."""
    k = _require(name)
    raw = os.environ.get(name)
    if raw is None or raw.strip() == "":
        raw = str(k.default if default is None else default)
    return raw.strip().lower() not in ("0", "false", "no", "off", "none")


def knobs_rows() -> List[dict]:
    """One row per declared knob: name, type, default, current value
    (unset → ''), doc — the `shifu knobs` table."""
    rows = []
    for k in sorted(KNOBS.values()):
        cur = os.environ.get(k.name)
        rows.append({"name": k.name, "type": k.type,
                     "default": "" if k.default is None else str(k.default),
                     "current": "" if cur is None else cur,
                     "doc": k.doc})
    return rows


def knobs_markdown() -> str:
    """The knob reference table as markdown (KNOBS.md;
    `python -m shifu_tpu.analysis --knobs-md`)."""
    out = ["# SHIFU_TPU_* knob reference",
           "",
           "Auto-generated by `python -m shifu_tpu.analysis --knobs-md`"
           " from the registry in `shifu_tpu/config/environment.py`.",
           "",
           "| Knob | Type | Default | Doc |",
           "|---|---|---|---|"]
    for k in sorted(KNOBS.values()):
        default = "*(unset)*" if k.default is None else f"`{k.default}`"
        out.append(f"| `{k.name}` | {k.type} | {default} | {k.doc} |")
    return "\n".join(out) + "\n"
