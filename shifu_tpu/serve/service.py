"""Persistent scorer service: AOT-warmed, micro-batched, in-process.

`ScorerService` owns one `eval.scorer.Scorer` ensemble and a
`MicroBatcher`.  Every micro-batch is padded up the shape-bucket
ladder and scored through `Scorer.score` → `score_matrix` — the exact
code path batch eval uses, including the fused normalize+score Pallas
kernel and bf16 spec metadata — so a served request scored at the
same bucket batch eval lands on is bit-identical to batch eval by
construction; across DIFFERENT buckets XLA's shape-dependent
scheduling bounds the difference at ~1 ulp (see serve/aot.py) — with
one exception on a TPU at default matmul precision: the ONE-row bucket.
Every larger shape takes one bf16 MXU pass and gives the same rows bit
for bit; a one-row contraction is computed in exact f32, so a one-row
request sits a bf16 pass (~1e-3; 1.65e-3 measured on a v5e) from its
batch-eval score.  Two standing caveats: batch-GLOBAL tree-score conversions like MAXMIN are
batch-defined and therefore applied per micro-batch (the default RAW
conversion has no such dependence), and which requests share a
micro-batch depends on arrival timing.

Per-request latency decomposes into queue / pad / h2d / device / d2h:
queue is measured by the batcher, pad is host-side batch assembly,
h2d is an explicit `jax.device_put` of the padded feature block that
the device kernel actually reads — the dense block for an all-NN
ensemble, the raw_dense block for an all-tree ensemble riding the
fused Pallas route (SHIFU_TPU_TREE_FUSED; `make_fused_inputs`
transposes the pre-placed array device-side and
`ops/pallas_trees.predict_ensemble` bins it in-register) — device is
the `Scorer.score` call, and d2h is per-request result extraction.
For mixed ensembles (and tree ensembles on the interpretive XLA
walk) the transfer happens inside `score_matrix` and is accounted
under device.
"""

from __future__ import annotations

import collections
import queue
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from shifu_tpu.config import environment as env
from shifu_tpu.data import pipeline
from shifu_tpu.resilience import make_lock
from shifu_tpu.eval.scorer import Scorer
from shifu_tpu.obs import trace as obs_trace
from shifu_tpu.serve import aot
from shifu_tpu.serve.batcher import MicroBatcher, Request

_BLOCK_KEYS = ("dense", "index", "raw_dense", "raw_codes")


class ScorerService:
    """In-process serving front end; `submit` is thread-safe."""

    def __init__(self, models_dir: Optional[str] = None,
                 model_paths: Optional[List[str]] = None,
                 score_selector: str = "mean",
                 gbt_convert: str = "RAW",
                 norm: Optional[Dict[str, Any]] = None,
                 ladder: Optional[Tuple[int, ...]] = None,
                 max_delay: Optional[float] = None,
                 queue_depth: Optional[int] = None,
                 workspace_root: Optional[str] = None,
                 aot_compile: bool = True,
                 priority: str = "high",
                 metrics_tags: Optional[Dict[str, str]] = None):
        if priority not in ("high", "low"):
            raise ValueError(
                f"priority must be high|low, got {priority!r}")
        self.priority = priority
        self._score_selector = score_selector
        self._gbt_convert = gbt_convert
        # fleet mode labels this service's metric points (model=...)
        self._metrics_tags = dict(metrics_tags or {})
        self._workspace_root = workspace_root
        if workspace_root is not None:
            # a service built through the library (not cli.main) still
            # gets the persistent cache + compile counters; idempotent
            from shifu_tpu import profiling
            profiling.enable_compile_cache()
        if models_dir is not None:
            self.scorer = Scorer.from_dir(models_dir, model_paths,
                                          score_selector=score_selector,
                                          gbt_convert=gbt_convert)
        else:
            self.scorer = Scorer(model_paths or [],
                                 score_selector=score_selector,
                                 gbt_convert=gbt_convert)
        self.norm = norm
        self.ladder = tuple(ladder) if ladder else aot.bucket_ladder()
        self._aot_enabled = aot_compile
        self._aot_executables: Dict[Tuple[int, int], Any] = {}
        # incumbent device param pytrees, keyed like the executables'
        # model index — the swappable half of the AOT artifacts
        self._aot_params: Dict[int, Any] = {}
        self._proto: Optional[Dict[str, np.ndarray]] = None
        self.swaps = 0
        self._batcher = MicroBatcher(self._score_batch,
                                     max_rows=self.ladder[-1],
                                     max_delay=max_delay,
                                     depth=queue_depth)
        self._schema: Optional[frozenset] = None
        self._started = False
        self._warm_s = 0.0
        self._warmed_buckets = 0
        # consumer-thread-appended; stats() reads racily (monitoring)
        self._latencies: collections.deque = collections.deque(maxlen=8192)
        self._schema_lock = make_lock("service.schema")
        # 429s by the rejected request's priority class (the fleet's
        # admission shed bumps "low" here too via note_rejected)
        self.rejected_by_class: Dict[str, int] = {"high": 0, "low": 0}
        self._flush_stop = threading.Event()
        self._flush_thread: Optional[threading.Thread] = None

    # pre-place the padded dense block on device only when every model
    # reads it as-is: an all-NN ensemble with no fused-normalize route
    @property
    def _preplace(self) -> bool:
        return self.norm is None and all(
            kind in ("nn", "lr") for kind, _, _ in self.scorer.models)

    # same for the raw numeric block of an all-tree ensemble on the
    # fused kernel route — predict_ensemble reads it directly, so the
    # placement is the request's real h2d and gets timed as such
    @property
    def _tree_preplace(self) -> bool:
        from shifu_tpu.ops.pallas_trees import tree_fused_mode
        return (self.norm is None and tree_fused_mode() == "pallas"
                and bool(self.scorer.models) and all(
                    kind in ("gbt", "rf")
                    for kind, _, _ in self.scorer.models))

    # -- lifecycle -----------------------------------------------------
    def start(self, proto: Optional[Dict[str, np.ndarray]] = None
              ) -> "ScorerService":
        """Warm every shape bucket, then open the admission queue.
        `proto` is one representative request (row blocks); without
        one, an all-NN ensemble warms from a zeros row and anything
        else warms lazily on first traffic."""
        if self._started:
            return self
        if proto is None:
            proto = self._default_proto()
        if proto:
            t0 = time.monotonic()
            proto = {k: np.asarray(v) for k, v in proto.items()
                     if v is not None}
            self._schema = frozenset(proto)
            self._proto = proto
            if self._aot_enabled and ("dense" in proto
                                      or "raw_dense" in proto):
                self._aot_executables, self._aot_params = aot.aot_compile(
                    self.scorer, proto, self.ladder)
                aot.aot_selfcheck(self._aot_executables, self._aot_params,
                                  self.scorer, proto)
            self._warmed_buckets = aot.warm_scores(
                self._place_and_score, proto, self.ladder)
            self._warm_s = time.monotonic() - t0
            pipeline.add_stage_time("serve_warm_s", self._warm_s)
        self._batcher.start()
        self._started = True
        self._start_metrics_flusher()
        return self

    def close(self) -> None:
        self._flush_stop.set()
        if self._flush_thread is not None:
            self._flush_thread.join(timeout=5.0)
            self._flush_thread = None
        self._flush_metrics()   # final snapshot before teardown
        self._batcher.close()
        self._started = False

    def __enter__(self) -> "ScorerService":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()

    def _default_proto(self) -> Optional[Dict[str, np.ndarray]]:
        for kind, meta, _ in self.scorer.models:
            if kind in ("nn", "lr"):
                dim = int(meta["spec"]["input_dim"])
                return {"dense": np.zeros((1, dim), np.float32)}
        return None

    # -- request path --------------------------------------------------
    def submit_async(self, dense: Optional[np.ndarray] = None,
                     index: Optional[np.ndarray] = None,
                     raw_dense: Optional[np.ndarray] = None,
                     raw_codes: Optional[np.ndarray] = None) -> Request:
        blocks = {"dense": dense, "index": index,
                  "raw_dense": raw_dense, "raw_codes": raw_codes}
        blocks = {k: np.asarray(v) for k, v in blocks.items()
                  if v is not None}
        if not blocks:
            raise ValueError("request carries no feature blocks")
        schema = frozenset(blocks)
        with self._schema_lock:
            if self._schema is None:
                self._schema = schema
            elif schema != self._schema:
                raise ValueError(
                    f"request blocks {sorted(schema)} do not match the "
                    f"service schema {sorted(self._schema)}")
        n = next(iter(blocks.values())).shape[0]
        if any(v.shape[0] != n for v in blocks.values()):
            raise ValueError("feature blocks disagree on row count")
        try:
            return self._batcher.submit(blocks, n)
        except queue.Full:
            self.note_rejected()  # the 429 the front end answers with
            raise

    def note_rejected(self, priority: Optional[str] = None) -> None:
        """Count one 429 against a priority class (default: this
        service's own class)."""
        self.rejected_by_class[priority or self.priority] += 1

    @property
    def _rejected(self) -> int:
        return sum(self.rejected_by_class.values())

    def submit(self, dense: Optional[np.ndarray] = None,
               index: Optional[np.ndarray] = None,
               raw_dense: Optional[np.ndarray] = None,
               raw_codes: Optional[np.ndarray] = None,
               timeout: Optional[float] = 30.0) -> Dict[str, np.ndarray]:
        """Score one request (blocking) → the `Scorer.score` dict
        ({"model0"..,"mean","max","min","median","final"}) sliced to
        this request's rows."""
        return self.submit_async(dense, index, raw_dense,
                                 raw_codes).wait(timeout)

    def submit_timed(self, timeout: Optional[float] = 30.0, **blocks
                     ) -> Tuple[Dict[str, np.ndarray], Dict[str, float]]:
        req = self.submit_async(**blocks)
        return req.wait(timeout), dict(req.timing)

    # -- hot refresh ----------------------------------------------------
    def swap_params(self, models_dir: str,
                    model_paths: Optional[List[str]] = None) -> bool:
        """In-place hot swap: load the challenger ensemble from
        `models_dir` and place its params into the RESIDENT compiled
        executables — no recompile, no restart, no dropped request.

        Structural gate first: same model count, same kinds, same
        NN-family spec, and per-model param pytrees with identical tree
        structure + leaf shapes + dtypes.  Any mismatch returns False
        and mutates NOTHING — the caller falls back to the evict/
        re-warm path.  A candidate that passes is then parity-gated
        through `aot.aot_selfcheck` with the NEW params: the resident
        executables must score them exactly as a cold re-warm would
        (`score_matrix` recomputed with the same params) before the
        swap goes live.  The flip itself is one attribute store of the
        new models list, so a concurrently-scoring batch reads wholly
        old or wholly new params — never a mix.
        """
        import jax
        import jax.numpy as jnp

        challenger = Scorer.from_dir(models_dir, model_paths,
                                     score_selector=self._score_selector,
                                     gbt_convert=self._gbt_convert)
        old = self.scorer.models
        new = challenger.models
        if len(old) != len(new):
            return False
        for (ok_, om, op), (nk, nm, np_) in zip(old, new):
            if ok_ != nk:
                return False
            if ok_ in ("nn", "lr") and om.get("spec") != nm.get("spec"):
                return False
            try:
                ot = jax.tree_util.tree_structure(op)
                nt = jax.tree_util.tree_structure(np_)
            except Exception:  # noqa: BLE001 — unhashable/foreign params
                return False
            if ot != nt:
                return False
            ol = jax.tree_util.tree_leaves(op)
            nl = jax.tree_util.tree_leaves(np_)
            for a, b in zip(ol, nl):
                a, b = np.asarray(a), np.asarray(b)
                if a.shape != b.shape or a.dtype != b.dtype:
                    return False

        # device-place the challenger params for every model the AOT
        # layer compiled; parity-gate through the LIVE executables
        cand: Dict[int, Any] = {}
        for i, (kind, meta, params) in enumerate(new):
            if i in self._aot_params or (self._aot_enabled and
                                         kind in ("nn", "lr",
                                                  "gbt", "rf")):
                cand[i] = jax.tree.map(jnp.asarray, params)
        if self._aot_executables and self._proto is not None \
                and ("dense" in self._proto
                     or "raw_dense" in self._proto):
            check = dict(self._aot_params)
            check.update(cand)
            aot.aot_selfcheck(self._aot_executables, check,
                              self.scorer, self._proto)

        new_list = [(kind, meta, cand.get(i, params))
                    for i, (kind, meta, params) in enumerate(new)]
        # one store — concurrent _score_batch reads old-or-new, never mixed
        self.scorer.models = new_list
        self._aot_params.update(cand)
        self.swaps += 1
        return True

    # -- device consumer (batcher thread) ------------------------------
    def _place_and_score(self, padded: Dict[str, Optional[np.ndarray]]
                         ) -> Tuple[Dict[str, np.ndarray], float]:
        """Place a bucket-padded request on the device and score it →
        (scores, time the h2d finished). Steady traffic AND the warm-up
        go through here, so warm-up compiles exactly what traffic runs —
        including the on-device pad/reshard programs `shard_axis` builds
        for a pre-placed block."""
        padded = dict(padded)
        t_h2d = time.monotonic()
        # the one block every model reads as-is gets pre-placed: `dense`
        # for an all-NN ensemble (score_matrix's shard_axis then moves
        # it onto the data mesh without a host round-trip), `raw_dense`
        # for an all-tree ensemble on the fused kernel route (binned
        # in-register; the small host-mapped categorical codes stay
        # host-side). First leased device — a sliced serving node stays
        # on its slice. The placement is the request's real h2d.
        key = "dense" if self._preplace else \
            "raw_dense" if self._tree_preplace else None
        if key in padded:
            import jax
            from shifu_tpu.parallel import mesh as mesh_mod
            padded[key] = jax.device_put(
                np.asarray(padded[key], np.float32),
                mesh_mod.leased_devices()[0])
            jax.block_until_ready(padded[key])
            t_h2d = time.monotonic()

        # tree ensembles may serve raw blocks only; score_matrix's tree
        # path reads raw_dense, so any row-aligned block satisfies the
        # positional dense argument
        out = self.scorer.score(
            dense=padded.get("dense", padded.get("raw_dense")),
            index=padded.get("index"),
            raw_dense=padded.get("raw_dense"),
            raw_codes=padded.get("raw_codes"),
            norm=self.norm)
        return out, t_h2d

    def _score_batch(self, batch: List[Request]) -> None:
        t0 = time.monotonic()
        n = sum(r.n for r in batch)
        keys = sorted(batch[0].blocks)
        concat = {k: (batch[0].blocks[k] if len(batch) == 1
                      else np.concatenate([r.blocks[k] for r in batch]))
                  for k in keys}
        bucket = aot.bucket_for(n, self.ladder)
        padded = aot.pad_blocks(concat, bucket)
        t_pad = time.monotonic()

        out, t_h2d = self._place_and_score(padded)
        t_dev = time.monotonic()

        off, t_prev = 0, t_dev
        for r in batch:
            r.timing.update(
                pad_s=t_pad - t0, h2d_s=t_h2d - t_pad,
                device_s=t_dev - t_h2d)
            sliced = {k: np.ascontiguousarray(v[off:off + r.n])
                      for k, v in out.items()}
            off += r.n
            t_done = time.monotonic()
            r.timing["d2h_s"] = t_done - t_prev
            r.timing["total_s"] = t_done - r.t_submit
            self._latencies.append(r.timing["total_s"])
            if obs_trace.active():
                # one span per request, children cut from the exact
                # timestamps the timing splits are computed from
                rid = obs_trace.record_span(
                    "serve.request", r.t_submit, t_done,
                    track="serve", rows=r.n)
                obs_trace.record_span("serve.queue", r.t_submit,
                                      r.t_batched, parent=rid,
                                      track="serve")
                obs_trace.record_span("serve.pad", t0, t_pad,
                                      parent=rid, track="serve")
                obs_trace.record_span("serve.h2d", t_pad, t_h2d,
                                      parent=rid, track="serve")
                obs_trace.record_span("serve.device", t_h2d, t_dev,
                                      parent=rid, track="serve")
                obs_trace.record_span("serve.d2h", t_prev, t_done,
                                      parent=rid, track="serve")
            t_prev = t_done
            r.resolve(sliced)
        t_d2h = time.monotonic()

        pipeline.add_stage_time("serve_pad_s", t_pad - t0)
        pipeline.add_stage_time("serve_h2d_s", t_h2d - t_pad)
        pipeline.add_stage_time("serve_device_s", t_dev - t_h2d)
        pipeline.add_stage_time("serve_d2h_s", t_d2h - t_dev)

    # -- monitoring ----------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        lat = np.asarray(self._latencies, np.float64)
        pct = {}
        if lat.size:
            p50, p95, p99 = np.percentile(lat, [50, 95, 99])
            pct = {"p50_ms": p50 * 1e3, "p95_ms": p95 * 1e3,
                   "p99_ms": p99 * 1e3}
        return {
            "models": [kind for kind, _, _ in self.scorer.models],
            "ladder": list(self.ladder),
            "priority": self.priority,
            "warm_s": self._warm_s,
            "warmed_buckets": self._warmed_buckets,
            "aot_executables": len(self._aot_executables),
            "swaps": self.swaps,
            "rejected": self._rejected,
            "rejected_by_class": dict(self.rejected_by_class),
            "latency": pct,
            "batcher": self._batcher.stats(),
        }

    # -- health plane --------------------------------------------------
    def _start_metrics_flusher(self) -> None:
        """Background thread: snapshot stats() into the persistent
        metrics store every SHIFU_TPU_METRICS_FLUSH_S seconds, so
        long-lived serve processes leave a time-series behind (batch
        steps get theirs from step_metrics exit). No-op unless
        SHIFU_TPU_METRICS=1 and the service knows its workspace."""
        from shifu_tpu.obs.health import store as health_store
        if self._workspace_root is None or \
                not health_store.metrics_enabled() or \
                self._flush_thread is not None:
            return
        period = float(env.knob_float("SHIFU_TPU_METRICS_FLUSH_S"))
        self._flush_stop.clear()

        def loop() -> None:
            while not self._flush_stop.wait(period):
                self._flush_metrics()

        self._flush_thread = threading.Thread(
            target=loop, name="serve-metrics-flush", daemon=True)
        self._flush_thread.start()

    def _flush_metrics(self) -> None:
        """One stats() snapshot → serve.* gauges; absorbed — a metrics
        failure can never degrade serving."""
        try:
            from shifu_tpu.obs.health import store as health_store
            if self._workspace_root is None or \
                    not health_store.metrics_enabled():
                return
            st = health_store.store(self._workspace_root)
            snap = self.stats()
            tags = self._metrics_tags
            for k, v in snap["latency"].items():
                st.emit(f"serve.{k}", round(float(v), 4), **tags)
            b = snap["batcher"]
            for k in ("requests", "batches", "rows", "queued_now",
                      "occupancy_mean", "rows_per_batch"):
                if isinstance(b.get(k), (int, float)):
                    st.emit(f"serve.{k}", b[k], **tags)
            st.emit("serve.rejected", self._rejected, kind="counter",
                    **tags)
            for cls, n in self.rejected_by_class.items():
                st.emit("serve.rejected_by_class", n, kind="counter",
                        priority=cls, **tags)
            admitted = b.get("requests", 0) or 0
            denom = admitted + self._rejected
            st.emit("serve.reject_rate",
                    round(self._rejected / denom, 6) if denom else 0.0,
                    **tags)
            st.flush()
        except Exception as e:  # noqa: BLE001 — absorbed by design
            import logging
            logging.getLogger(__name__).warning(
                "serve metrics flush failed (absorbed): %s", e)

    def health_state(self) -> Optional[Dict[str, Any]]:
        """The workspace's SLO state (obs.health.slo.health_state),
        or None when the service has no workspace or the read fails —
        /healthz stays a liveness check either way."""
        if self._workspace_root is None:
            return None
        try:
            from shifu_tpu.obs.health import slo as slo_mod
            return slo_mod.health_state(self._workspace_root)
        except Exception:  # noqa: BLE001 — liveness must not break
            return None
