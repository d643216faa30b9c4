"""Drift-breach → warm-start retrain → eval guardrail → atomic promote
→ in-place hot swap → instant rollback: the closed loop of ROADMAP
item 1.

`RefreshController` plugs into the watch loop's `on_breach` seam
(obs/health/watch.py). A breach of any SLO schedules ONE refresh run:

  schedule   clone the model set into a challenger workspace under
             ``tmp/refresh/run****`` (parent ModelConfig with paths
             absolutized, ColumnConfig copied), seed it with the
             incumbent's model files and flip ``train#isContinuous``
             on, and point its dataPath at the accumulated drift
             window (the rows the watch loop saw arrive — capped at
             ``SHIFU_TPU_REFRESH_WINDOW_ROWS``; no window yet → the
             full training table). With an ingest row log bound
             (`shifu watch --ingest`), the window is instead read
             from the ``refresh`` consumer offset and materialized
             byte-for-byte; the exact (segment, offset) range lands
             in the published manifest (``refresh.ingest_window``)
             and the offset commits only after the materialization.
             `fault_point("refresh.schedule")`.

  train      norm + train inside the clone, in process — the
             continuous-training path restores the incumbent params
             (``_continuous_init`` / the tree warm start) and takes
             incremental epochs over the drifted data only.

  guardrail  score the incumbent AND the challenger over the SAME
             held-out eval set (`_build_eval_dataset` built once, two
             `Scorer`s through `_score_dataset`) and compare weighted
             AUC. The challenger is REFUSED unless
             ``challenger_auc >= incumbent_auc - SHIFU_TPU_REFRESH_
             TOLERANCE``. Either way the decision lands in the
             metrics store as a ``refresh`` event (visible in
             `shifu health` / `shifu top`).
             `fault_point("refresh.guardrail")`; an eval fault HOLDS —
             the incumbent keeps serving, HEAD never moved.

  promote    `registry.publish` — the two-rename atomic commit — with
             the guardrail verdict recorded in the manifest.
             `fault_point("refresh.promote")`: a kill before commit 1
             leaves only a scrubbed ``.tmp``; between the renames, a
             complete-but-unreferenced version dir and the old HEAD.

  swap       `FleetService.swap_in_place` — parity-gated in-place
             param swap into the resident AOT executables, zero
             recompiles; structural change falls back to evict +
             re-warm. A swap failure AFTER publish triggers the
             instant rollback: `registry.rollback` + a re-swap to
             re-pin the incumbent (span ``refresh.rollback``).

Every phase is span-traced (``refresh.run`` / ``refresh.guardrail`` /
``refresh.rollback`` + the fleet's ``fleet.swap``) and stage-timed
(``refresh_train_s`` / ``refresh_guardrail_s`` / ``refresh_promote_s``),
so `shifu top` shows drift → retrain → guardrail → promote live. What
the retrain and the guardrail compile counts as
``background_compile_s`` / ``background_compile_cache_hits`` /
``background_compile_cache_misses`` (`profiling.background_compiles`);
the bare compile counters stay the serving
path's — the swap runs outside the scope, so a swap that recompiles
still trips the zero-recompile gate.

HYSTERESIS: breaches arriving while a refresh is in flight or within
``SHIFU_TPU_REFRESH_COOLDOWN_S`` of the last run are COALESCED — one
retrain absorbs the storm; the coalesced count is an event + counter
in the store (``shifu health`` shows it) and in `stats()`.
"""

from __future__ import annotations

import logging
import os
import shutil
import time
from typing import Any, Dict, List, Optional

from shifu_tpu import profiling
from shifu_tpu.config.environment import knob_float, knob_int
from shifu_tpu.obs import trace as obs_trace
from shifu_tpu.obs.health import store as health_store

log = logging.getLogger(__name__)


class GuardrailHold(RuntimeError):
    """The challenger was refused (metric regressed beyond tolerance
    or its eval faulted) — promotion did not happen, the incumbent
    keeps serving. Raised only out of `refresh_once`; the controller
    absorbs it into a `held` outcome."""


class RefreshController:
    """Owns the breach→promote pipeline for ONE model set.

    `ctx` is the incumbent's ProcessorContext. `registry_root` +
    `model_name` bind promotion to a registry model (None → the
    guardrail still runs, but the verdict is report-only: nothing to
    promote into). `fleet` is the live FleetService to hot-swap (None
    → publish moves HEAD; the next serve restart picks it up).
    `post_train` is a test seam called with the challenger workspace
    dir after training, before the guardrail (the sabotage drill).
    `canary` switches promotion to LIVE mode: instead of the offline
    eval guardrail, the trained challenger goes through the staged
    shadow→canary controller (obs/health/canary.py) and the verdict
    comes from real traffic — pass True for knob-driven defaults or a
    dict of CanaryController overrides (shadow_pct, canary_pct,
    min_requests, window_s, psi_max, p99_factor, slo_p99_ms, poll_s).
    Live mode requires registry_root + model_name + fleet.
    """

    def __init__(self, ctx, registry_root: Optional[str] = None,
                 model_name: Optional[str] = None,
                 fleet=None, eval_name: Optional[str] = None,
                 cooldown_s: Optional[float] = None,
                 tolerance: Optional[float] = None,
                 window_rows: Optional[int] = None,
                 post_train=None, ingest_log=None, canary=None):
        self.ctx = ctx
        # durable row log (data/ingest.py): when bound, the challenger
        # trains on a window read from the `refresh` consumer offset,
        # materialized byte-for-byte and recorded in the publish
        # manifest as a replayable (segment, offset) range
        if isinstance(ingest_log, str):
            from shifu_tpu.data.ingest import RowLog
            ingest_log = RowLog(ingest_log)
        self.ingest_log = ingest_log
        self.registry_root = registry_root
        self.model_name = model_name
        self.fleet = fleet
        self.eval_name = eval_name
        self.cooldown_s = cooldown_s if cooldown_s is not None \
            else knob_float("SHIFU_TPU_REFRESH_COOLDOWN_S")
        self.tolerance = tolerance if tolerance is not None \
            else knob_float("SHIFU_TPU_REFRESH_TOLERANCE")
        self.window_rows = int(window_rows if window_rows is not None
                               else knob_int("SHIFU_TPU_REFRESH_WINDOW_ROWS"))
        self.post_train = post_train
        self.canary = canary
        self.runs = 0
        self.promoted = 0
        self.held = 0
        self.rolled_back = 0
        self.coalesced = 0
        self.last_outcome: Optional[str] = None
        self._window_frames: List[Any] = []
        self._window_len = 0
        self._in_flight = False
        self._last_done: Optional[float] = None

    # -- window accumulation (fed by the watch loop) --------------------

    def note_window(self, df) -> None:
        """Remember the newest arriving rows as retrain fodder; keeps
        at most `window_rows` of tail (oldest frames dropped whole)."""
        if df is None or not len(df):
            return
        self._window_frames.append(df)
        self._window_len += len(df)
        while self._window_frames and \
                self._window_len - len(self._window_frames[0]) \
                >= self.window_rows:
            self._window_len -= len(self._window_frames[0])
            self._window_frames.pop(0)

    def _take_window(self):
        if not self._window_frames:
            return None
        import pandas as pd
        df = pd.concat(self._window_frames, ignore_index=True)
        if len(df) > self.window_rows:
            df = df.iloc[-self.window_rows:].reset_index(drop=True)
        self._window_frames, self._window_len = [], 0
        return df

    # -- breach entry point ----------------------------------------------

    def handle_breach(self, record: Dict) -> str:
        """One SLO transition into breach. Returns the outcome:
        promoted | held | rolled_back | coalesced | failed."""
        st = health_store.store(self.ctx.path_finder.root)
        now = time.monotonic()
        if self._in_flight or (self._last_done is not None
                               and now - self._last_done < self.cooldown_s):
            self.coalesced += 1
            st.counter("refresh.coalesced")
            st.event("refresh", phase="coalesced",
                     slo=record.get("slo", "?"), count=self.coalesced)
            log.info("refresh: breach of %r coalesced (%s, %d so far)",
                     record.get("slo"),
                     "in flight" if self._in_flight else "cooldown",
                     self.coalesced)
            return "coalesced"
        self._in_flight = True
        try:
            outcome = self.refresh_once(record)
        except GuardrailHold as e:
            outcome = "held"
            self.held += 1
            log.warning("refresh: challenger held: %s", e)
        except Exception as e:  # noqa: BLE001 — a failed refresh must
            # never kill the watch loop; the incumbent keeps serving
            outcome = "failed"
            st.event("refresh", phase="failed", error=str(e)[:200])
            log.warning("refresh: run failed (incumbent keeps serving): %s",
                        e)
        finally:
            self._in_flight = False
            self._last_done = time.monotonic()
        self.last_outcome = outcome
        return outcome

    # -- the pipeline ------------------------------------------------------

    def incumbent_models_dir(self) -> str:
        """Registry HEAD when bound (deployment source of truth), else
        the workspace's own models/."""
        if self.registry_root and self.model_name:
            from shifu_tpu import registry
            try:
                _, vdir, _ = registry.resolve(self.registry_root,
                                              self.model_name)
                return vdir
            except FileNotFoundError:
                pass
        return self.ctx.path_finder.models_path()

    def refresh_once(self, record: Dict) -> str:
        """The full schedule→train→guardrail→promote→swap run. Raises
        GuardrailHold when the challenger is refused; any other
        exception means the run failed before changing anything the
        incumbent depends on."""
        from shifu_tpu import resilience
        from shifu_tpu.data import pipeline as data_pipeline

        st = health_store.store(self.ctx.path_finder.root)
        t_breach = time.monotonic()
        self.runs += 1
        run_name = f"run{self.runs:04d}"
        with obs_trace.span("refresh.run", slo=record.get("slo", "?"),
                            run=run_name):
            # -- schedule: challenger workspace --------------------------
            resilience.fault_point("refresh.schedule")
            window, win = None, None
            if self.ingest_log is not None:
                from shifu_tpu.data.ingest import REFRESH_CONSUMER
                win = self.ingest_log.read_window(
                    REFRESH_CONSUMER, max_rows=self.window_rows)
            if win is None:
                window = self._take_window()
            w_rows = win.rows if win is not None \
                else (0 if window is None else len(window))
            st.event("refresh", phase="scheduled",
                     slo=record.get("slo", "?"), run=run_name,
                     window_rows=w_rows)
            clone = self._prepare_challenger(run_name, window,
                                             raw_window=win)
            if win is not None:
                # the training-set materialization IS this consumer's
                # downstream commit point: the window now exists
                # byte-for-byte in the clone, so the offset may move —
                # a crash before this line replays the window, never
                # skips it
                self.ingest_log.commit(REFRESH_CONSUMER, win.end)

            # -- train: warm-start incremental epochs --------------------
            # the retrain and the guardrail eval compile their own
            # programs at this window's row counts; counted under
            # background_compile_* so the serving gate (bare counters:
            # the batchers, the swap below) reads only the serving path
            t0 = time.monotonic()
            with profiling.background_compiles():
                self._train_challenger(clone)
            data_pipeline.add_stage_time("refresh_train_s",
                                         time.monotonic() - t0)
            if self.post_train is not None:
                self.post_train(clone)

            # -- live mode: verdict from real traffic, not the eval ------
            if self.canary and self.registry_root and self.model_name \
                    and self.fleet is not None:
                return self._canary_promote(clone, run_name, record,
                                            win, st, t_breach)

            # -- guardrail: challenger vs incumbent on held-out eval -----
            t0 = time.monotonic()
            with profiling.background_compiles():
                verdict = self.guardrail(os.path.join(clone, "models"))
            data_pipeline.add_stage_time("refresh_guardrail_s",
                                         time.monotonic() - t0)
            st.emit("refresh.guardrail_delta", verdict["delta"],
                    kind="gauge", run=run_name)
            st.event("refresh", phase="guardrail", run=run_name,
                     decision=verdict["decision"],
                     incumbent=round(verdict["incumbent"], 6),
                     challenger=round(verdict["challenger"], 6),
                     tolerance=self.tolerance)
            if verdict["decision"] != "promote":
                raise GuardrailHold(
                    f"challenger {verdict['challenger']:.6f} vs incumbent "
                    f"{verdict['incumbent']:.6f} (tolerance "
                    f"{self.tolerance}): {verdict['reason']}")

            if not (self.registry_root and self.model_name):
                # report-only mode: verdict recorded, nothing to promote
                self.promoted += 1
                st.event("refresh", phase="promoted", run=run_name,
                         version="(unbound)", swap="none")
                return "promoted"

            # -- promote: two-rename atomic registry commit ---------------
            from shifu_tpu import registry
            t0 = time.monotonic()
            resilience.fault_point("refresh.promote")
            prev_head = registry.head(self.registry_root, self.model_name)
            refresh_block = {
                "run": run_name, "slo": record.get("slo", "?"),
                "incumbent_auc": verdict["incumbent"],
                "challenger_auc": verdict["challenger"],
                "refreshed_from": prev_head}
            if win is not None:
                # the exact (segment, offset) range retrained on —
                # `RowLog.read_range(start, end)` re-reads it bitwise
                refresh_block["ingest_window"] = dict(
                    win.range_record(), log=self.ingest_log.root)
            version = registry.publish(
                self.registry_root, self.model_name,
                os.path.join(clone, "models"),
                extra={"refresh": refresh_block})
            data_pipeline.add_stage_time("refresh_promote_s",
                                         time.monotonic() - t0)

            # -- swap: in-place into the running fleet --------------------
            swap = "none"
            if self.fleet is not None:
                try:
                    swap = self.fleet.swap_in_place(self.model_name)
                except Exception as e:  # noqa: BLE001 — any swap failure
                    # (parity gate, injected fault) → instant rollback
                    self._rollback(version, prev_head, run_name, e)
                    self.rolled_back += 1
                    st.event("refresh", phase="rolled_back", run=run_name,
                             version=version, to=prev_head or "?",
                             error=str(e)[:200])
                    return "rolled_back"
            self.promoted += 1
            wall = time.monotonic() - t_breach
            st.emit("refresh.breach_to_promoted_s", wall, kind="gauge",
                    run=run_name)
            st.event("refresh", phase="promoted", run=run_name,
                     version=version, swap=swap,
                     breach_to_promoted_s=round(wall, 3))
            log.info("refresh: %s promoted as %s/%s (swap=%s, %.2fs "
                     "breach→promoted)", run_name, self.model_name,
                     version, swap, wall)
            return "promoted"

    def _canary_promote(self, clone: str, run_name: str, record: Dict,
                        win, st, t_breach: float) -> str:
        """Live promotion path: hand the trained challenger to the
        staged shadow→canary controller and map its traffic-derived
        verdict onto this controller's outcomes. The offline eval
        never runs — decide() reads the arms."""
        from shifu_tpu import registry
        from shifu_tpu.obs.health.canary import CanaryController

        prev_head = registry.head(self.registry_root, self.model_name)
        refresh_block = {"run": run_name, "slo": record.get("slo", "?"),
                         "refreshed_from": prev_head, "mode": "live"}
        if win is not None:
            refresh_block["ingest_window"] = dict(
                win.range_record(), log=self.ingest_log.root)
        overrides = self.canary if isinstance(self.canary, dict) else {}
        ctl = CanaryController(
            self.fleet, self.registry_root, self.model_name,
            store_root=self.ctx.path_finder.root, **overrides)
        result = ctl.run(os.path.join(clone, "models"), run_name,
                         refresh_block=refresh_block)
        if result["outcome"] == "promoted":
            self.promoted += 1
            wall = time.monotonic() - t_breach
            st.emit("refresh.breach_to_promoted_s", wall, kind="gauge",
                    run=run_name)
            st.event("refresh", phase="promoted", run=run_name,
                     version=result["version"],
                     swap=result.get("swap", "none"),
                     mode="live", breach_to_promoted_s=round(wall, 3))
            log.info("refresh: %s live-promoted as %s/%s (%.2fs "
                     "breach→promoted)", run_name, self.model_name,
                     result["version"], wall)
            return "promoted"
        self.rolled_back += 1
        st.event("refresh", phase="rolled_back", run=run_name,
                 version=result["version"],
                 to=result.get("prev_head") or "?", mode="live",
                 error=result["verdict"].get("reason", "")[:200])
        return "rolled_back"

    # -- phases ------------------------------------------------------------

    def _prepare_challenger(self, run_name: str, window,
                            raw_window=None) -> str:
        """Materialize the challenger workspace: parent ModelConfig
        (paths absolutized) with isContinuous on, ColumnConfig copied,
        the incumbent's model files seeded into models/ for the warm
        start, and — when a drift window accumulated — its own private
        dataPath holding exactly those rows (`raw_window`, an ingest
        `Window`, is written byte-for-byte from the log's raw lines so
        the recorded offset range IS the training data). Re-running
        after a kill rebuilds from scratch (the clone is disposable
        state)."""
        import json as _json

        from shifu_tpu.pipeline.nodes import _absolutize
        from shifu_tpu.resilience import atomic_write

        root = self.ctx.path_finder.root
        clone = os.path.join(root, "tmp", "refresh", run_name)
        if os.path.exists(clone):
            shutil.rmtree(clone)   # rerun recovers: stale attempt gone
        os.makedirs(os.path.join(clone, "tmp"), exist_ok=True)

        with open(os.path.join(root, "ModelConfig.json"),
                  encoding="utf-8") as f:
            raw = _json.load(f)
        raw = _absolutize(raw, root)
        raw.setdefault("train", {})["isContinuous"] = True
        raw.setdefault("basic", {})["name"] = \
            f"{raw.get('basic', {}).get('name', 'model')}:{run_name}"
        if raw_window is not None and raw_window.rows:
            raw["dataSet"]["dataPath"], raw["dataSet"]["headerPath"] = \
                self._write_window_raw(clone, raw_window.lines,
                                       self.ingest_log.header,
                                       self.ingest_log.delimiter)
            raw["dataSet"]["dataDelimiter"] = self.ingest_log.delimiter
            raw["dataSet"]["headerDelimiter"] = self.ingest_log.delimiter
        elif window is not None and len(window):
            raw["dataSet"]["dataPath"], raw["dataSet"]["headerPath"] = \
                self._write_window(clone, window,
                                   raw["dataSet"].get("dataDelimiter", "|"))
        with atomic_write(os.path.join(clone, "ModelConfig.json")) as f:
            _json.dump(raw, f, indent=2)

        cc_src = os.path.join(root, "ColumnConfig.json")
        if os.path.exists(cc_src):
            shutil.copyfile(cc_src, os.path.join(clone,
                                                 "ColumnConfig.json"))
        # seed the warm start: incumbent model files become the clone's
        # models/ so the continuous-training path restores them
        inc = self.incumbent_models_dir()
        dst = os.path.join(clone, "models")
        os.makedirs(dst, exist_ok=True)
        from shifu_tpu.models import spec as spec_mod
        for src in spec_mod.list_models(inc):
            shutil.copy2(src, os.path.join(dst, os.path.basename(src)))
        return clone

    @staticmethod
    def _write_window_raw(clone: str, lines, header, delim: str):
        """The ingest window as a private raw table, written from the
        log's raw lines UNMODIFIED — `sha256(part-00000)` equals the
        hash of `RowLog.read_range` over the recorded range, so the
        promoted model's training data audits byte-for-byte."""
        from shifu_tpu.resilience import atomic_write
        wdir = os.path.join(clone, "window")
        os.makedirs(wdir, exist_ok=True)
        header_path = os.path.join(wdir, ".pig_header")
        with atomic_write(header_path, "w", encoding="utf-8") as f:
            f.write(delim.join(str(c) for c in header) + "\n")
        with atomic_write(os.path.join(wdir, "part-00000"), "w",
                          encoding="utf-8") as f:
            for line in lines:
                f.write(line + "\n")
        return wdir, header_path

    @staticmethod
    def _write_window(clone: str, window, delim: str):
        """The drift window as a private raw table (pipe-delimited text
        with a .pig_header, the same layout the parent reads)."""
        from shifu_tpu.resilience import atomic_write
        wdir = os.path.join(clone, "window")
        os.makedirs(wdir, exist_ok=True)
        header_path = os.path.join(wdir, ".pig_header")
        with atomic_write(header_path, "w", encoding="utf-8") as f:
            f.write(delim.join(str(c) for c in window.columns) + "\n")
        vals = window.astype(object).where(window.notna(), "")
        with atomic_write(os.path.join(wdir, "part-00000"), "w",
                          encoding="utf-8") as f:
            for row in vals.itertuples(index=False):
                f.write(delim.join(str(v) for v in row) + "\n")
        return wdir, header_path

    def _train_challenger(self, clone: str) -> None:
        """norm + train inside the clone, in process. Norm re-bins the
        window rows with the PARENT's frozen ColumnConfig stats (the
        clone copied it), so the challenger sees the drifted data
        through the same feature space the incumbent was trained on."""
        from shifu_tpu.processor import norm as norm_proc
        from shifu_tpu.processor import train as train_proc
        from shifu_tpu.processor.base import ProcessorContext
        cctx = ProcessorContext.load(clone)
        rc = norm_proc.run(cctx)
        if rc:
            raise RuntimeError(f"refresh: challenger norm failed (rc={rc})")
        cctx = ProcessorContext.load(clone)   # re-read post-norm configs
        rc = train_proc.run(cctx)
        if rc:
            raise RuntimeError(f"refresh: challenger train failed (rc={rc})")

    def guardrail(self, challenger_dir: str) -> Dict[str, Any]:
        """Score incumbent vs challenger over the SAME held-out eval
        set and decide. The eval dataset is built ONCE; both scorers
        run through the normal `_score_dataset` path (normalization,
        padding, selector) so the comparison is apples-to-apples.
        Any fault in here → `hold` (raised as GuardrailHold by the
        caller's decision check or propagated and absorbed into
        `failed`) — a broken eval NEVER promotes."""
        import numpy as np

        from shifu_tpu import resilience
        from shifu_tpu.eval.scorer import Scorer
        from shifu_tpu.ops import metrics as ops_metrics
        from shifu_tpu.processor.eval import (_build_eval_dataset,
                                              _eval_by_name, _score_dataset)

        with obs_trace.span("refresh.guardrail"):
            resilience.fault_point("refresh.guardrail")
            ec = _eval_by_name(self.ctx, self.eval_name)[0]
            dset, cols = _build_eval_dataset(self.ctx, ec)
            mc = self.ctx.model_config
            kw = dict(score_selector=ec.performanceScoreSelector,
                      gbt_convert=ec.gbtScoreConvertStrategy)
            scores = {}
            for side, mdir in (("incumbent", self.incumbent_models_dir()),
                               ("challenger", challenger_dir)):
                scorer = Scorer.from_dir(mdir, **kw)
                out = _score_dataset(mc, scorer, dset, cols)
                labels = np.asarray(dset.tags, dtype=np.float32)
                weights = np.asarray(dset.weights, dtype=np.float32)
                scores[side] = float(ops_metrics.weighted_auc(
                    np.asarray(out["final"], dtype=np.float32),
                    labels, weights))
            decision, reason = self.decide(scores["incumbent"],
                                           scores["challenger"],
                                           self.tolerance)
            return {"decision": decision, "reason": reason,
                    "incumbent": scores["incumbent"],
                    "challenger": scores["challenger"],
                    "delta": scores["challenger"] - scores["incumbent"]}

    @staticmethod
    def decide(incumbent: float, challenger: float, tolerance: float):
        """The promotion rule, bare: promote when the challenger
        improved or regressed no more than `tolerance` on the
        guardrail metric; hold otherwise."""
        delta = challenger - incumbent
        if delta >= 0:
            return "promote", "challenger improved"
        if -delta <= tolerance:
            return "promote", "within tolerance"
        return "hold", "regressed beyond tolerance"

    def _rollback(self, version: str, prev_head: Optional[str],
                  run_name: str, err: Exception) -> None:
        """Instant rollback after a failed swap: HEAD back to the
        incumbent, then a re-swap so the fleet is provably pinned to
        it (absorbed — the fleet never mutated on the failed swap, so
        even a failed re-swap leaves the incumbent serving)."""
        from shifu_tpu import registry
        with obs_trace.span("refresh.rollback", run=run_name,
                            version=version):
            log.warning("refresh: swap of %s failed (%s) — rolling back "
                        "HEAD to %s", version, err, prev_head)
            registry.rollback(self.registry_root, self.model_name,
                              to=prev_head)
            if self.fleet is not None:
                try:
                    self.fleet.swap_in_place(self.model_name)
                except Exception as e:  # noqa: BLE001 — absorbed: the
                    # failed forward swap never mutated the fleet
                    log.warning("refresh: re-swap after rollback failed "
                                "(incumbent still resident): %s", e)

    # -- observability ------------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        return {"runs": self.runs, "promoted": self.promoted,
                "held": self.held, "rolled_back": self.rolled_back,
                "coalesced": self.coalesced,
                "window_rows_pending": self._window_len,
                "last_outcome": self.last_outcome}
