#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the product's main path runs
on the chip: `init → stats → norm → train → eval → serve`, NN then GBT,
through `shifu_tpu.cli.main` and the serving classes `cmd_serve` builds,
all in THIS one process (a chip belongs to one process at a time).

    python3 chip_smoke.py                 # one TPU chip, HIGGS-shaped 1M rows
    python3 chip_smoke.py --chips 4       # ONLY the 4-device-mesh phase vs 1
    JAX_PLATFORMS=cpu python3 chip_smoke.py --rehearse   # tiny CPU rehearsal

One JSON line per phase; on success the LAST line is exactly
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`
as jax reports the device. Without `--rehearse` a platform other than
"tpu" fails before the first phase. Any phase that raises ends the run
non-zero: there is no handler around a phase.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import time
import urllib.error
import urllib.request

# What the checks hold the run to. Each bound sits one to two orders of
# magnitude above what the v5e showed (PERF.md, PR 21), and below what a
# kernel that rounds or routes differently from its reference gives.
AUC_FLOOR = 0.75        # seeded data: the Bayes-optimal AUC is ~0.85
# eval (fused Pallas kernels) vs the plain XLA route on the same 100k
# rows. NN: on the v5e a default-precision f32 matmul is ONE bf16 MXU
# pass, in XLA and in `_score_kernel` alike: both sit ~2e-3 from a
# float64 forward and 6.4e-7 from each other (measured), because they
# round the same operands at the same points. The bound holds the
# kernel to that: same rounding as XLA, not merely the same precision
# class. GBT: routing is integer-exact on every route, each row lands
# in the same leaves; only the f32 order of the per-tree sum and exp()
# differ — measured 1.2e-6.
NN_REF_TOL = 1e-4
GBT_SCORE_TOL = 1e-5
AUC_TOL = 5e-4          # AUCs measured equal to 5 digits
# served vs eval on the same rows. GBT serves through the same fused
# kernel as eval — measured 2.1e-7, same bound as above. NN: the server
# scores the normalized block through `nn.forward` at its bucket shape.
# A bucket of 8 rows or more goes through the same bf16 MXU pass as
# eval and gives the same rows bit for bit whatever the shape, so those
# requests are held to the reference bound (measured: <= 1.8e-7 for the
# 3..512-row requests). The ONE-row bucket is not an MXU matmul: XLA
# computes it in exact f32 (1e-6 from float64, measured), so it sits a
# bf16 pass's error away from eval — measured 1.65e-3, on that size
# alone; that bucket alone gets the class bound.
NN_SERVE_TOL = NN_REF_TOL
NN_SERVE_ONE_ROW_TOL = 5e-3
# 4-device vs 1-device training: the gradient mean / histogram sum is a
# psum whose f32 order differs, nothing else. tests/test_parallel.py
# holds the 8-vs-1 CPU runs to rtol 2e-3 on weights and 1e-3 on
# validation error; here max |Δweight| measured 2.7e-4, ΔAUC 1e-6.
MESH_AUC_TOL = 1e-3
MESH_WEIGHT_TOL = 2e-3
# GBT trees: per-shard histograms psum to the one-device histogram up to
# f32 order, so the same splits win except where two gains tie at that
# precision. Measured: 0 of 2540 decisions differ. The issue asks for
# identical trees; a couple of exact-tie flips is what
# tests/test_parallel.py allows the 8-vs-1 CPU build, and no more.
MESH_MAX_SPLIT_FLIPS = 2
# the size of the run is part of what it proves, so it is not an option:
# only `--rehearse` (never on a TPU, never reporting one) runs smaller
N_COLS = 28                                  # HIGGS-shaped
ROWS, EVAL_ROWS = 1_000_000, 100_000
REHEARSE_ROWS, REHEARSE_EVAL_ROWS = 4_000, 1_500
EPOCHS, TREES, DEPTH = 20, 20, 6
BINS = 64                                    # 63 value bins + missing
SERVE_SIZES = (1, 3, 8, 13, 64, 100, 512)
SERVE_PASSES = 4                             # steady passes after warm-up


def emit(**rec) -> None:
    print(json.dumps(rec), flush=True)


# ---------------------------------------------------------------------------
# data: a HIGGS-shaped table from --seed
# ---------------------------------------------------------------------------

def _frame(rng, n: int):
    import numpy as np
    x = rng.normal(0.0, 1.0, (n, N_COLS)).astype(np.float32)
    w = np.linspace(1.0, 0.2, 8, dtype=np.float32)
    logit = (x[:, :8] @ w + 0.9 * x[:, 8] * x[:, 9]
             + 0.6 * (x[:, 10] ** 2 - 1.0) - 0.4 * np.abs(x[:, 11]))
    y = rng.random(n) < 1.0 / (1.0 + np.exp(-1.2 * logit))
    miss = rng.random((n, 4)) < 0.005          # columns 20..23 carry "?"
    return x, y, miss


def _write_part(path: str, x, y, miss) -> None:
    import numpy as np
    import pandas as pd
    x = x.astype(np.float64)
    x[:, 20:20 + miss.shape[1]][miss] = np.nan     # written as "?"
    df = pd.DataFrame(x, columns=[f"f{j}" for j in range(N_COLS)])
    df["target"] = np.where(y, "1", "0")
    df.to_csv(path, sep="|", header=False, index=False,
              float_format="%.5f", na_rep="?")


def write_model_set(root: str, seed: int, n_train: int,
                    n_eval: int) -> None:
    import numpy as np
    rng = np.random.default_rng(seed)
    header = "|".join([f"f{j}" for j in range(N_COLS)] + ["target"])
    dirs = {"data": n_train, "evaldata": n_eval}
    for sub, n in dirs.items():
        d = os.path.join(root, sub)
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, ".pig_header"), "w") as f:
            f.write(header + "\n")
        part, done = 0, 0
        while done < n:                       # 250k-row parts bound memory
            m = min(250_000, n - done)
            _write_part(os.path.join(d, f"part-{part:05d}"), *_frame(rng, m))
            part, done = part + 1, done + m
    os.makedirs(os.path.join(root, "columns"), exist_ok=True)
    for name in ("meta.column.names", "categorical.column.names"):
        open(os.path.join(root, "columns", name), "w").close()

    def dataset(sub):
        d = os.path.join(root, sub)
        return {"source": "LOCAL", "dataPath": d, "dataDelimiter": "|",
                "headerPath": os.path.join(d, ".pig_header"),
                "headerDelimiter": "|", "filterExpressions": "",
                "weightColumnName": "", "targetColumnName": "target",
                "posTags": ["1"], "negTags": ["0"],
                "missingOrInvalidValues": ["", "?", "null"]}

    ds = dataset("data")
    ds["metaColumnNameFile"] = os.path.join(root, "columns",
                                            "meta.column.names")
    ds["categoricalColumnNameFile"] = os.path.join(
        root, "columns", "categorical.column.names")
    mc = {
        "basic": {"name": "ChipSmoke", "author": "chip_smoke",
                  "description": "", "version": "0.1.0", "runMode": "LOCAL",
                  "postTrainOn": False, "customPaths": {}},
        "dataSet": ds,
        # 63 value bins + the shared missing bin = the 64-bin histograms
        "stats": {"maxNumBin": BINS - 1, "binningMethod": "EqualPositive",
                  "sampleRate": 1.0, "sampleNegOnly": False,
                  "binningAlgorithm": "SPDTI", "psiColumnName": ""},
        "varSelect": {"forceEnable": False, "forceSelectColumnNameFile": "",
                      "forceRemoveColumnNameFile": "", "filterEnable": True,
                      "filterNum": 200, "filterBy": "KS",
                      "wrapperEnabled": False, "wrapperNum": 50,
                      "wrapperRatio": 0.05, "wrapperBy": "S",
                      "missingRateThreshold": 0.98, "filterBySE": True,
                      "params": None},
        "normalize": {"stdDevCutOff": 4.0, "sampleRate": 1.0,
                      "sampleNegOnly": False, "normType": "ZSCALE"},
        "train": {"baggingNum": 1, "baggingWithReplacement": False,
                  "baggingSampleRate": 1.0, "validSetRate": 0.1,
                  "numTrainEpochs": EPOCHS, "epochsPerIteration": 1,
                  "trainOnDisk": False, "isContinuous": False,
                  "workerThreadCount": 4, "algorithm": "NN",
                  "multiClassifyMethod": "NATIVE", "params": NN_PARAMS,
                  "customPaths": {}},
        "evals": [{"name": "Eval1", "dataSet": dataset("evaldata"),
                   "performanceBucketNum": 10,
                   "performanceScoreSelector": "mean",
                   "scoreMetaColumnNameFile": "", "customPaths": {}}],
    }
    with open(os.path.join(root, "ModelConfig.json"), "w") as f:
        json.dump(mc, f, indent=2)


# 28 → 64 → 1: the reference's flagship narrow shape (ROADMAP S6)
NN_PARAMS = {"NumHiddenLayers": 1, "ActivationFunc": ["tanh"],
             "NumHiddenNodes": [64], "RegularizedConstant": 0.0,
             "LearningRate": 0.05, "Propagation": "ADAM"}


GBT_PARAMS = {"TreeNum": TREES, "MaxDepth": DEPTH, "LearningRate": 0.2,
              "Loss": "log", "FeatureSubsetStrategy": "ALL",
              "MinInstancesPerNode": 5, "Impurity": "variance"}


def set_algorithm(root: str, algorithm: str, params: dict) -> None:
    path = os.path.join(root, "ModelConfig.json")
    with open(path) as f:
        mc = json.load(f)
    mc["train"]["algorithm"] = algorithm
    mc["train"]["params"] = params
    with open(path, "w") as f:
        json.dump(mc, f, indent=2)


# ---------------------------------------------------------------------------
# driving the CLI, and reading what it recorded
# ---------------------------------------------------------------------------

class Steps:
    """Runs CLI commands in-process and folds the `steps.jsonl` records
    they append (wall, compile seconds, cache hits/misses) per phase."""

    def __init__(self, root: str):
        self.root = root
        self.path = os.path.join(root, "tmp", "metrics", "steps.jsonl")
        self.seen = 0

    def run(self, *cmd: str) -> None:
        from shifu_tpu.cli import main
        rc = main(["--dir", self.root, *cmd])
        if rc != 0:
            raise SystemExit(f"chip_smoke: `{' '.join(cmd)}` exited {rc}")

    def fold(self) -> dict:
        recs = []
        if os.path.exists(self.path):
            with open(self.path) as f:
                recs = [json.loads(line) for line in f if line.strip()]
        new, self.seen = recs[self.seen:], len(recs)
        out = {"steps": {}, "compile_s": 0.0, "cache_hits": 0,
               "cache_misses": 0}
        for r in new:
            if "wallSeconds" not in r:      # an event line, not a step
                continue
            ip = r.get("inputPipeline", {})
            out["steps"][r["step"]] = r["wallSeconds"]
            out["compile_s"] += float(ip.get("compile_s", 0.0))
            out["cache_hits"] += int(ip.get("compile_cache_hits", 0))
            out["cache_misses"] += int(ip.get("compile_cache_misses", 0))
        out["compile_s"] = round(out["compile_s"], 3)
        return out


class KernelLog:
    """Records every `pallas_call` the product makes (kernel name →
    interpret flags seen): which kernel route REALLY ran, taken at the
    one call every route goes through, not inferred from a knob."""

    def __init__(self):
        from jax.experimental import pallas as pl
        self.calls: dict = {}
        self._pl, self._orig = pl, pl.pallas_call

        def recording(kernel, *a, **kw):
            name = getattr(getattr(kernel, "func", kernel), "__name__", "?")
            seen = self.calls.setdefault(name, {"calls": 0, "interpret": []})
            seen["calls"] += 1
            flag = bool(kw.get("interpret", False))
            if flag not in seen["interpret"]:
                seen["interpret"].append(flag)
            return self._orig(kernel, *a, **kw)

        pl.pallas_call = recording

    def close(self) -> None:
        self._pl.pallas_call = self._orig

    def routes(self, expect: dict, on_chip: bool) -> dict:
        """{route: {kernel, calls, interpret}} for the expected kernels;
        on the chip every one must have run, compiled (never interpret)."""
        out = {}
        for route, kernel in expect.items():
            seen = self.calls.get(kernel, {"calls": 0, "interpret": []})
            if not seen["calls"]:
                raise SystemExit(f"chip_smoke: kernel route {route!r} never "
                                 f"reached {kernel} (resolved to XLA)")
            out[route] = {"route": "pallas", "kernel": kernel,
                          "calls": seen["calls"],
                          "interpret": seen["interpret"]}
            if on_chip and seen["interpret"] != [False]:
                raise SystemExit(f"chip_smoke: {kernel} ran in interpret "
                                 "mode on the chip")
        return out


def assert_lowers_to_custom_call() -> dict:
    """Belt and braces for `compiled`: lower each kernel's entry point
    for the attached chip and look for Mosaic's `tpu_custom_call`."""
    import jax
    import jax.numpy as jnp
    from shifu_tpu.ops import (pallas_hist, pallas_score, pallas_split,
                               pallas_trees)
    f32, i32 = jnp.float32, jnp.int32
    S = jax.ShapeDtypeStruct
    r = 4096
    cases = {
        "hist": (lambda b, s, g, h: pallas_hist.level_histograms_pallas(
            b, s, g, h, 32, 64),
            S((N_COLS, r), i32), S((r,), i32), S((r,), f32), S((r,), f32)),
        "split": (lambda g, h, m: pallas_split.best_splits_pallas(
            g, h, m, 1.0, 5.0),
            S((32, N_COLS, 64), f32), S((32, N_COLS, 64), f32),
            S((32, N_COLS), f32)),
        "trees": (lambda nd, v, c: pallas_trees.predict_ensemble(
            nd, v, c, n_trees=20, kind="gbt", loss="log", learning_rate=0.2,
            max_depth=6, n_bins=64),
            S((8, 20 * 128), f32), S((N_COLS, 512), f32),
            S((N_COLS, 63), f32)),
        "score": (lambda x, m, sd, w, b: pallas_score.fused_first_layer(
            x, m, sd, 4.0, w, b, mode="pallas"),
            S((512, N_COLS), f32), S((N_COLS,), f32), S((N_COLS,), f32),
            S((N_COLS, 64), f32), S((64,), f32)),
    }
    out = {}
    for name, (fn, *shapes) in cases.items():
        text = jax.jit(fn).lower(*shapes).as_text()
        out[name] = "tpu_custom_call" in text
        if not out[name]:
            raise SystemExit(f"chip_smoke: {name} kernel did not lower to "
                             "a tpu_custom_call")
    return out


# ---------------------------------------------------------------------------
# checks, by the repo's own means
# ---------------------------------------------------------------------------

def eval_blocks(root: str):
    """The eval set as the blocks `shifu eval` scores, the eval
    scorer's own scores on them, labels — and the AUC `shifu eval`
    wrote, which must be the AUC of exactly these scores."""
    import numpy as np
    from shifu_tpu.eval.scorer import Scorer
    from shifu_tpu.ops import metrics as ops_metrics
    from shifu_tpu.processor import eval as eval_proc
    from shifu_tpu.processor import norm as norm_proc
    from shifu_tpu.processor.base import ProcessorContext
    ctx = ProcessorContext.load(root)
    ec = eval_proc._eval_by_name(ctx, "Eval1")[0]
    dset, cols = eval_proc._build_eval_dataset(ctx, ec)
    scorer = Scorer.from_dir(ctx.path_finder.models_path(),
                             score_selector=ec.performanceScoreSelector,
                             gbt_convert=ec.gbtScoreConvertStrategy)
    scores = np.asarray(eval_proc._score_dataset(
        ctx.model_config, scorer, dset, cols)["final"], np.float32)
    labels = np.asarray(dset.tags, np.float32)
    weights = np.asarray(dset.weights, np.float32)
    dense = np.asarray(norm_proc.normalize_columns(
        ctx.model_config, cols, dset).dense, np.float32)
    with open(ctx.path_finder.eval_performance_path("Eval1")) as f:
        auc_cli = float(json.load(f)["areaUnderRoc"])
    auc = float(ops_metrics.weighted_auc(scores, labels, weights))
    if abs(auc - auc_cli) > 1e-4:
        raise SystemExit(f"chip_smoke: `eval` wrote AUC {auc_cli}, its "
                         f"scorer gives {auc} on the same rows")
    return {"scorer": scorer, "scores": scores, "labels": labels,
            "weights": weights, "dense": dense,
            "raw_dense": np.asarray(dset.numeric, np.float32),
            "auc": auc_cli}


def check_against_reference(kind: str, ev: dict) -> dict:
    """Same model, same rows, plain XLA route: `nn.forward` on the
    normalized block / `gbdt.predict(route="xla")` on the raw one."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from shifu_tpu.models import gbdt, nn as nn_mod
    from shifu_tpu.ops import metrics as ops_metrics
    mkind, meta, params = ev["scorer"].models[0]
    if kind == "nn":
        sd = dict(meta["spec"])
        sd["hidden_dims"] = tuple(sd.get("hidden_dims", ()))
        sd["activations"] = tuple(sd.get("activations", ()))
        ref = nn_mod.forward(nn_mod.MLPSpec(**sd),
                             jax.tree.map(jnp.asarray, params),
                             jnp.asarray(ev["dense"]))
        tol = NN_REF_TOL
    else:
        ref = gbdt.predict(meta, params, ev["raw_dense"], None, route="xla")
        from shifu_tpu.eval.scorer import convert_tree_score
        ref = convert_tree_score(np.asarray(ref), ev["scorer"].gbt_convert)
        tol = GBT_SCORE_TOL
    ref = np.asarray(ref, np.float32)
    if ref.shape != ev["scores"].shape or not np.isfinite(ref).all() \
            or not np.isfinite(ev["scores"]).all():
        raise SystemExit(f"chip_smoke: {kind} scores malformed")
    auc_ref = float(ops_metrics.weighted_auc(ref, ev["labels"],
                                             ev["weights"]))
    diff = float(np.max(np.abs(ref - ev["scores"])))
    mean_diff = float(np.mean(np.abs(ref - ev["scores"])))
    out = {"model": mkind, "auc": round(ev["auc"], 5),
           "auc_xla_reference": round(auc_ref, 5),
           "max_abs_score_diff": diff, "score_tol": tol,
           "mean_abs_score_diff": mean_diff,
           "auc_floor": AUC_FLOOR, "auc_tol": AUC_TOL}
    if ev["auc"] < AUC_FLOOR:
        raise SystemExit(f"chip_smoke: {kind} AUC below floor: {out}")
    if abs(ev["auc"] - auc_ref) > AUC_TOL or diff > tol:
        raise SystemExit(f"chip_smoke: {kind} disagrees with the XLA "
                         f"reference: {out}")
    return out


def serve_and_check(kind: str, root: str, models_dir: str,
                    ev: dict) -> dict:
    """The scorer `cmd_serve` starts (ScorerService + HttpFrontEnd), in
    this process; ragged requests over HTTP; served == eval scores."""
    import numpy as np
    from shifu_tpu.data import pipeline
    from shifu_tpu.serve.http import HttpFrontEnd
    from shifu_tpu.serve.service import ScorerService
    block = "dense" if kind == "nn" else "raw_dense"
    tols = {n: GBT_SCORE_TOL if kind == "gbt" else
            NN_SERVE_ONE_ROW_TOL if n == 1 else NN_SERVE_TOL
            for n in SERVE_SIZES}
    owner = ScorerService(models_dir=models_dir, workspace_root=root)
    owner.start()
    front = HttpFrontEnd(owner, port=0).start()
    url = "http://%s:%d/score" % tuple(front.address)
    answered, off = 0, 0
    worst = dict.fromkeys(SERVE_SIZES, 0.0)   # by request size
    t0 = time.time()

    def one(n: int, off: int) -> float:
        rows = ev[block][off:off + n]
        body = json.dumps({block: rows.tolist()}).encode()
        req = urllib.request.Request(
            url, data=body, headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=300) as resp:
                got = json.loads(resp.read())
        except urllib.error.HTTPError as e:   # any non-200 fails the run
            raise SystemExit(f"chip_smoke: /score answered {e.code} for "
                             f"{n} rows: {e.read()[:300]!r}") from e
        scores = got.get("scores", got)
        served = np.asarray(scores["final"], np.float32)
        want = ev["scores"][off:off + n]
        if served.shape != want.shape:
            raise SystemExit(f"chip_smoke: served {served.shape} for "
                             f"{n} rows")
        return float(np.max(np.abs(served - want)))

    try:
        span = len(ev["scores"]) - max(SERVE_SIZES)   # wrap the offsets
        for n in SERVE_SIZES:                 # warm-up: every bucket once
            worst[n] = max(worst[n], one(n, off % span))
            off, answered = off + n, answered + 1
        warm = pipeline.drain_stage_timers()
        for _ in range(SERVE_PASSES):         # the steady window
            for n in SERVE_SIZES:
                worst[n] = max(worst[n], one(n, off % span))
                off, answered = off + n, answered + 1
        steady = pipeline.drain_stage_timers()
        stats = owner.stats()
    finally:
        front.close()
        owner.close()
    out = {"phase": f"serve.{kind}", "wall_s": round(time.time() - t0, 2),
           "requests": answered, "sizes": list(SERVE_SIZES),
           "rows": off, "max_abs_served_vs_eval": max(worst.values()),
           "served_vs_eval_by_size": {str(n): worst[n] for n in worst},
           "score_tol_by_size": {str(n): tols[n] for n in tols},
           "warm_compile_s": round(warm.get("compile_s", 0.0), 3),
           "warm_cache_hits": int(warm.get("compile_cache_hits", 0)),
           "warm_cache_misses": int(warm.get("compile_cache_misses", 0)),
           "steady_compile_cache_misses":
               int(steady.get("compile_cache_misses", 0)),
           "steady_compile_s": round(steady.get("compile_s", 0.0), 3),
           # `compile_s` is the compiler alone: a steady batch that is
           # traced again and read back from the cache shows here
           "steady_compile_cache_read_s":
               round(steady.get("compile_cache_read_s", 0.0), 3),
           "steady_batches": int(steady.get("serve_batches", 0)),
           "latency": stats.get("latency", {})}
    if out["steady_compile_cache_misses"] or out["steady_compile_s"] \
            or out["steady_compile_cache_read_s"]:
        raise SystemExit(f"chip_smoke: steady traffic compiled: {out}")
    if any(worst[n] > tols[n] for n in SERVE_SIZES):
        raise SystemExit(f"chip_smoke: served scores differ from eval's: "
                         f"{out}")
    return out


# ---------------------------------------------------------------------------
# the one-chip run
# ---------------------------------------------------------------------------

def run_one_chip(args, root: str, device: dict, cache_dir: str) -> None:
    import numpy as np
    from shifu_tpu import native
    from shifu_tpu.processor.base import ProcessorContext
    on_chip = device["platform"] == "tpu"
    klog = KernelLog()
    steps = Steps(root)

    t0 = time.time()
    so = os.path.join(os.path.dirname(native.__file__), "_fast_reader.so")
    so_was_there = os.path.exists(so)
    write_model_set(root, args.seed, args.rows, args.eval_rows)
    reader = "native" if native.get_reader_lib() is not None else "pandas"
    emit(phase="data", wall_s=round(time.time() - t0, 2), seed=args.seed,
         rows=args.rows, eval_rows=args.eval_rows, columns=N_COLS,
         reader=reader,
         reader_built_this_run=(reader == "native" and not so_was_there),
         cache_dir=cache_dir)

    # -- nn: init, stats, norm, train, eval --------------------------------
    t0 = time.time()
    for cmd in ("init", "stats", "norm", "train", "eval"):
        steps.run(cmd)
    rec = steps.fold()
    ev_nn = eval_blocks(root)
    nn_check = check_against_reference("nn", ev_nn)
    emit(phase="nn", wall_s=round(time.time() - t0, 2), rows=args.rows,
         eval_rows=int(ev_nn["scores"].shape[0]), hidden=64,
         epochs=EPOCHS, cache_dir=cache_dir, reader=reader,
         routes=klog.routes({"score": "_score_kernel"}, on_chip),
         **rec, **nn_check)
    ctx = ProcessorContext.load(root)
    models = ctx.path_finder.models_path()
    nn_models = models + ".nn"                # keep the NN for its server
    shutil.move(models, nn_models)

    # -- gbt: train, eval on the same data ---------------------------------
    t0 = time.time()
    set_algorithm(root, "GBT", GBT_PARAMS)
    for cmd in ("train", "eval"):
        steps.run(cmd)
    rec = steps.fold()
    ev_gbt = eval_blocks(root)
    gbt_check = check_against_reference("gbt", ev_gbt)
    _, meta, forest = ev_gbt["scorer"].models[0]
    built = {"trees": int(np.asarray(forest["trees"]["feature"]).shape[0]),
             "depth": int(meta["treeConfig"]["max_depth"]),
             "bins": int(meta["treeConfig"]["n_bins"])}
    if built != {"trees": TREES, "depth": DEPTH, "bins": BINS}:
        raise SystemExit(f"chip_smoke: the GBT that was built is not the "
                         f"one asked for: {built}")
    emit(phase="gbt", wall_s=round(time.time() - t0, 2), rows=args.rows,
         eval_rows=int(ev_gbt["scores"].shape[0]), **built,
         cache_dir=cache_dir,
         routes=klog.routes({"hist": "_hist_kernel",
                             "split": "_split_kernel",
                             "trees": "_tree_kernel"}, on_chip),
         **rec, **gbt_check)

    # -- serve: NN then GBT, over HTTP --------------------------------------
    emit(**serve_and_check("nn", root, nn_models, ev_nn),
         cache_dir=cache_dir)
    emit(**serve_and_check("gbt", root, models, ev_gbt),
         cache_dir=cache_dir)
    klog.close()
    if on_chip:
        emit(phase="lowering", tpu_custom_call=assert_lowers_to_custom_call())


# ---------------------------------------------------------------------------
# --chips 4: the data-parallel path and what it is compared with
# ---------------------------------------------------------------------------

def _device_memory() -> list:
    import jax
    out = []
    for d in jax.devices():
        st = d.memory_stats() or {}
        out.append({"id": d.id, "bytes_in_use": st.get("bytes_in_use"),
                    "peak_bytes_in_use": st.get("peak_bytes_in_use")})
    return out


@contextlib.contextmanager
def counting_shard_axis():
    """Counts `mesh.shard_axis` calls (and the data-mesh size they
    shard over) while the block runs: the XLA score path shards rows
    over the whole mesh, a fused-kernel path never calls it and stays
    on the default device."""
    from shifu_tpu.parallel import mesh as mesh_mod
    seen = {"calls": 0, "mesh": 1}
    orig = mesh_mod.shard_axis

    def counting(mesh, a, axis=0, pad_value=0):
        seen["calls"] += 1
        seen["mesh"] = int(mesh.shape["data"])
        return orig(mesh, a, axis, pad_value)

    mesh_mod.shard_axis = counting
    try:
        yield seen
    finally:
        mesh_mod.shard_axis = orig


def _eval_auc(root: str) -> float:
    """The AUC the last `eval` wrote."""
    from shifu_tpu.processor.base import ProcessorContext
    ctx = ProcessorContext.load(root)
    with open(ctx.path_finder.eval_performance_path("Eval1")) as f:
        return float(json.load(f)["areaUnderRoc"])


def run_mesh_phase(args, root: str, device: dict, cache_dir: str) -> None:
    import jax
    import numpy as np
    from shifu_tpu.eval.scorer import Scorer
    from shifu_tpu.processor.base import ProcessorContext
    n_dev = len(jax.devices())
    if n_dev != args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but jax sees "
                         f"{n_dev} device(s)")
    on_chip = device["platform"] == "tpu"
    klog = KernelLog()
    steps = Steps(root)
    t0 = time.time()
    write_model_set(root, args.seed, args.rows, args.eval_rows)
    for cmd in ("init", "stats", "norm"):
        steps.run(cmd)
    emit(phase="mesh.data", wall_s=round(time.time() - t0, 2),
         rows=args.rows, eval_rows=args.eval_rows, devices=n_dev,
         cache_dir=cache_dir, **steps.fold())
    models = ProcessorContext.load(root).path_finder.models_path()

    def train_eval(label: str, mesh_devices: int) -> dict:
        """`train` + `eval` with the default mesh capped at
        `mesh_devices` (SHIFU_TPU_MESH_DEVICES), models kept aside."""
        os.environ["SHIFU_TPU_MESH_DEVICES"] = str(mesh_devices)
        t0 = time.time()
        steps.run("train")
        train_rec = steps.fold()
        mem = _device_memory()
        with counting_shard_axis() as shards:
            steps.run("eval")
        kept = f"{models}.{label}"
        shutil.rmtree(kept, ignore_errors=True)
        shutil.move(models, kept)
        return {"label": label, "mesh_devices": mesh_devices,
                "wall_s": round(time.time() - t0, 2),
                "train_compile_s": train_rec["compile_s"],
                "auc": _eval_auc(root), "models": kept,
                "device_memory_after_train": mem,
                "eval": {"shard_axis_calls": shards["calls"],
                         "devices_used": shards["mesh"]}}

    results = {}
    for alg, params in (("NN", NN_PARAMS),
                        ("GBT", GBT_PARAMS)):
        set_algorithm(root, alg, params)
        for n in (n_dev, 1):
            r = train_eval(f"{alg.lower()}{n}", n)
            results[r["label"]] = r
            emit(phase=f"mesh.{alg.lower()}", **r)
    os.environ.pop("SHIFU_TPU_MESH_DEVICES", None)

    # NN: same seed, same data — the psum'd gradient mean differs from
    # the one-device mean only in summation order
    many, one = results[f"nn{n_dev}"], results["nn1"]
    pm = Scorer.from_dir(many["models"]).models[0][2]
    p1 = Scorer.from_dir(one["models"]).models[0][2]
    w_diff = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                 for a, b in zip(jax.tree.leaves(pm), jax.tree.leaves(p1)))
    nn_cmp = {"auc_many": many["auc"], "auc_one": one["auc"],
              "max_abs_weight_diff": w_diff, "auc_tol": MESH_AUC_TOL,
              "weight_tol": MESH_WEIGHT_TOL}
    # GBT: the trees themselves, decision by decision
    tm = Scorer.from_dir(results[f"gbt{n_dev}"]["models"]).models[0][2]
    t1 = Scorer.from_dir(results["gbt1"]["models"]).models[0][2]
    decisions = int(np.asarray(t1["trees"]["feature"]).size)
    flips = int(sum((np.asarray(tm["trees"][k])
                     != np.asarray(t1["trees"][k])).sum()
                    for k in ("feature", "bin")))
    gbt_cmp = {"auc_many": results[f"gbt{n_dev}"]["auc"],
               "auc_one": results["gbt1"]["auc"],
               "split_decisions": decisions, "split_flips": flips,
               # the rehearsal's 4,000 rows leave a depth-6 tree's deep
               # nodes a handful of rows each: gains tie at f32 order
               # and one early flip moves every later tree (6 flips
               # seen). It walks the path; the chip run holds the trees.
               "max_flips": decisions // 20 if args.rehearse
               else MESH_MAX_SPLIT_FLIPS}

    # serve: where the live scorer places a request (no HTTP needed to
    # see it — the front end adds no device work)
    from shifu_tpu.serve.service import ScorerService
    serve_devices = {}
    for kind, label in (("nn", f"nn{n_dev}"), ("gbt", f"gbt{n_dev}")):
        with counting_shard_axis() as shards, \
                ScorerService(models_dir=results[label]["models"],
                              workspace_root=root) as svc:
            block = "dense" if kind == "nn" else "raw_dense"
            svc.submit(**{block: np.zeros((64, N_COLS), np.float32)},
                       timeout=300.0)
        serve_devices[kind] = shards["mesh"]
    klog.close()
    emit(phase="mesh.compare", devices=n_dev, nn=nn_cmp, gbt=gbt_cmp,
         serve_devices_used=serve_devices,
         routes={k: v for k, v in klog.calls.items()},
         device_memory=_device_memory())
    if on_chip and any(v["interpret"] != [False]
                       for v in klog.calls.values()):
        raise SystemExit("chip_smoke: a kernel ran in interpret mode")
    if abs(nn_cmp["auc_many"] - nn_cmp["auc_one"]) > MESH_AUC_TOL or \
            w_diff > MESH_WEIGHT_TOL or \
            min(nn_cmp["auc_many"], nn_cmp["auc_one"]) < AUC_FLOOR:
        raise SystemExit(f"chip_smoke: NN {n_dev}-device vs 1-device: "
                         f"{nn_cmp}")
    if flips > gbt_cmp["max_flips"] or \
            abs(gbt_cmp["auc_many"] - gbt_cmp["auc_one"]) > MESH_AUC_TOL:
        raise SystemExit(f"chip_smoke: GBT {n_dev}-device vs 1-device: "
                         f"{gbt_cmp}")


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=20260926)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = run ONLY the 4-device-mesh phase and the "
                         "1-device run it is compared with")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: tiny sizes, kernels in "
                         "interpret mode; never reports platform tpu")
    ap.add_argument("--workdir", default=None,
                    help="where the model set is built (default: a "
                         "fresh directory under the checkout's tmp/)")
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if device["platform"] != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU — jax found {device}; only "
              "`--rehearse` runs elsewhere", file=sys.stderr)
        return 2
    if args.rehearse:
        if device["platform"] == "tpu":
            print("chip_smoke: --rehearse is the CPU rehearsal; run it "
                  "with JAX_PLATFORMS=cpu", file=sys.stderr)
            return 2
        # the kernels' routes resolve to XLA off the chip; the rehearsal
        # pins them to Pallas so it walks the chip's code path (the
        # kernels then run in interpret mode)
        for knob in ("SHIFU_TPU_HIST", "SHIFU_TPU_SPLIT_FUSED",
                     "SHIFU_TPU_TREE_FUSED", "SHIFU_TPU_SCORE_FUSED"):
            os.environ.setdefault(knob, "pallas")
    args.rows, args.eval_rows = (REHEARSE_ROWS, REHEARSE_EVAL_ROWS) \
        if args.rehearse else (ROWS, EVAL_ROWS)

    here = os.path.dirname(os.path.abspath(__file__))
    # absolute: ModelConfig paths are resolved against the model set
    root = os.path.abspath(args.workdir or os.path.join(
        here, "tmp", "chip_smoke", f"run-{os.getpid()}", "ModelSet"))
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    from shifu_tpu import profiling
    cache_dir = profiling.enable_compile_cache()
    t0 = time.time()
    if args.chips == 4:
        run_mesh_phase(args, root, device, cache_dir)
    else:
        run_one_chip(args, root, device, cache_dir)
    emit(phase="total", wall_s=round(time.time() - t0, 2),
         cache_dir=cache_dir, model_set=root)
    if not args.workdir:
        shutil.rmtree(os.path.dirname(root), ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
