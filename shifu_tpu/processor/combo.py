"""`shifu combo` — assembled multi-algorithm (stacked) models.

Replaces `core/processor/ComboModelProcessor.java` + `combo/*`
(DataMerger, PigDataJoin): the user names a chain of algorithms
(`combo -new NN,GBT,LR`); all but the last become sub-models, each
trained as its own model set in a subdirectory, and the LAST algorithm
is the assemble model trained on the sub-models' scores — classic
stacking. The reference joins per-sub-model Pig score outputs by uid
(`DataMerger`); here every sub-model scores the same in-memory frame,
so the join is row order and disappears.

Steps (ComboModelProcessor.ComboStep):
  new  → write ComboTrain.json                     (:133 createNewCombo)
  init → scaffold sub-model workspaces             (:150 initComboModels)
  run  → train subs ∥, score train data, train the
         assemble model on the score matrix        (:278 runComboModels)
  eval → run eval sets through subs + assemble     (:363 evalComboModels)

`-resume` skips sub-models that already have trained models
(`shifu combo -run -resume`).
"""

from __future__ import annotations

import json
import logging
import os
import time
from typing import Dict, List, Optional

import numpy as np

from shifu_tpu.config.model_config import Algorithm, ModelConfig
from shifu_tpu.processor.base import ProcessorContext
from shifu_tpu.resilience import atomic_write

log = logging.getLogger("shifu_tpu")

COMBO_FILE = "ComboTrain.json"


def _combo_path(ctx: ProcessorContext) -> str:
    return os.path.join(ctx.path_finder.root, COMBO_FILE)


def _load_combo(ctx: ProcessorContext) -> Dict:
    p = _combo_path(ctx)
    if not os.path.exists(p):
        raise FileNotFoundError(
            f"{COMBO_FILE} not found under {ctx.path_finder.root}; run "
            "`combo -new ALG1,ALG2,...` first")
    with open(p) as f:
        return json.load(f)


def _sub_dir(ctx: ProcessorContext, name: str) -> str:
    return os.path.join(ctx.path_finder.root, name)


def new(ctx: ProcessorContext, algorithms: str) -> int:
    """`combo -new NN,GBT,LR` — all but the last algorithm are
    sub-models, the last is the assemble model
    (ComboModelProcessor.validate:483-516 requires ≥3 entries)."""
    try:
        algs = [Algorithm.parse(a.strip()) for a in algorithms.split(",")
                if a.strip()]
    except ValueError as e:
        raise ValueError(f"unknown algorithm in {algorithms!r}: {e}")
    if len(algs) < 3:
        raise ValueError("combo needs at least 3 algorithms: "
                         "N-1 sub-models + 1 assemble model")
    name = ctx.model_config.model_set_name
    spec = {
        "uidColumnName": "",
        "subModels": [{"name": f"{name}_{a.value}_{i}",
                       "algorithm": a.value}
                      for i, a in enumerate(algs[:-1])],
        "assemble": {"name": f"{name}_assemble_{algs[-1].value}",
                     "algorithm": algs[-1].value},
    }
    with atomic_write(_combo_path(ctx), "w") as f:
        json.dump(spec, f, indent=2)
    log.info("combo: %d sub-models + %s assemble → %s",
             len(spec["subModels"]), algs[-1].value, _combo_path(ctx))
    return 0


def init(ctx: ProcessorContext) -> int:
    """Scaffold one model-set directory per sub-model, inheriting the
    parent dataSet/stats/varSelect and overriding the algorithm (the
    reference also tunes normType per algorithm,
    createModelNormalizeConf:559 — tree subs keep raw-ish norm)."""
    combo = _load_combo(ctx)
    mc = ctx.model_config
    mc_dict = mc.to_dict()

    def absolutize(d: Dict, keys: List[str]) -> None:
        # the sub-model workspace is a SUBDIRECTORY of the parent, so
        # parent-relative paths must become absolute before copying
        for k in keys:
            if d.get(k):
                d[k] = os.path.abspath(mc.resolve_path(str(d[k])))

    for block, keys in (("dataSet", ["dataPath", "headerPath",
                                     "validationDataPath",
                                     "metaColumnNameFile",
                                     "categoricalColumnNameFile",
                                     "segExpressionFile"]),
                        ("varSelect", ["forceSelectColumnNameFile",
                                       "forceRemoveColumnNameFile",
                                       "candidateColumnNameFile"])):
        if block in mc_dict:
            absolutize(mc_dict[block], keys)
    for ev in mc_dict.get("evals", []):
        absolutize(ev.get("dataSet", {}),
                   ["dataPath", "headerPath", "metaColumnNameFile",
                    "categoricalColumnNameFile"])

    for sub in combo["subModels"]:
        sub_dir = _sub_dir(ctx, sub["name"])
        os.makedirs(sub_dir, exist_ok=True)
        sub_mc = json.loads(json.dumps(mc_dict))  # deep copy
        sub_mc["basic"]["name"] = sub["name"]
        sub_mc["train"]["algorithm"] = sub["algorithm"]
        with atomic_write(os.path.join(sub_dir, "ModelConfig.json"),
                          "w") as f:
            json.dump(sub_mc, f, indent=2)
        log.info("combo init: %s (%s)", sub_dir, sub["algorithm"])
    return 0


def _sub_trained(sub_dir: str) -> bool:
    models = os.path.join(sub_dir, "models")
    return os.path.isdir(models) and any(
        f.startswith("model") for f in os.listdir(models))


def _train_sub(sub_dir: str) -> None:
    from shifu_tpu.processor import init as init_p
    from shifu_tpu.processor import norm as norm_p
    from shifu_tpu.processor import stats as stats_p
    from shifu_tpu.processor import train as train_p
    for proc in (init_p, stats_p, norm_p, train_p):
        sctx = ProcessorContext.load(sub_dir)
        rc = proc.run(sctx)
        if rc != 0:
            raise RuntimeError(f"combo sub-model step failed in {sub_dir}")


def _train_sub_node(root: str, sub_dir: str, name: str) -> None:
    """One sub-model's init→stats→norm→train as a subprocess (this
    module's __main__ hook), so sibling subs scheduled concurrently
    keep their process-global state — abort scope, stage timers, jax
    config — as isolated as the serial loop kept it. All siblings
    resolve the same persistent compile cache
    (`profiling.enable_compile_cache`)."""
    import subprocess
    import sys
    log_dir = os.path.join(root, "tmp", "dag_logs")
    os.makedirs(log_dir, exist_ok=True)
    log_path = os.path.join(log_dir, f"{name.replace('/', '_')}.log")
    with open(log_path, "w") as lf:  # lint: disable=non-atomic-write -- live-tailed subprocess log; must exist mid-run
        rc = subprocess.call(
            [sys.executable, "-m", "shifu_tpu.processor.combo", sub_dir],
            stdout=lf, stderr=subprocess.STDOUT)
    if rc != 0:
        try:
            with open(log_path, errors="replace") as lf:
                tail = "".join(lf.readlines()[-15:])
        except OSError:
            tail = "<log unavailable>"
        raise RuntimeError(f"combo sub-model {name} exited {rc} "
                           f"(log: {log_path})\n{tail}")


def _sub_scores(ctx: ProcessorContext, combo: Dict, df) -> np.ndarray:
    """(R, n_subs) ensemble-mean score of every sub-model over a raw
    frame — the DataMerger join collapses to column stacking."""
    from shifu_tpu.eval.model_runner import ModelRunner
    cols = []
    for sub in combo["subModels"]:
        runner = ModelRunner.from_model_set(_sub_dir(ctx, sub["name"]))
        cols.append(runner.score_frame(df.copy())["final"])
    return np.stack(cols, axis=1).astype(np.float32)


def _load_training_frame(mc: ModelConfig):
    from shifu_tpu.data.dataset import parse_tags, valid_tag_mask
    from shifu_tpu.data.purifier import DataPurifier
    from shifu_tpu.data.reader import read_raw_table, simple_column_name
    df = read_raw_table(mc)
    keep = DataPurifier(mc.dataSet.filterExpressions).apply(df)
    df = df[keep].reset_index(drop=True)
    valid = valid_tag_mask(mc, df)
    df = df[valid].reset_index(drop=True)
    tgt = simple_column_name(mc.dataSet.targetColumnName.split("|")[0])
    tags = parse_tags(df[tgt].astype(str).str.strip().to_numpy(),
                      mc.pos_tags, mc.neg_tags)
    wname = mc.dataSet.weightColumnName
    if wname and wname in df.columns:
        import pandas as pd
        weights = pd.to_numeric(df[wname], errors="coerce") \
            .fillna(1.0).to_numpy(np.float32)
    else:
        weights = np.ones(len(df), np.float32)
    return df, tags.astype(np.float32), weights


def run(ctx: ProcessorContext, resume: bool = False) -> int:
    """Train all sub-models — embarrassingly parallel, so they run as
    sibling nodes through the pipeline DAG scheduler — then score the
    training data with each and train the assemble model on the
    (R, n_subs) score matrix as the sink node."""
    from shifu_tpu.pipeline.scheduler import Node, run_dag
    t0 = time.time()
    mc = ctx.model_config
    combo = _load_combo(ctx)
    root = ctx.path_finder.root

    nodes = []
    sub_names = []
    for sub in combo["subModels"]:
        sub_dir = _sub_dir(ctx, sub["name"])
        if not os.path.exists(os.path.join(sub_dir, "ModelConfig.json")):
            raise FileNotFoundError(f"{sub_dir} not scaffolded; run "
                                    "`combo -init` first")
        name = f"combo.{sub['name']}"
        sub_names.append(name)
        nodes.append(Node(
            name=name,
            fn=(lambda d=sub_dir, n=name: _train_sub_node(root, d, n)),
            deps=(), device=True,
            done_check=(lambda d=sub_dir: _sub_trained(d)) if resume
            else None))

    def assemble() -> None:
        df, tags, weights = _load_training_frame(mc)
        scores = _sub_scores(ctx, combo, df)
        asm = combo["assemble"]
        alg = Algorithm.parse(asm["algorithm"])
        asm_dir = _sub_dir(ctx, asm["name"])
        os.makedirs(os.path.join(asm_dir, "models"), exist_ok=True)
        if alg.is_tree:
            # tree assemble (e.g. `combo -new NN,LR,GBT`): boost/bag
            # over the score matrix with its own tree trainer, like the
            # reference's ComboModelProcessor trains the assemble with
            # its configured algorithm — NOT an MLP mislabeled as a tree
            val_err = _train_assemble_tree(ctx, asm_dir, alg, scores,
                                           tags, weights, combo)
        else:
            val_err = _train_assemble_dense(ctx, asm_dir, alg, scores,
                                            tags, weights, combo, asm)
        log.info("combo run: %d subs + assemble (%s) in %.2fs; assemble "
                 "val err %.6f", len(combo["subModels"]),
                 asm["algorithm"], time.time() - t0, val_err)

    nodes.append(Node(name="combo.assemble", fn=assemble,
                      deps=tuple(sub_names), device=True))
    run_dag(nodes, root=root, label="combo")
    return 0


def _train_assemble_dense(ctx: ProcessorContext, asm_dir: str, alg,
                          scores: np.ndarray, tags: np.ndarray,
                          weights: np.ndarray, combo: Dict,
                          asm: Dict) -> float:
    """Assemble model as a dense gradient model over sub-model scores."""
    from shifu_tpu.models.spec import save_model
    from shifu_tpu.train.trainer import train_nn
    mc = ctx.model_config
    conf = mc.train
    if alg in (Algorithm.LR, Algorithm.SVM):
        from shifu_tpu.processor.train import _lr_spec
        spec = _lr_spec(conf.params, scores.shape[1])
    else:
        from shifu_tpu.models import nn as nn_mod
        spec = nn_mod.MLPSpec.from_train_params(conf.params, scores.shape[1])
    res = train_nn(conf, scores, tags, weights, seed=4001, spec=spec)
    kind = "lr" if alg in (Algorithm.LR, Algorithm.SVM) else "nn"
    meta = {
        "spec": {
            "input_dim": res.spec.input_dim,
            "hidden_dims": list(res.spec.hidden_dims),
            "activations": list(res.spec.activations),
            "output_dim": 1, "output_activation": "sigmoid",
            "dropout_rate": 0.0, "l2": res.spec.l2, "l1": res.spec.l1,
            "loss": res.spec.loss, "weight_init": res.spec.weight_init,
        },
        "inputNames": [s["name"] for s in combo["subModels"]],
        "normType": "SCORE", "modelSetName": asm["name"],
    }
    save_model(os.path.join(asm_dir, "models", f"model0.{kind}"), kind,
               meta, res.params_per_bag[0])
    return float(res.best_val.min())


def _train_assemble_tree(ctx: ProcessorContext, asm_dir: str, alg,
                         scores: np.ndarray, tags: np.ndarray,
                         weights: np.ndarray, combo: Dict) -> float:
    """Assemble model as GBT/RF over the (R, n_subs) score matrix.
    Scores live in [0,1], so equal-interval interior cuts bin them."""
    import dataclasses

    from shifu_tpu.models import gbdt
    from shifu_tpu.models.spec import save_model
    from shifu_tpu.processor.train_tree import tree_config_from_params
    from shifu_tpu.train.trainer import split_validation
    mc = ctx.model_config
    n_sub = scores.shape[1]
    n_cut_slots = 32  # score-space resolution; scores are smooth in [0,1]
    cuts = np.tile(np.linspace(0.0, 1.0, n_cut_slots + 1)[1:-1,
                                                          None],
                   (1, n_sub)).astype(np.float32)
    n_bins = cuts.shape[0] + 2  # cut slots + 1 value slot + missing
    cfg = dataclasses.replace(tree_config_from_params(mc), n_bins=n_bins)
    tables = gbdt.make_bin_tables(cuts, [], n_bins)
    bins = gbdt.bin_dataset(tables, scores, None, n_bins)

    # same TreeNum/subset defaults as the standalone tree trainer
    # (run_tree) so an identically configured assemble matches it
    n_trees = int(mc.train.get_param(
        "TreeNum", 10 if alg is Algorithm.RF else 100) or 10)
    if alg is Algorithm.DT:
        n_trees = 1
    subset = str(mc.train.get_param("FeatureSubsetStrategy", "ALL") or "ALL")
    tr_mask, val_mask = split_validation(len(tags), mc.train.validSetRate,
                                         4001)
    val_err = float("nan")
    if alg is Algorithm.GBT:
        trees, val_errs = gbdt.build_gbt(
            cfg, bins[tr_mask], tags[tr_mask], weights[tr_mask], n_trees,
            val_data=((bins[val_mask], tags[val_mask])
                      if val_mask.any() else None))
        kind = "gbt"
        if val_errs:
            val_err = val_errs[-1]
    else:
        trees = gbdt.build_rf(cfg, bins[tr_mask], tags[tr_mask],
                              weights[tr_mask], n_trees, subset,
                              mc.train.baggingSampleRate, 4001)
        kind = "rf"
    meta = {
        "kind": kind,
        "treeConfig": {"max_depth": cfg.max_depth, "n_bins": cfg.n_bins,
                       "learning_rate": cfg.learning_rate, "loss": cfg.loss},
        "denseNames": [s["name"] for s in combo["subModels"]],
        "indexNames": [], "modelSetName": mc.model_set_name,
        "nTrees": n_trees, "normType": "SCORE",
    }
    save_model(os.path.join(asm_dir, "models", f"model0.{kind}"), kind,
               meta, {"trees": trees, "tables": tables})
    return val_err


def evaluate(ctx: ProcessorContext,
             eval_name: Optional[str] = None) -> int:
    """Run eval sets through the sub-models then the assemble model;
    writes EvalPerformance.json per eval set under
    evals/<name>_combo/."""
    from shifu_tpu.data.dataset import parse_tags
    from shifu_tpu.data.purifier import DataPurifier
    from shifu_tpu.data.reader import read_raw_table, simple_column_name
    from shifu_tpu.models import nn as nn_mod
    from shifu_tpu.models.spec import load_model
    from shifu_tpu.ops.metrics import performance_result
    from shifu_tpu.processor.eval import effective_dataset_conf

    import copy as _copy
    import jax
    import jax.numpy as jnp

    mc = ctx.model_config
    combo = _load_combo(ctx)
    asm = combo["assemble"]
    asm_alg = Algorithm.parse(asm["algorithm"])
    ext = {"LR": "lr", "SVM": "lr", "GBT": "gbt", "RF": "rf",
           "DT": "rf"}.get(asm_alg.value, "nn")
    kind, meta, params = load_model(
        os.path.join(_sub_dir(ctx, asm["name"]), "models", f"model0.{ext}"))
    if asm_alg.is_tree:
        from shifu_tpu.models import gbdt
        score_asm = lambda s: gbdt.predict(meta, params, s, None)  # noqa: E731
    else:
        sd = dict(meta["spec"])
        sd["hidden_dims"] = tuple(sd.get("hidden_dims", ()))
        sd["activations"] = tuple(sd.get("activations", ()))
        spec = nn_mod.MLPSpec(**sd)
        jparams = jax.tree.map(jnp.asarray, params)
        score_asm = lambda s: np.asarray(  # noqa: E731
            nn_mod.forward(spec, jparams, jnp.asarray(s)))

    for ec in mc.evals:
        if eval_name is not None and ec.name != eval_name:
            continue
        ds = effective_dataset_conf(mc, ec)
        eval_mc = _copy.copy(mc)
        eval_mc.dataSet = ds
        df = read_raw_table(eval_mc, ds=ds)
        keep = DataPurifier(ds.filterExpressions).apply(df)
        df = df[keep].reset_index(drop=True)
        tgt = simple_column_name(ds.targetColumnName.split("|")[0])
        tags = parse_tags(df[tgt].astype(str).str.strip().to_numpy(),
                          [str(t) for t in ds.posTags],
                          [str(t) for t in ds.negTags])
        ok = ~np.isnan(tags)
        df, tags = df[ok].reset_index(drop=True), tags[ok]
        wname = ds.weightColumnName
        if wname and wname in df.columns:
            import pandas as pd
            weights = pd.to_numeric(df[wname], errors="coerce") \
                .fillna(1.0).to_numpy(np.float32)
        else:
            weights = np.ones(len(tags), np.float32)
        scores = _sub_scores(ctx, combo, df)
        final = score_asm(scores)
        perf = performance_result(final, tags, weights,
                                  n_buckets=ec.performanceBucketNum,
                                  score_scale=float(ec.scoreScale))
        out_dir = os.path.join(ctx.path_finder.root, "evals",
                               f"{ec.name}_combo")
        os.makedirs(out_dir, exist_ok=True)
        with atomic_write(os.path.join(out_dir, "EvalPerformance.json"),
                          "w") as f:
            json.dump(perf, f, indent=1)
        log.info("combo eval[%s]: %d rows, AUC=%.4f", ec.name, len(final),
                 perf["areaUnderRoc"])
    return 0


if __name__ == "__main__":
    # subprocess entry for the DAG scheduler: one sub-model's
    # init→stats→norm→train in an isolated process (_train_sub_node)
    import sys
    logging.basicConfig(level=logging.INFO,
                        format="%(levelname)s %(name)s %(message)s")
    from shifu_tpu.profiling import enable_compile_cache
    enable_compile_cache()   # what cli.main does for a CLI child
    _train_sub(sys.argv[1])
