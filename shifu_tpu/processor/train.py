"""`shifu train` — dispatch to the per-algorithm TPU trainers.

Mirrors `core/processor/TrainModelProcessor.java:225-458` orchestration:
validate, pick algorithm, handle bagging / grid search / k-fold /
continuous training, write models + tmp artifacts. The Guagua job
submission machinery (`runDistributedTrain:773`,
`GuaguaMapReduceClient`) disappears — LOCAL and TPU run modes execute
the same jitted program, differing only in device mesh
(`shifu_tpu/parallel/mesh.py`).
"""

from __future__ import annotations

import json
import logging
import os
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from shifu_tpu import resilience
from shifu_tpu.config.inspector import ModelStep
from shifu_tpu.config.model_config import Algorithm, ModelConfig
from shifu_tpu.models import nn as nn_mod
from shifu_tpu.models.spec import load_model, save_model
from shifu_tpu.processor import norm as norm_proc
from shifu_tpu.processor.base import ProcessorContext, step_guard
from shifu_tpu.train import grid_search
from shifu_tpu.train.trainer import TrainResult, train_nn

log = logging.getLogger("shifu_tpu")


def run(ctx: ProcessorContext, seed: int = 12306) -> int:
    t0 = time.time()
    mc = ctx.model_config
    ctx.validate(ModelStep.TRAIN)
    ctx.require_columns()
    alg = mc.train.algorithm

    if mc.is_multi_classification and \
            alg not in (Algorithm.NN, Algorithm.LR, Algorithm.SVM):
        raise ValueError(
            f"multi-class (>2 tags) is supported for NN/LR/SVM, not "
            f"{alg.value}; the reference likewise restricts "
            f"multiClassifyMethod to its NN-family trainers")

    # only the dense family writes val_error_path; the others record a
    # fingerprint-only manifest (skip still requires matching inputs)
    outs = [ctx.path_finder.val_error_path()] \
        if alg in (Algorithm.NN, Algorithm.LR, Algorithm.SVM,
                   Algorithm.TENSORFLOW) else []
    with step_guard(ctx, "train", outputs=outs) as go:
        if not go:
            return 0

        def _attempt():
            if alg in (Algorithm.NN, Algorithm.LR, Algorithm.SVM):
                return _train_dense(ctx, seed)
            if alg.is_tree:
                from shifu_tpu.processor import train_tree
                return train_tree.run_tree(ctx, seed)
            if alg in (Algorithm.WDL,):
                from shifu_tpu.processor import train_wdl
                return train_wdl.run_wdl(ctx, seed)
            if alg in (Algorithm.MTL,):
                from shifu_tpu.processor import train_mtl
                return train_mtl.run_mtl(ctx, seed)
            if alg is Algorithm.TENSORFLOW:
                # the reference's TF bridge spawns distributed-TF python
                # training (TrainModelProcessor.java:472-527); here the
                # same network trains natively in JAX and `export -t tf`
                # emits a SavedModel via jax2tf when tensorflow is
                # importable
                log.info("TENSORFLOW algorithm: training the network "
                         "natively in JAX (use `export -t tf` for a "
                         "SavedModel)")
                return _train_dense(ctx, seed)
            raise ValueError(f"unsupported algorithm {alg}")

        # supervised restart loop: with SHIFU_TPU_MAX_RESTARTS > 0, a
        # preemption or transient failure re-invokes the trainer, which
        # restores from its checkpoint dir and resumes mid-run (the
        # single-process stand-in for YARN re-dispatching containers)
        result = resilience.supervise(_attempt, step="train")
        log.info("train[%s] done in %.2fs", alg.value, time.time() - t0)
    return 0


# ---------------------------------------------------------------------------
# NN / LR / SVM (dense-input gradient models)
# ---------------------------------------------------------------------------

def _load_dense_training_data(ctx: ProcessorContext):
    path = ctx.path_finder.normalized_data_path()
    if not os.path.exists(os.path.join(path, "data.npz")):
        raise FileNotFoundError(
            f"normalized data not found at {path}; run `norm` first")
    data, meta = norm_proc.load_normalized(path)
    return data, meta


def _lr_spec(params: Dict[str, Any], input_dim: int) -> nn_mod.MLPSpec:
    """LR = zero-hidden-layer sigmoid net with log loss
    (`lr/LogisticRegressionWorker.java:312-332` gradient ≡ ∇ of this)."""
    import dataclasses
    spec = nn_mod.MLPSpec.from_train_params(params, input_dim)
    return dataclasses.replace(spec, hidden_dims=(), activations=(),
                               loss="log")


def _svm_spec(params: Dict[str, Any], input_dim: int) -> nn_mod.MLPSpec:
    """SVM maps to a linear model with squared hinge via log-loss
    approximation — the reference's SVMTrainer is an Encog SVM used only
    in LOCAL mode; we train a linear margin classifier."""
    spec = _lr_spec(params, input_dim)
    return spec


def _train_dense(ctx: ProcessorContext, seed: int) -> List[TrainResult]:
    mc = ctx.model_config
    # streaming first: loading the npz here would materialize the very
    # table trainOnDisk exists to keep out of RAM
    if mc.train.trainOnDisk and not mc.is_multi_classification:
        if (mc.train.numKFold or 0) > 1:
            raise ValueError(
                "train#numKFold is not supported with trainOnDisk — the "
                "streaming layout carries one fixed validation region; "
                "run k-fold resident (drop trainOnDisk) or use "
                "validSetRate instead")
        return _train_dense_streaming(ctx, seed)

    data, meta = _load_dense_training_data(ctx)
    x = data["dense"].astype(np.float32)
    y = data["tags"].astype(np.float32)
    w = data["weights"].astype(np.float32)
    alg = mc.train.algorithm

    classes = mc.class_tags if mc.is_multi_classification else None
    if mc.train.upSampleWeight != 1.0:
        if classes:
            # reference upsampling is positive-vs-negative only; for
            # multi-class y holds class indices, so y>0.5 would be wrong
            log.warning("upSampleWeight ignored for multi-class training")
        else:
            # duplicate-positive rebalance expressed as weight upsampling
            # (core/shuffle rebalance + train#upSampleWeight)
            w = w * np.where(y > 0.5, np.float32(mc.train.upSampleWeight), 1.0)

    if classes and mc.train.multiClassifyMethod.value == "ONEVSALL":
        # one-vs-all decomposition: one binary model per class, trained
        # as parallel independent regressions
        # (TrainModelProcessor.validateDistributedTrain:403-405)
        return _train_dense_ovr(ctx, x, y, w, classes, seed)

    combos = grid_search.expand(mc.train.params)
    if mc.train.gridConfigFile:
        gc = grid_search.parse_grid_config_file(
            mc.resolve_path(mc.train.gridConfigFile))
        merged = dict(mc.train.params)
        merged.update(gc)
        combos = grid_search.expand(merged)

    is_gs = len(combos) > 1
    kfold = mc.train.numKFold if mc.train.numKFold and mc.train.numKFold > 1 else 0

    def make_spec(params):
        if alg is Algorithm.LR:
            spec = _lr_spec(params, x.shape[1])
        elif alg is Algorithm.SVM:
            spec = _svm_spec(params, x.shape[1])
        else:
            spec = nn_mod.MLPSpec.from_train_params(params, x.shape[1])
        if classes:
            # NATIVE multi-class: softmax head, one unit per tag
            import dataclasses
            spec = dataclasses.replace(
                spec, output_dim=len(classes), output_activation="softmax",
                loss="log")
        return spec

    results: List[Tuple[Dict[str, Any], TrainResult]] = []
    t_train = time.time()
    total_epochs = 0
    for ci, params in enumerate(combos):
        tc = mc.train
        spec = make_spec(params)
        conf = _conf_with_params(tc, params)
        total_epochs += int(conf.numTrainEpochs or 0) * (kfold or 1)
        if kfold:
            res = _train_kfold(conf, spec, x, y, w, kfold, seed)
        else:
            init_params, fixed, gmask = _continuous_init(ctx, spec, seed)
            # mid-training fault tolerance: CheckpointInterval epochs per
            # orbax checkpoint (NNOutput tmp models / DTMaster
            # checkpointInterval analog); grid-search combos skip it
            ck_int = int(tc.get_param("CheckpointInterval", 0) or 0)
            res = train_nn(conf, x, y, w, seed=seed + ci, spec=spec,
                           init_params=init_params, fixed_layers=fixed,
                           grad_mask=gmask,
                           checkpoint_dir=(ctx.path_finder.checkpoint_path(0)
                                           if ck_int and not is_gs else None),
                           checkpoint_interval=ck_int)
        results.append((params, res))
        if is_gs:
            log.info("grid[%d/%d] %s → val %.6f", ci + 1, len(combos),
                     params, float(res.best_val.min()))

    best_params, best = min(results, key=lambda pr: float(pr[1].best_val.min()))
    if is_gs:
        log.info("grid search best params: %s", best_params)

    _record_train_roofline(best.spec, x.shape[0], mc.train.validSetRate,
                           total_epochs, time.time() - t_train)
    _save_dense_models(ctx, best, alg)
    _write_val_errors(ctx, best)
    return [best]


def _record_train_roofline(spec: nn_mod.MLPSpec, n_rows: int,
                           valid_rate: float, total_epochs: int,
                           wall: float) -> None:
    """Queue a `roofline` block for this command's steps.jsonl record:
    analytic per-row costs from the trained spec combined with the
    measured row-epochs/s (profiling.roofline). Wall covers the whole
    train loop (compile included), so the utilization figures are a
    floor."""
    from shifu_tpu import profiling
    try:
        n_train = max(int(n_rows * (1 - (valid_rate or 0.0))), 1)
        bpe = 2 if spec.compute_dtype == "bfloat16" else 4
        f, b = profiling.mlp_row_costs(spec.input_dim, spec.hidden_dims,
                                       spec.output_dim, dtype_bytes=bpe)
        profiling.set_step_extra("roofline", profiling.roofline(
            "NN", f, b, n_train * total_epochs / max(wall, 1e-9),
            compute_dtype=spec.compute_dtype))
    except Exception as e:  # noqa: BLE001 — metrics must never fail a run
        log.debug("roofline record skipped: %s", e)


def _conf_with_params(tc, params):
    import copy
    conf = copy.copy(tc)
    conf.params = params
    return conf


def _continuous_init(ctx: ProcessorContext, spec: nn_mod.MLPSpec,
                     seed: int = 12306):
    """Continuous training: resume from models/model0 when structure
    matches; absorb the old model into a LARGER new structure (old
    weights into the corner, 1-based FixedLayers freezing the absorbed
    indices); hard-error when the new structure cannot hold the old one
    (`NNMaster.initOrRecoverParams:356-387` absorbs via
    fitExistingModelIn / throws GuaguaRuntimeException on shrinkage;
    `NNStructureComparator`;
    `TrainModelProcessor.inputOutputModelCheckSuccess:1389-1450`).
    Returns (init_params, fixed_layers, grad_mask) — grad_mask is only
    set on the growth path, where frozen indices are element-wise."""
    mc = ctx.model_config
    if not mc.train.isContinuous:
        return None, None, None
    path = ctx.path_finder.model_path(0)
    if not os.path.exists(path):
        log.info("continuous training: no existing model at %s, fresh start",
                 path)
        return None, None, None
    kind, meta, params = load_model(path)
    old_spec = meta.get("spec", {})
    old_dims = [old_spec.get("input_dim")] \
        + list(old_spec.get("hidden_dims") or []) \
        + [old_spec.get("output_dim", 1)]
    fixed = mc.train.get_param("FixedLayers") or None
    if fixed is not None:
        fixed = [int(i) for i in fixed]
    cmp = nn_mod.compare_structure(old_dims, spec.layer_dims)
    if cmp == 0:
        return params, fixed, None
    if cmp < 0:
        # warn-and-discard would silently throw away the old model's
        # knowledge on the feature's primary use case — refuse instead
        raise ValueError(
            "continuous training: new network "
            f"{spec.layer_dims} cannot hold the existing model "
            f"{old_dims} (shrunk input/hidden/output). Grow the "
            "structure, or set train#isContinuous=false to retrain "
            "from scratch")
    log.info("continuous training: absorbing existing model %s into "
             "larger structure %s%s", old_dims, spec.layer_dims,
             f" (FixedLayers={fixed})" if fixed else "")
    import jax
    fresh = nn_mod.init_params(spec, jax.random.PRNGKey(seed))
    grown, grad_mask = nn_mod.absorb_params(params, fresh,
                                            fixed_layers=fixed)
    # fixed_layers=None: the element-wise grad_mask already encodes the
    # frozen absorbed indices; passing both would re-freeze whole layers
    return grown, None, grad_mask


def _train_kfold(conf, spec, x, y, w, k: int, seed: int) -> TrainResult:
    """K-fold CV: average validation error across folds, keep the
    best-fold model (`TrainModelProcessor.postProcess4KFoldCV:929-954`)."""
    rng = np.random.default_rng(seed)
    fold_of = rng.integers(0, k, len(y))
    fold_results = []
    for f in range(k):
        vmask = fold_of == f
        res = train_nn(conf, x[~vmask], y[~vmask], w[~vmask], seed=seed + f,
                       spec=spec, val_data=(x[vmask], y[vmask], w[vmask]))
        fold_results.append(res)
    avg_val = float(np.mean([r.best_val.min() for r in fold_results]))
    log.info("k-fold (%d folds) average val error: %.6f", k, avg_val)
    best = min(fold_results, key=lambda r: float(r.best_val.min()))
    return best


def _dense_spec_meta(ctx: ProcessorContext, spec: nn_mod.MLPSpec,
                     meta: Optional[Dict] = None) -> Dict:
    mc = ctx.model_config
    if meta is None:
        # meta.json alone carries denseNames — never reload data.npz
        # here (the streaming path exists to keep it out of host RAM)
        meta = norm_proc.load_normalized_meta(
            ctx.path_finder.normalized_data_path())
    out = {
        "spec": {
            "input_dim": spec.input_dim,
            "hidden_dims": list(spec.hidden_dims),
            "activations": list(spec.activations),
            "output_dim": spec.output_dim,
            "output_activation": spec.output_activation,
            "dropout_rate": 0.0,  # inference never drops
            "l2": spec.l2, "l1": spec.l1,
            "loss": spec.loss, "weight_init": spec.weight_init,
            # training-dtype provenance: scoring rebuilds the spec from
            # this dict, so a bf16-trained model scores in bf16 too
            "compute_dtype": spec.compute_dtype,
        },
        "inputNames": meta["denseNames"],
        "normType": mc.normalize.normType.value,
        "modelSetName": mc.model_set_name,
    }
    if mc.is_multi_classification:
        out["classes"] = mc.class_tags
    return out


def _save_dense_models(ctx: ProcessorContext, res: TrainResult,
                       alg: Algorithm) -> None:
    kind = {"NN": "nn", "LR": "lr", "SVM": "lr"}.get(alg.value, "nn")
    spec_meta = _dense_spec_meta(ctx, res.spec)
    for i, params in enumerate(res.params_per_bag):
        path = ctx.path_finder.model_path(i, kind)
        ctx.path_finder.ensure(path)
        save_model(path, kind, spec_meta, params)
    log.info("saved %d %s model(s) under %s", len(res.params_per_bag),
             kind, ctx.path_finder.models_path())


def _train_dense_streaming(ctx: ProcessorContext,
                           seed: int) -> List[TrainResult]:
    """train#trainOnDisk — >HBM datasets stream as memory-mapped row
    chunks with double-buffered host→device transfer
    (train/streaming.py; MemoryDiskFloatMLDataSet's disk-spill analog).
    Grid search / k-fold are full-batch features and are ignored here."""
    import jax
    import jax.numpy as jnp

    from shifu_tpu.train.streaming import train_nn_streaming
    mc = ctx.model_config
    path = ctx.path_finder.normalized_data_path()
    dense_p = os.path.join(path, "dense.npy")
    if not os.path.exists(dense_p):
        raise FileNotFoundError(
            f"streaming layout not found at {path}; run `norm` with "
            "train#trainOnDisk=true so dense.npy/tags.npy are written")
    from shifu_tpu.train.streaming import (mmap_layout,
                                           streaming_train_args,
                                           upsampled_weights)
    dense, tags, weights = mmap_layout(path, "dense", "tags", "weights")

    def get_chunk(a, b):
        # keep the stored dtype: an f16 layout transfers at half the
        # bytes and widens on device (streaming core's _upcast)
        x = np.asarray(dense[a:b])
        y = np.asarray(tags[a:b], np.float32)
        w = upsampled_weights(y, np.asarray(weights[a:b], np.float32),
                              mc.train.upSampleWeight)
        return x, y, w

    alg = mc.train.algorithm
    if alg is Algorithm.LR:
        spec = _lr_spec(mc.train.params, dense.shape[1])
    elif alg is Algorithm.SVM:
        spec = _svm_spec(mc.train.params, dense.shape[1])
    else:
        spec = None
    init_params, fixed, gmask = _continuous_init(
        ctx, spec or nn_mod.MLPSpec.from_train_params(mc.train.params,
                                                      dense.shape[1]),
        seed)
    meta = norm_proc.load_normalized_meta(path)
    from shifu_tpu.train.streaming import (checkpoint_args,
                                           cleanup_checkpoints)
    chunk_rows, n_val = streaming_train_args(mc, meta)
    ck_dir, ck_int = checkpoint_args(mc, ctx, "streaming")
    res = train_nn_streaming(mc.train, get_chunk, len(tags), dense.shape[1],
                             seed=seed, spec=spec, chunk_rows=chunk_rows,
                             n_val=n_val,
                             bag_labels=lambda a, b: np.asarray(
                                 tags[a:b], np.float32),
                             checkpoint_dir=ck_dir,
                             checkpoint_interval=ck_int,
                             init_params=(jax.tree.map(jnp.asarray,
                                                       init_params)
                                          if init_params is not None
                                          else None),
                             fixed_layers=fixed, grad_mask=gmask)
    _save_dense_models(ctx, res, alg)
    _write_val_errors(ctx, res)
    cleanup_checkpoints(ck_dir)
    return [res]


def _train_dense_ovr(ctx: ProcessorContext, x: np.ndarray, y: np.ndarray,
                     w: np.ndarray, classes: List[str],
                     seed: int) -> List[TrainResult]:
    """ONEVSALL multi-class: class c's model is a binary model on
    y==c — the reference submits these as parallel one-vs-all
    regression jobs; here they are sequential jitted trainings sharing
    the compiled step (identical shapes → one XLA compile).
    Grid search / k-fold are not combined with ONEVSALL (first combo
    wins, as the reference never tunes per-class jobs)."""
    mc = ctx.model_config
    alg = mc.train.algorithm
    kind = {"NN": "nn", "LR": "lr", "SVM": "lr"}.get(alg.value, "nn")

    combos = grid_search.expand(mc.train.params)
    if len(combos) > 1 or (mc.train.numKFold or 0) > 1:
        log.warning("ONEVSALL: grid search / k-fold ignored; using the "
                    "first parameter combination")
    params0 = combos[0]

    def make_spec():
        if alg is Algorithm.LR:
            return _lr_spec(params0, x.shape[1])
        if alg is Algorithm.SVM:
            return _svm_spec(params0, x.shape[1])
        return nn_mod.MLPSpec.from_train_params(params0, x.shape[1])

    conf = _conf_with_params(mc.train, params0)
    conf.baggingNum = 1  # one model per class, like one job per class
    _, norm_meta = _load_dense_training_data(ctx)
    results: List[TrainResult] = []
    for c in range(len(classes)):
        y_c = (y == c).astype(np.float32)
        res = train_nn(conf, x, y_c, w, seed=seed + c, spec=make_spec())
        meta = _dense_spec_meta(ctx, res.spec, norm_meta)
        meta["ovaClass"] = c
        path = ctx.path_finder.model_path(c, kind)
        ctx.path_finder.ensure(path)
        save_model(path, kind, meta, res.params_per_bag[0])
        results.append(res)
        log.info("one-vs-all class %d (%s): best val err %.6f", c,
                 classes[c], float(res.best_val.min()))
    # per-class validation curves, one entry per class model
    vpath = ctx.path_finder.val_error_path()
    ctx.path_finder.ensure(vpath)
    from shifu_tpu.resilience import atomic_write
    with atomic_write(vpath) as f:
        json.dump({"bestValError": [float(r.best_val.min()) for r in results],
                   "bestEpoch": [int(r.best_epoch[0]) for r in results],
                   "wallSeconds": sum(r.wall_seconds for r in results),
                   "classes": [str(c) for c in classes]}, f, indent=1)
    return results


def _write_val_errors(ctx: ProcessorContext, res: TrainResult) -> None:
    path = ctx.path_finder.val_error_path()
    ctx.path_finder.ensure(path)
    from shifu_tpu.resilience import atomic_write
    with atomic_write(path) as f:
        json.dump({"bestValError": [float(v) for v in res.best_val],
                   "bestEpoch": [int(e) for e in res.best_epoch],
                   "wallSeconds": res.wall_seconds}, f, indent=1)
