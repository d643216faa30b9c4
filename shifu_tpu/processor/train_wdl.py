"""WDL training step — wide-and-deep over *_INDEX-normalized data
(mirrors `wdl/WDLMaster/WDLWorker` wiring in
`TrainModelProcessor.prepareWDLParams:1675-1690`)."""

from __future__ import annotations

import logging
import os
import time
from typing import Any, Optional, Tuple

import jax
import numpy as np
import optax

from shifu_tpu.config.model_config import ModelTrainConf
from shifu_tpu.models import wdl
from shifu_tpu.models.spec import save_model
from shifu_tpu.obs import trace as obs_trace
from shifu_tpu.parallel import mesh as mesh_mod
from shifu_tpu.processor import norm as norm_proc
from shifu_tpu.processor.base import ProcessorContext
from shifu_tpu.train.optimizers import (optimizer_from_params,
                                        program_static)
from shifu_tpu.train.trainer import (TrainResult, bag_row_weights,
                                     bags_drawn, objectives,
                                     split_validation, train_bags)

log = logging.getLogger("shifu_tpu")


TABLE_LEAVES = ("embed", "wide_cat")


class _TablesScoped(optax.GradientTransformation):
    """An optimizer that also says how its update is applied:
    `train_bags_carry` adds the update through `apply_updates` where an
    optimizer has one. The two tables' add lies under the scope their
    update lies under, so the one fused pass XLA makes of both (value,
    accumulator and gradient read, value and accumulator written) reads
    as `update/table_update` and not as the MLP's `update`. Metadata
    only: the adds are `optax.apply_updates`' own."""

    @staticmethod
    def apply_updates(params, updates):
        out = {}
        for k, v in params.items():
            if k in TABLE_LEAVES:
                with jax.named_scope("table_update"):
                    out[k] = optax.apply_updates(v, updates[k])
            else:
                out[k] = optax.apply_updates(v, updates[k])
        return out


@program_static
def _tables_scoped(optimizer: optax.GradientTransformation
                   ) -> optax.GradientTransformation:
    """The same optimizer with the two tables' update, and the add that
    applies it, under the device scope `table_update`, so a trace tells
    the table pass from the MLP's. Every `Propagation` rule is per
    element, so the two halves update exactly as the whole did. One
    wrapper an optimizer, as the optimizer is one object a setting."""
    def scoped_update(grads, state, params=None):
        with jax.named_scope("table_update"):
            return optimizer.update(grads, state, params)

    def labels(params):
        return {k: jax.tree.map(
            lambda _: "tables" if k in TABLE_LEAVES else "rest", v)
            for k, v in params.items()}

    return _TablesScoped(*optax.multi_transform(
        {"tables": optax.GradientTransformation(optimizer.init,
                                                scoped_update),
         "rest": optimizer}, labels))


def train_wdl(train_conf: ModelTrainConf, dense, idx, y, w, vocab_sizes,
              seed: int = 12306,
              val_data: Optional[Tuple[Any, Any, Any, Any]] = None
              ) -> TrainResult:
    """Train `baggingNum` wide-and-deep models at once over resident
    rows: what `run_wdl` calls once `norm`'s matrix is loaded, as
    `train_nn` is to `processor/train.py`. `dense` (N, Dd) float, `idx`
    (N, Cc) int32 per-column ids, `y`/`w` (N,), host or device arrays;
    `vocab_sizes` the table rows of each categorical column, missing
    slot included (`norm`'s `indexVocabSizes`). `val_data` = (dense,
    idx, y, w) overrides the random validSetRate split. train#params
    MiniBatchRows > 0 trains in shuffled mini-batches (see
    `train_bags`); device inputs then stay on the device. The bag
    weights are `trainer.bag_row_weights`': one bag at rate >= 1.0
    without replacement is `w` itself with a leading axis (a view of a
    host array), and `y` comes to the host only for a stratified or
    neg-only draw."""
    t0 = time.time()
    spec = wdl.WDLSpec.from_train_params(train_conf.params, dense.shape[1],
                                         idx.shape[1], vocab_sizes)
    n_bags = max(train_conf.baggingNum, 1)
    batch_rows = int(train_conf.get_param("MiniBatchRows", 0) or 0)
    n_rows = int(y.shape[0])
    with obs_trace.span("train.job", family="wdl", rows=n_rows,
                        steps=train_conf.numTrainEpochs, bags=n_bags,
                        bags_drawn=int(bags_drawn(train_conf, n_bags)),
                        batches=(-(-n_rows // batch_rows)
                                 if 0 < batch_rows < n_rows else 1),
                        lookups=n_rows * spec.n_cat
                        * train_conf.numTrainEpochs):
        with obs_trace.span("train.prepare"):
            if val_data is not None:
                d_tr, i_tr, y_tr, w_tr = dense, idx, y, w
                d_v, i_v, y_v, w_v = val_data
            else:
                tr_mask, val_mask = split_validation(
                    n_rows, train_conf.validSetRate, seed)
                d_tr, i_tr, y_tr, w_tr = (a[tr_mask]
                                          for a in (dense, idx, y, w))
                d_v, i_v, y_v, w_v = (a[val_mask]
                                      for a in (dense, idx, y, w))
            bag_w = bag_row_weights(train_conf, y_tr, w_tr, n_bags, seed)

            # rows shard over 'data'; with SHIFU_TPU_MESH_MODEL > 1 the
            # embedding + wide tables additionally shard over 'model' by
            # row (the vocab-heavy leaves that data-parallel would
            # replicate per chip), padded so that the split is even
            mesh = mesh_mod.default_mesh()
            n_model = mesh.shape.get("model", 1)
            key = jax.random.PRNGKey(seed)
            bag_keys = jax.random.split(key, n_bags)
            stacked = jax.vmap(lambda k: wdl.pad_tables(
                wdl.init_params(spec, k), n_model))(bag_keys)
            loss, metric = objectives(wdl, spec)
            optimizer = _tables_scoped(
                optimizer_from_params(train_conf.params))
            ew = train_conf.earlyStoppingRounds
            shardings = None
            if n_model > 1:
                one = jax.tree.map(lambda l: l[0], stacked)
                shardings = mesh_mod.wdl_train_shardings(mesh, one)
        best_params, train_errs, val_errs, best_val, best_epoch = train_bags(
            loss, metric, optimizer, train_conf.numTrainEpochs,
            ew if ew and ew > 0 else 0,
            float(train_conf.convergenceThreshold or 0.0),
            stacked, (d_tr, i_tr, y_tr), bag_w, (d_v, i_v, y_v), w_v,
            bag_keys, None, batch_rows=batch_rows, perm_seed=seed,
            param_shardings=shardings)

        with obs_trace.span("train.fetch"):
            res = TrainResult(
                spec=spec,
                params_per_bag=[wdl.file_params(spec, jax.tree.map(
                    lambda a, i=i: np.asarray(a[i]), best_params))
                    for i in range(n_bags)],
                train_errors=np.asarray(train_errs),
                val_errors=np.asarray(val_errs),
                best_val=np.asarray(best_val),
                best_epoch=np.asarray(best_epoch),
                wall_seconds=time.time() - t0)
    log.info("train[WDL]: %d bag(s), %d epochs, best val %s in %.2fs",
             n_bags, train_conf.numTrainEpochs,
             np.round(res.best_val, 6).tolist(), res.wall_seconds)
    return res


def run_wdl(ctx: ProcessorContext, seed: int = 12306):
    mc = ctx.model_config
    path = ctx.path_finder.normalized_data_path()
    if mc.train.trainOnDisk:
        return _run_wdl_streaming(ctx, seed)
    if not os.path.exists(os.path.join(path, "data.npz")):
        raise FileNotFoundError(f"normalized data not found at {path}; "
                                "run `norm` first (WDL needs an *_INDEX "
                                "normType)")
    data, meta = norm_proc.load_normalized(path)
    dense = data["dense"].astype(np.float32)
    idx = data["index"].astype(np.int32)
    y = data["tags"].astype(np.float32)
    w = data["weights"].astype(np.float32)

    if mc.train.upSampleWeight != 1.0:
        # duplicate-positive rebalance expressed as weight upsampling
        # (core/shuffle rebalance + train#upSampleWeight)
        w = w * np.where(y > 0.5, np.float32(mc.train.upSampleWeight), 1.0)
    if idx.shape[1] == 0:
        log.warning("WDL without categorical index block — deep-only model")

    res = train_wdl(mc.train, dense, idx, y, w, meta["indexVocabSizes"],
                    seed=seed)
    spec_meta = _wdl_spec_meta(mc, res.spec, meta)
    for i, p in enumerate(res.params_per_bag):
        path = ctx.path_finder.model_path(i, "wdl")
        ctx.path_finder.ensure(path)
        save_model(path, "wdl", spec_meta, p)
    return None


def _wdl_spec_meta(mc, spec, meta):
    return {
        "kind": "wdl",
        "spec": {"dense_dim": spec.dense_dim, "n_cat": spec.n_cat,
                 "vocab_sizes": list(spec.vocab_sizes),
                 "embed_size": spec.embed_size,
                 "hidden_dims": list(spec.hidden_dims),
                 "activations": list(spec.activations), "l2": spec.l2,
                 "wide_enable": spec.wide_enable,
                 "deep_enable": spec.deep_enable},
        "denseNames": meta["denseNames"], "indexNames": meta["indexNames"],
        "indexVocabSizes": meta["indexVocabSizes"],
        "normType": mc.normalize.normType.value,
        "modelSetName": mc.model_set_name,
    }


def _run_wdl_streaming(ctx: ProcessorContext, seed: int):
    """train#trainOnDisk for WDL: mmap'd dense + index chunks stream
    through the shared double-buffered core (the Criteo-scale family
    IS the >RAM case — reference WDLWorker holds its split in RAM)."""
    from shifu_tpu.train.streaming import train_wdl_streaming
    t0 = time.time()
    mc = ctx.model_config
    path = ctx.path_finder.normalized_data_path()
    dense_p = os.path.join(path, "dense.npy")
    if not os.path.exists(dense_p):
        raise FileNotFoundError(
            f"streaming layout not found at {path}; run `norm` with "
            "train#trainOnDisk=true so dense/index .npy blocks are "
            "written")
    if not os.path.exists(os.path.join(path, "index.npy")):
        # same behavior as the resident path: deep-only model
        log.warning("WDL without categorical index block — deep-only "
                    "model")
    meta = norm_proc.load_normalized_meta(path)
    from shifu_tpu.train.streaming import (checkpoint_args,
                                           cleanup_checkpoints,
                                           mmap_layout,
                                           streaming_train_args,
                                           upsampled_weights)
    dense, idx, tags, weights = mmap_layout(path, "dense", "index",
                                            "tags", "weights")

    def get_chunk(a, b):
        y = np.asarray(tags[a:b], np.float32)
        w = upsampled_weights(y, np.asarray(weights[a:b], np.float32),
                              mc.train.upSampleWeight)
        i_blk = (np.asarray(idx[a:b], np.int32) if idx is not None
                 else np.zeros((b - a, 0), np.int32))
        # stored dtype preserved: f16 layouts transfer at half
        # the bytes and widen on device
        return (np.asarray(dense[a:b]), i_blk, y, w)

    n_cat = idx.shape[1] if idx is not None else 0
    spec = wdl.WDLSpec.from_train_params(mc.train.params, dense.shape[1],
                                         n_cat, meta["indexVocabSizes"])
    chunk_rows, n_val = streaming_train_args(mc, meta)
    ck_dir, ck_int = checkpoint_args(mc, ctx, "streaming-wdl")
    res = train_wdl_streaming(mc.train, get_chunk, len(tags), spec,
                              seed=seed, chunk_rows=chunk_rows,
                              n_val=n_val, checkpoint_dir=ck_dir,
                              checkpoint_interval=ck_int,
                              bag_labels=lambda a, b: np.asarray(
                                  tags[a:b], np.float32))
    spec_meta = _wdl_spec_meta(mc, spec, meta)
    for i, p in enumerate(res.params_per_bag):
        out = ctx.path_finder.model_path(i, "wdl")
        ctx.path_finder.ensure(out)
        save_model(out, "wdl", spec_meta, wdl.file_params(spec, p))
    cleanup_checkpoints(ck_dir)
    log.info("train[WDL streaming]: %d bag(s), best val %s in %.2fs",
             len(res.params_per_bag),
             np.round(np.asarray(res.best_val), 6).tolist(),
             time.time() - t0)
    return None
