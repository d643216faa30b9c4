"""WDL training step — wide-and-deep over *_INDEX-normalized data
(mirrors `wdl/WDLMaster/WDLWorker` wiring in
`TrainModelProcessor.prepareWDLParams:1675-1690`)."""

from __future__ import annotations

import logging
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from shifu_tpu.models import wdl
from shifu_tpu.models.spec import save_model
from shifu_tpu.obs import trace as obs_trace
from shifu_tpu.processor import norm as norm_proc
from shifu_tpu.processor.base import ProcessorContext
from shifu_tpu.train.optimizers import optimizer_from_params
from shifu_tpu.train.trainer import (bagging_weights, split_validation,
                                     train_bags)

log = logging.getLogger("shifu_tpu")


def run_wdl(ctx: ProcessorContext, seed: int = 12306):
    t0 = time.time()
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

    vocab = max(meta["indexVocabSizes"], default=1)
    spec = wdl.WDLSpec.from_train_params(mc.train.params, dense.shape[1],
                                         idx.shape[1], vocab)

    n_bags = max(mc.train.baggingNum, 1)
    with obs_trace.span("train.job", family="wdl", rows=len(y),
                        steps=mc.train.numTrainEpochs, bags=n_bags):
        with obs_trace.span("train.prepare"):
            tr_mask, val_mask = split_validation(
                len(y), mc.train.validSetRate, seed)
            bag_w = bagging_weights(int(tr_mask.sum()), n_bags,
                                    mc.train.baggingSampleRate,
                                    mc.train.baggingWithReplacement, seed,
                                    labels=np.asarray(y[tr_mask]),
                                    stratified=mc.train.stratifiedSample,
                                    neg_only=mc.train.sampleNegOnly) \
                * w[tr_mask][None, :]

            key = jax.random.PRNGKey(seed)
            bag_keys = jax.random.split(key, n_bags)
            stacked = jax.vmap(lambda k: wdl.init_params(spec, k))(bag_keys)
            grad_mask = jax.tree.map(lambda l: jnp.ones_like(l[0]), stacked)

            def loss(params, inputs, w_, key_):
                d_, i_, y_ = inputs
                return wdl.loss_fn(spec, params, d_, i_, y_, w_)

            def metric(params, inputs, w_):
                d_, i_, y_ = inputs
                return wdl.mse(spec, params, d_, i_, y_, w_)

            optimizer = optimizer_from_params(mc.train.params)
            ew = mc.train.earlyStoppingRounds
            # rows shard over 'data'; with SHIFU_TPU_MESH_MODEL > 1 the
            # embedding + wide tables additionally shard over 'model' (the
            # vocab-heavy leaves that data-parallel would replicate per chip)
            from shifu_tpu.parallel import mesh as mesh_mod
            mesh = mesh_mod.default_mesh()
            shardings = None
            if mesh.shape.get("model", 1) > 1:
                one = jax.tree.map(lambda l: l[0], stacked)
                shardings = mesh_mod.wdl_train_shardings(mesh, one)
        best_params, train_errs, val_errs, best_val, best_epoch = train_bags(
            loss, metric, optimizer, mc.train.numTrainEpochs,
            ew if ew and ew > 0 else 0,
            float(mc.train.convergenceThreshold or 0.0),
            stacked,
            (dense[tr_mask], idx[tr_mask], y[tr_mask]),
            bag_w,
            (dense[val_mask], idx[val_mask], y[val_mask]),
            w[val_mask], bag_keys, grad_mask, param_shardings=shardings)

        with obs_trace.span("train.fetch"):
            spec_meta = _wdl_spec_meta(mc, spec, meta)
            for i in range(n_bags):
                p = jax.tree.map(lambda a, i=i: np.asarray(a[i]), best_params)
                path = ctx.path_finder.model_path(i, "wdl")
                ctx.path_finder.ensure(path)
                save_model(path, "wdl", spec_meta, p)
    log.info("train[WDL]: %d bag(s), best val %s in %.2fs", n_bags,
             np.round(np.asarray(best_val), 6).tolist(), time.time() - t0)
    return None


def _wdl_spec_meta(mc, spec, meta):
    return {
        "kind": "wdl",
        "spec": {"dense_dim": spec.dense_dim, "n_cat": spec.n_cat,
                 "vocab_size": spec.vocab_size,
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

    vocab = max(meta["indexVocabSizes"], default=1)
    n_cat = idx.shape[1] if idx is not None else 0
    spec = wdl.WDLSpec.from_train_params(mc.train.params, dense.shape[1],
                                         n_cat, vocab)
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
        save_model(out, "wdl", spec_meta, p)
    cleanup_checkpoints(ck_dir)
    log.info("train[WDL streaming]: %d bag(s), best val %s in %.2fs",
             len(res.params_per_bag),
             np.round(np.asarray(res.best_val), 6).tolist(),
             time.time() - t0)
    return None
