"""MTL training step — shared-trunk multi-task model.

Mirrors `mtl/MTLMaster/MTLWorker` wiring
(`TrainModelProcessor.prepareMTLParams:1658-1673`): '|'-separated
targetColumnName defines the task list; each task is a binary tag
parsed with the shared pos/neg tags. Rows missing a task's label
contribute no loss for that task (NaN-masked). Round-1 limitation:
rows missing the FIRST task's label are dropped by the norm step's
row filter."""

from __future__ import annotations

import logging
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from shifu_tpu.data.dataset import parse_tags
from shifu_tpu.data.purifier import DataPurifier
from shifu_tpu.data.reader import read_raw_table, simple_column_name
from shifu_tpu.models import mtl
from shifu_tpu.models.spec import save_model
from shifu_tpu.obs import trace as obs_trace
from shifu_tpu.processor import norm as norm_proc
from shifu_tpu.processor.base import ProcessorContext
from shifu_tpu.train.optimizers import optimizer_from_params
from shifu_tpu.train.trainer import (bag_row_weights, bags_drawn, objectives,
                                     split_validation, train_bags)

log = logging.getLogger("shifu_tpu")


def task_names(mc) -> list:
    return [simple_column_name(t) for t in
            mc.dataSet.targetColumnName.split("|") if t.strip()]


def load_task_targets(ctx: ProcessorContext, data: dict) -> np.ndarray:
    """(R, T) per-task tags. The norm step persists them in data.npz
    (`task_tags`), already aligned with its row filter; a raw re-read
    fallback covers normalized data written before MTL support."""
    if "task_tags" in data and data["task_tags"].size:
        return data["task_tags"].astype(np.float32)
    mc = ctx.model_config
    df = read_raw_table(mc)
    if mc.dataSet.filterExpressions:
        keep = DataPurifier(mc.dataSet.filterExpressions).apply(df)
        df = df[keep].reset_index(drop=True)
    names = task_names(mc)
    cols = []
    for t in names:
        raw = df[t].astype(str).str.strip().to_numpy()
        cols.append(parse_tags(raw, mc.pos_tags, mc.neg_tags))
    y = np.stack(cols, axis=1)
    # norm step drops rows whose FIRST task tag is invalid — align
    return y[~np.isnan(y[:, 0])]


def run_mtl(ctx: ProcessorContext, seed: int = 12306):
    """Train `baggingNum` multi-task models over `norm`'s resident
    matrix and save them (`train#trainOnDisk` streams instead). Bags
    are stratified or neg-sampled on the primary task's label; the bag
    weights are `trainer.bag_row_weights`': one bag at rate >= 1.0
    without replacement is the training rows' `w` itself with a leading
    axis (a view, never written to), and the labels are read only for
    a stratified or neg-only draw."""
    t0 = time.time()
    mc = ctx.model_config
    path = ctx.path_finder.normalized_data_path()
    if mc.train.trainOnDisk:
        return _run_mtl_streaming(ctx, seed)
    if not os.path.exists(os.path.join(path, "data.npz")):
        raise FileNotFoundError(f"normalized data not found at {path}; "
                                "run `norm` first")
    data, meta = norm_proc.load_normalized(path)
    dense = data["dense"].astype(np.float32)
    w = data["weights"].astype(np.float32)
    y = load_task_targets(ctx, data)
    if mc.train.upSampleWeight != 1.0:
        w = w * np.where(y[:, 0] > 0.5, np.float32(mc.train.upSampleWeight),
                         1.0)
    if len(y) != len(dense):
        raise ValueError(f"MTL target rows {len(y)} != normalized rows "
                         f"{len(dense)}")
    names = task_names(mc)
    spec = mtl.MTLSpec.from_train_params(mc.train.params, dense.shape[1],
                                         len(names))

    n_bags = max(mc.train.baggingNum, 1)
    with obs_trace.span("train.job", family="mtl", rows=len(y),
                        steps=mc.train.numTrainEpochs, bags=n_bags,
                        bags_drawn=int(bags_drawn(mc.train, n_bags))):
        with obs_trace.span("train.prepare"):
            tr_mask, val_mask = split_validation(
                len(y), mc.train.validSetRate, seed)
            # stratify/neg-sample on the primary task's label (task 0 — the
            # same label upSampleWeight keys on above)
            y_tr = y[tr_mask]
            bag_w = bag_row_weights(mc.train, y_tr[:, 0], w[tr_mask],
                                    n_bags, seed)

            key = jax.random.PRNGKey(seed)
            bag_keys = jax.random.split(key, n_bags)
            stacked = jax.vmap(lambda k: mtl.init_params(spec, k))(bag_keys)
            grad_mask = jax.tree.map(lambda l: jnp.ones_like(l[0]), stacked)
            loss, metric = objectives(mtl, spec)
            optimizer = optimizer_from_params(mc.train.params)
            ew = mc.train.earlyStoppingRounds
            # train_bags shards rows / replicates params over the default mesh
            # with SHIFU_TPU_MESH_MODEL > 1, per-task head rows shard over
            # 'model' (tasks are independent); the shared trunk replicates
            from shifu_tpu.parallel import mesh as mesh_mod
            mesh = mesh_mod.default_mesh()
            shardings = None
            if mesh.shape.get("model", 1) > 1:
                one = jax.tree.map(lambda l: l[0], stacked)
                shardings = mesh_mod.mtl_train_shardings(mesh, one)
        best_params, _, _, best_val, _ = train_bags(
            loss, metric, optimizer, mc.train.numTrainEpochs,
            ew if ew and ew > 0 else 0,
            float(mc.train.convergenceThreshold or 0.0),
            stacked, (dense[tr_mask], y_tr),
            bag_w,
            (dense[val_mask], y[val_mask]),
            w[val_mask], bag_keys, grad_mask, param_shardings=shardings)

        with obs_trace.span("train.fetch"):
            spec_meta = _mtl_spec_meta(mc, spec, names, meta)
            for i in range(n_bags):
                p = jax.tree.map(lambda a, i=i: np.asarray(a[i]), best_params)
                mpath = ctx.path_finder.model_path(i, "mtl")
                ctx.path_finder.ensure(mpath)
                save_model(mpath, "mtl", spec_meta, p)
    log.info("train[MTL]: %d tasks, %d bag(s), best val %s in %.2fs",
             len(names), n_bags, np.round(np.asarray(best_val), 6).tolist(),
             time.time() - t0)
    return None


def _mtl_spec_meta(mc, spec, names, meta):
    return {
        "kind": "mtl",
        "spec": {"input_dim": spec.input_dim, "n_tasks": spec.n_tasks,
                 "hidden_dims": list(spec.hidden_dims),
                 "activations": list(spec.activations), "l2": spec.l2},
        "taskNames": names, "denseNames": meta["denseNames"],
        "normType": mc.normalize.normType.value,
        "modelSetName": mc.model_set_name,
    }


def _run_mtl_streaming(ctx: ProcessorContext, seed: int):
    """train#trainOnDisk for MTL: mmap'd dense + (R, T) task-tag
    chunks through the shared streaming core."""
    from shifu_tpu.train.streaming import (checkpoint_args,
                                           cleanup_checkpoints,
                                           mmap_layout,
                                           streaming_train_args,
                                           train_streaming_core,
                                           upsampled_weights)
    t0 = time.time()
    mc = ctx.model_config
    path = ctx.path_finder.normalized_data_path()
    dense, task_tags, weights = mmap_layout(
        path, "dense", "task_tags", "weights")
    if dense is None:
        raise FileNotFoundError(
            f"streaming layout not found at {path}; run `norm` with "
            "train#trainOnDisk=true")
    if task_tags is None:
        raise FileNotFoundError(
            "MTL needs the task_tags block; re-run `norm` (multi-task "
            "targetColumnName) with train#trainOnDisk=true")
    meta = norm_proc.load_normalized_meta(path)
    names = task_names(mc)
    spec = mtl.MTLSpec.from_train_params(mc.train.params, dense.shape[1],
                                         len(names))

    def get_chunk(a, b):
        y = np.asarray(task_tags[a:b], np.float32)
        w = upsampled_weights(y[:, 0],
                              np.asarray(weights[a:b], np.float32),
                              mc.train.upSampleWeight)
        # stored dtype preserved: f16 layouts transfer at half
        # the bytes and widen on device
        return (np.asarray(dense[a:b]), y, w)

    def loss_fn(params, inputs, w_, key_):
        x_, y_ = inputs
        return mtl.loss_fn(spec, params, x_, y_, w_)

    def metric_sum_fn(params, inputs, w_):
        # mtl.mse's numerator (masked weighted error SUM) — the core
        # divides by the accumulated valid-mass, so chunks with uneven
        # labeled fractions can't bias the epoch metric vs resident
        x_, y_ = inputs
        p = mtl.forward(spec, params, x_)
        valid = ~jnp.isnan(y_)
        err = jnp.where(valid, jnp.square(jnp.where(valid, y_, 0.0) - p),
                        0.0)
        return jnp.sum(err * w_[:, None])

    def metric_mass_fn(inputs, w_):
        _, y_ = inputs
        return jnp.sum((~jnp.isnan(y_)) * w_[:, None])

    chunk_rows, n_val = streaming_train_args(mc, meta)
    ck_dir, ck_int = checkpoint_args(mc, ctx, "streaming-mtl")
    res = train_streaming_core(
        mc.train, get_chunk, len(weights), seed=seed,
        chunk_rows=chunk_rows,
        init_fn=lambda k: mtl.init_params(spec, k),
        loss_fn=loss_fn, metric_sum_fn=metric_sum_fn, n_val=n_val,
        spec=spec, metric_mass_fn=metric_mass_fn,
        checkpoint_dir=ck_dir, checkpoint_interval=ck_int,
        # primary (task-0) tag keys neg-only sampling, as resident MTL
        bag_labels=lambda a, b: np.asarray(task_tags[a:b, 0], np.float32))
    spec_meta = _mtl_spec_meta(mc, spec, names, meta)
    for i, p in enumerate(res.params_per_bag):
        out = ctx.path_finder.model_path(i, "mtl")
        ctx.path_finder.ensure(out)
        save_model(out, "mtl", spec_meta, p)
    cleanup_checkpoints(ck_dir)
    log.info("train[MTL streaming]: %d tasks, %d bag(s), best val %s "
             "in %.2fs", len(names), len(res.params_per_bag),
             np.round(np.asarray(res.best_val), 6).tolist(),
             time.time() - t0)
    return None
