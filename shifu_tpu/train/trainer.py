"""NN/LR training loop — the TPU replacement for Guagua BSP training.

The reference's flagship path (`TrainModelProcessor.runDistributedTrain`
→ Guagua master/worker iterations: workers run per-record backprop over
their HDFS split (`nn/ParallelGradient.java:186-297`), the master
aggregates gradients and applies `Weight.calculateWeights`
(`nn/NNMaster.java:214-337`)) collapses into ONE jitted program:

- "worker gradient over split, master aggregate" ≡ a full-batch
  `jax.grad` over the (sharded) HBM-resident matrix — the mean over
  rows IS the aggregation; under `shard_map` it is a `psum` over ICI.
- "iteration" ≡ one step of a `lax.scan` over epochs.
- "bagging jobs in parallel" (≤5 concurrent YARN jobs,
  `TrainModelProcessor.java:1016-1135`) ≡ `vmap` over the bag axis —
  every bag trains simultaneously on the same device pass, with
  per-bag Poisson/Bernoulli sample weights reproducing
  `AbstractNNWorker`'s Poisson bagging.
- early stop (window + convergence: `core/dtrain/earlystop/
  WindowEarlyStop.java`, `ConvergeAndValidToleranceEarlyStop.java`)
  runs in-graph: a stopped bag's parameters freeze while the scan
  completes, and best-validation parameters are tracked in the carry
  (NNOutput keeps the best tmp model).
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
import numpy as np
import optax

from shifu_tpu import resilience
from shifu_tpu.config.model_config import ModelTrainConf
from shifu_tpu.data import pipeline as pipe
from shifu_tpu.models import nn as nn_mod
from shifu_tpu.obs import trace as obs_trace
from shifu_tpu.ops import pallas_mlp
from shifu_tpu.parallel import mesh as mesh_mod
from shifu_tpu.train.optimizers import (optimizer_from_params,
                                        program_static)

log = logging.getLogger("shifu_tpu")


@dataclass
class TrainResult:
    spec: nn_mod.MLPSpec
    params_per_bag: List[Any]          # best-validation params, host-side
    train_errors: np.ndarray           # (bags, epochs)
    val_errors: np.ndarray             # (bags, epochs)
    best_val: np.ndarray               # (bags,)
    best_epoch: np.ndarray             # (bags,)
    wall_seconds: float = 0.0


def split_validation(n: int, valid_rate: float, seed: int,
                     cross_over: bool = False) -> Tuple[np.ndarray, np.ndarray]:
    """Random train/valid split (`AbstractNNWorker.init` validation
    sampling). Returns boolean masks (train, valid)."""
    rng = np.random.default_rng(seed)
    is_val = rng.random(n) < valid_rate
    if valid_rate <= 0.0:
        return np.ones(n, bool), np.zeros(n, bool)
    if is_val.all():
        is_val[0] = False
    if not is_val.any():
        is_val[-1] = True
    return ~is_val, is_val


def bagging_weights(n: int, n_bags: int, sample_rate: float,
                    with_replacement: bool, seed: int,
                    labels: Optional[np.ndarray] = None,
                    stratified: bool = False,
                    neg_only: bool = False) -> np.ndarray:
    """(bags, n) per-row multiplicities: Poisson(rate) for
    with-replacement (AbstractNNWorker Poisson bagging), Bernoulli mask
    otherwise. Bag 0 of a 1-bag run sees the full data (reference runs
    the plain training as bag 0).

    `neg_only` (train.sampleNegOnly, `wdl/WDLWorker.java:431-455`):
    positive records are always kept; only negatives are sampled at
    the bagging rate. `stratified` (train.stratifiedSample,
    `nn/AbstractNNWorker.java:173,216-222` per-class bagging random
    maps): each label class contributes exactly round(rate·n_class)
    rows per bag, removing class-imbalance variance from the bags.
    The reference's fixInitialInput (hash-range sampling so resumed
    runs see identical bags) is always-on here: weights derive from a
    fixed seed, so every resume replays the same bags.

    One bag at rate >= 1.0 without replacement is all ones in every
    branch: the trainers ask `bag_row_weights`, which takes that case
    without calling here, and which hands over `labels` only where
    `stratified` or `neg_only` reads them.
    """
    rng = np.random.default_rng(seed)
    if neg_only and stratified and labels is not None:
        log.warning("sampleNegOnly and stratifiedSample are both set: "
                    "neg-only sampling wins (every positive kept, "
                    "negatives rate-sampled); stratification is subsumed")
    if neg_only and labels is not None:
        lab = np.asarray(labels)
        # NaN labels (MTL primary-task gaps) are kept, like positives
        # (lab < 0.5 is False for NaN) — the streaming counterpart
        # (_chunk_bag_weights) mirrors this
        neg = lab < 0.5
        n_neg = int(neg.sum())
        if with_replacement:
            # Poisson bagging still applies to positives in the
            # reference (sampleNegOnly only DROPS negatives;
            # AbstractNNWorker keeps Poisson multiplicities for kept
            # rows) — force-keep clamps positives to ≥1 rather than
            # pinning them to exactly 1
            w = rng.poisson(sample_rate, size=(n_bags, n)) \
                .astype(np.float32)
            w[:, ~neg] = np.maximum(w[:, ~neg], 1.0)
        else:
            w = np.ones((n_bags, n), np.float32)
            w[:, neg] = rng.random((n_bags, n_neg)) < sample_rate
        return _rescue_empty_bags(w)
    if stratified and labels is not None:
        if sample_rate >= 1.0 and not with_replacement:
            if n_bags == 1:
                # keep-all IS the perfect stratified sample at rate 1.0
                return np.ones((1, n), np.float32)
            # N identical full-data bags are useless (same degrade as
            # the unstratified branch below) — use a BALANCED bootstrap:
            # per-class draws with replacement keep each bag's class mix
            # fixed instead of silently dropping stratification
            log.warning(
                "stratifiedSample with baggingSampleRate >= 1.0 and "
                "%d bags: using per-class balanced bootstrap (draw with "
                "replacement within each class)", n_bags)
            with_replacement = True
        lab = np.asarray(labels)
        w = np.zeros((n_bags, n), np.float32)
        valid = ~np.isnan(lab)
        for cls in np.unique(lab[valid]):
            idx = np.flatnonzero(valid & (lab == cls))
            k = max(1, int(round(sample_rate * len(idx))))
            for b in range(n_bags):
                if with_replacement:
                    np.add.at(w[b], rng.choice(idx, size=k, replace=True),
                              1.0)
                else:
                    w[b, rng.choice(idx, size=min(k, len(idx)),
                                    replace=False)] = 1.0
        nan_idx = np.flatnonzero(~valid)
        if len(nan_idx):
            # NaN labels (MTL primary-task gaps) have no class to
            # stratify into — they sample at the plain rate
            for b in range(n_bags):
                if with_replacement:
                    w[b, nan_idx] = rng.poisson(sample_rate, len(nan_idx))
                else:
                    w[b, nan_idx] = rng.random(len(nan_idx)) < sample_rate
        return _rescue_empty_bags(w)
    if n_bags == 1 and sample_rate >= 1.0 and not with_replacement:
        return np.ones((1, n), np.float32)
    if n_bags > 1 and sample_rate >= 1.0 and not with_replacement:
        # "100% sample without replacement" per bag would give every
        # bag the identical full dataset — N identical models at N×
        # cost. Degrade to Poisson(rate) resampling, which is what the
        # reference's per-bag worker actually does (AbstractNNWorker
        # Poisson bagging runs regardless of the replacement flag).
        with_replacement = True
    if with_replacement:
        w = rng.poisson(sample_rate, size=(n_bags, n)).astype(np.float32)
    else:
        w = (rng.random((n_bags, n)) < sample_rate).astype(np.float32)
    return _rescue_empty_bags(w)


def _rescue_empty_bags(w: np.ndarray) -> np.ndarray:
    """A bag with zero total weight would divide by ~0 — reset it to
    the full data (every bagging branch shares this guard)."""
    empty = w.sum(axis=1) == 0
    w[empty] = 1.0
    return w


def bags_drawn(train_conf: ModelTrainConf, n_bags: int) -> bool:
    """Whether a job's bags are a draw at all. One bag at
    `baggingSampleRate` >= 1.0 without replacement is the rows
    themselves whatever `stratifiedSample` and `sampleNegOnly` say
    (`bagging_weights` returns all ones in each of its branches): read
    from what the job states alone, it is `shifu:train.job`'s
    `bags_drawn`."""
    return not (n_bags == 1 and train_conf.baggingSampleRate >= 1.0
                and not train_conf.baggingWithReplacement)


def bag_row_weights(train_conf: ModelTrainConf, y_tr, w_tr, n_bags: int,
                    seed: int, neg_only: Optional[bool] = None):
    """(bags, n) training weight of each row in each bag, as
    `train_bags` takes it: `bagging_weights(...) * w_tr[None, :]`, bit
    for bit, for the three resident trainers (`train_nn`, `train_wdl`,
    `run_mtl`). `y_tr`, `w_tr` (n,) host or device arrays; `neg_only`
    overrides `train_conf.sampleNegOnly` (`train_nn` drops it for
    native multi-class).

    Where no bag is drawn (`bags_drawn`) the product is `w_tr` itself
    (`1.0 * w == w`), so `w_tr` goes back with a leading axis, in the
    product's dtype: a VIEW of a host array, which the caller must not
    write to, a reshape on the device for a device array; no row-sized
    array is built, multiplied or uploaded, and `y_tr` is not read.
    Every other bagging is `bagging_weights`' host draw, and `y_tr`
    comes to the host only where `stratifiedSample` or `neg_only` will
    read it."""
    if not bags_drawn(train_conf, n_bags):
        result_type = (jnp.result_type if isinstance(w_tr, jax.Array)
                       else np.result_type)
        return w_tr.reshape(1, -1).astype(
            result_type(np.float32, w_tr.dtype), copy=False)
    if neg_only is None:
        neg_only = train_conf.sampleNegOnly
    by_label = train_conf.stratifiedSample or neg_only
    return bagging_weights(
        int(w_tr.shape[0]), n_bags, train_conf.baggingSampleRate,
        train_conf.baggingWithReplacement, seed,
        labels=np.asarray(y_tr) if by_label else None,
        stratified=train_conf.stratifiedSample,
        neg_only=neg_only) * w_tr[None, :]


@partial(jax.jit, static_argnames=("loss_fn", "metric_fn", "optimizer",
                                   "n_epochs", "early_stop_window",
                                   "n_batches"),
         donate_argnames=("carry_in",))
def train_bags_carry(loss_fn, metric_fn, optimizer, n_epochs: int,
                     early_stop_window: int, convergence_threshold: float,
                     carry_in, train_inputs, w_train_bags,
                     val_inputs, w_val, grad_mask, n_batches: int = 1):
    """Generic vmapped-over-bags, scanned-over-epochs trainer (shared by
    NN/LR/WDL/MTL), resumable: takes and returns the full per-bag
    training carry (see init_train_carry) so callers can run in
    checkpointed chunks. The carry is DONATED: the program updates the
    parameters, optimizer state and best-epoch copy in the buffers it
    was given instead of copying each first (three table-sized copies
    for a WDL job), so a caller that still needs `carry_in` afterwards
    passes a copy.

    loss_fn(params, inputs_tuple, w, key) → scalar training loss;
    metric_fn(params, inputs_tuple, w) → scalar validation error.
    Both and `optimizer` are STATIC, and functions hash by identity: a
    job finds an earlier job's program only where all three are the
    same objects, so whoever trains with equal settings twice hands over
    equal objects twice (`program_static`: `nn_objectives`,
    `objectives`, `make_optimizer`), and a call whose epochs, batches
    and shapes repeat as well is a dispatch. Fresh closures are
    a fresh program every call: traced, lowered and read back from the
    compile cache, 0.7-1.3 s with the device idle. Whatever a static
    bakes into the trace has to be in the key it was made from.
    w_train_bags: (B, Nt) per-bag sample weights (bagging multiplicity ×
    row weight). grad_mask: pytree of {0,1} masking fixed layers
    (continuous training's frozen-layer fitting, NNMaster.java:369-379),
    or None where nothing is frozen (no multiply: a mask of ones costs a
    pass over every gradient, a gigabyte for a WDL table).

    n_batches > 1 switches one full-batch update per epoch to an inner
    scan of mini-batch updates (train#params MiniBatchRows): every row
    tensor arrives pre-reshaped to (n_batches, rows/batch, ...) and
    w_train_bags to (B, n_batches, rows/batch); batch order reshuffles
    per epoch via the carried PRNG key, a function of the bag's key
    alone: each epoch does `key, _ = split(key)`, `key, pkey =
    split(key)`, runs the batches in the order `jax.random.permutation(
    pkey, n_batches)`, and splits `key` once more a batch (`key, _ =
    split(key)`, the batch's dropout key). This is what keeps bagging /
    grid search / k-fold usable when bags × activations no longer fit
    HBM full-batch.
    """

    def masked(grads):
        if grad_mask is None:
            return grads
        return jax.tree.map(lambda g, m: g * m, grads, grad_mask)

    # an optimizer may say under which device scope each leaf's update
    # is added (`train_wdl._tables_scoped`); the adds are the same
    apply_updates = getattr(optimizer, "apply_updates",
                            optax.apply_updates)

    def one_bag(carry_in, w_train):

        def epoch_step(carry, e):
            params, opt_state, best, stop_state, key = carry
            best_params, best_val, bad_count, stopped = (
                best["params"], best["val"], stop_state["bad"],
                stop_state["stopped"])
            key, sub = jax.random.split(key)
            if n_batches > 1:
                def batch_step(bc, bi):
                    p, o, k = bc
                    k, bkey = jax.random.split(k)
                    inp_b = jax.tree.map(lambda t: t[bi], train_inputs)
                    with jax.named_scope("forward_loss"):
                        loss_b, grads_b = jax.value_and_grad(loss_fn)(
                            p, inp_b, w_train[bi], bkey)
                    with jax.named_scope("update"):
                        grads_b = masked(grads_b)
                        upd, o2 = optimizer.update(grads_b, o, p)
                        p2 = apply_updates(p, upd)
                    return (p2, o2, k), (loss_b, jnp.sum(w_train[bi]))

                key, pkey = jax.random.split(key)
                perm = jax.random.permutation(pkey, n_batches)
                (new_params, new_opt_state, key), (losses, wsums) = \
                    jax.lax.scan(batch_step, (params, opt_state, key), perm)
                # per-batch losses are already weight-normalized within
                # the batch; weight by batch mass so the zero-weight
                # padded tail (and weight-skewed batches) don't bias the
                # epoch error feeding convergenceThreshold (the
                # streaming trainer does the same per chunk)
                train_err = jnp.sum(losses * wsums) / \
                    jnp.maximum(jnp.sum(wsums), 1e-12)
            else:
                with jax.named_scope("forward_loss"):
                    train_err, grads = jax.value_and_grad(loss_fn)(
                        params, train_inputs, w_train, sub)
                with jax.named_scope("update"):
                    grads = masked(grads)
                    updates, new_opt_state = optimizer.update(
                        grads, opt_state, params)
                    new_params = apply_updates(params, updates)
            with jax.named_scope("update"):
                # freeze when stopped (scan must run to fixed length)
                keep = lambda new, old: jax.tree.map(  # noqa: E731
                    lambda a, b: jnp.where(stopped, b, a), new, old)
                params2 = keep(new_params, params)
                opt_state2 = jax.tree.map(
                    lambda a, b: jnp.where(stopped, b, a)
                    if a.shape == b.shape else a,
                    new_opt_state, opt_state)
            with jax.named_scope("validate"):
                val_err = metric_fn(params2, val_inputs, w_val)
            with jax.named_scope("select"):
                improved = val_err < best_val
                best_params2 = jax.tree.map(
                    lambda bp, p: jnp.where(improved & ~stopped, p, bp),
                    best_params, params2)
                best_val2 = jnp.where(improved & ~stopped, val_err,
                                      best_val)
                bad2 = jnp.where(stopped, bad_count,
                                 jnp.where(improved, 0, bad_count + 1))
                window_stop = (early_stop_window > 0) & \
                    (bad2 >= early_stop_window)
                converge_stop = (convergence_threshold > 0.0) & \
                    (train_err <= convergence_threshold)
                stopped2 = stopped | window_stop | converge_stop
            carry2 = (params2, opt_state2,
                      {"params": best_params2, "val": best_val2},
                      {"bad": bad2, "stopped": stopped2}, key)
            return carry2, (train_err, val_err)

        carry, (train_errs, val_errs) = jax.lax.scan(
            epoch_step, carry_in, jnp.arange(n_epochs))
        return carry, train_errs, val_errs

    return jax.vmap(one_bag)(carry_in, w_train_bags)




def _init_opt_state(optimizer, stacked_params):
    """vmapped optimizer.init whose outputs FOLLOW the parameter
    shardings: moment leaves (adam mu/nu, momentum traces) mirror a
    param leaf's shape+dtype and take its sharding via explicit
    out_shardings — eager init would materialize full-size moments on
    one device first, an HBM OOM at exactly the model-axis sizes the
    sharding exists for. Anything unmatched (step counters) replicates."""
    leaves = jax.tree.leaves(stacked_params)
    shardings = {}
    mesh = None
    for leaf in leaves:
        sh = getattr(leaf, "sharding", None)
        if isinstance(sh, NamedSharding):
            shardings.setdefault((leaf.shape, leaf.dtype), sh)
            mesh = sh.mesh
    if mesh is None or all(s.is_fully_replicated
                           for s in shardings.values()):
        return jax.vmap(optimizer.init)(stacked_params)
    replicated = NamedSharding(mesh, P())
    out_shapes = jax.eval_shape(jax.vmap(optimizer.init), stacked_params)
    out_sh = jax.tree.map(
        lambda s: shardings.get((s.shape, s.dtype), replicated),
        out_shapes)
    return jax.jit(jax.vmap(optimizer.init),
                   out_shardings=out_sh)(stacked_params)


@jax.jit
def _own(tree):
    """The tree in buffers of its own: one dispatch for all its leaves."""
    return jax.tree.map(jnp.copy, tree)


def init_train_carry(optimizer, stacked_params, keys):
    """Fresh per-bag training carry (params, opt_state, best tracker,
    early-stop state, PRNG key) — the checkpointable training state
    (NNOutput tmp-model + NNMaster recovery state in one pytree).

    `train_bags_carry` donates its carry, so `stacked_params` are handed
    over: they are the carry's running parameters and gone after the
    first call (a caller that trains from them twice passes a copy each
    time). The best-epoch tracker and the keys are copies."""
    opt_state = _init_opt_state(optimizer, stacked_params)
    n_bags = keys.shape[0]
    best, keys = _own((stacked_params, keys))
    return (stacked_params, opt_state,
            {"params": best, "val": jnp.full((n_bags,), jnp.inf)},
            {"bad": jnp.zeros((n_bags,), jnp.int32),
             "stopped": jnp.zeros((n_bags,), bool)},
            keys)


def minibatch_row_order(n_rows: int, seed: int) -> np.ndarray:
    """The order mini-batch mode puts the training rows in, a function
    of the job's seed alone: numpy's `default_rng(0xB47C4 ^ seed)
    .permutation(n_rows)`; batch b holds rows order[b·batch_rows :
    (b+1)·batch_rows]. The seed derives from the caller's train seed so
    bags/runs don't all share one order."""
    return np.random.default_rng(
        np.uint64(0xB47C4) ^ np.uint64(seed)).permutation(n_rows)


def _host_batches(a, axis_rows: int, perm, n_batches: int, batch_rows: int):
    """permute + pad + reshape in ONE allocation (a permuted
    intermediate copy would double host RAM exactly when MiniBatchRows
    is in use for memory reasons)."""
    a = np.asarray(a)
    padded = a.shape[:axis_rows] + (n_batches * batch_rows,) \
        + a.shape[axis_rows + 1:]
    out = np.zeros(padded, a.dtype)  # zero weight ⇒ pad is inert
    sel = [slice(None)] * a.ndim
    sel[axis_rows] = slice(0, a.shape[axis_rows])
    # mode='clip' (a no-op: perm is a permutation) lets take write
    # straight into the out view — the default mode='raise' always
    # buffers a full temporary copy
    np.take(a, perm, axis=axis_rows, out=out[tuple(sel)], mode="clip")
    shape = (a.shape[:axis_rows] + (n_batches, batch_rows)
             + a.shape[axis_rows + 1:])
    return out.reshape(shape)


@partial(jax.jit, static_argnames=("axis_rows", "n_batches", "batch_rows"))
def _device_batches(a, axis_rows: int, perm, n_batches: int,
                    batch_rows: int):
    """`_host_batches` for an array that lives on the device."""
    out = jnp.take(a, perm, axis=axis_rows)
    widths = [(0, 0)] * out.ndim
    widths[axis_rows] = (0, n_batches * batch_rows - out.shape[axis_rows])
    out = jnp.pad(out, widths)
    return out.reshape(out.shape[:axis_rows] + (n_batches, batch_rows)
                       + out.shape[axis_rows + 1:])


def train_bags(loss_fn, metric_fn, optimizer, n_epochs: int,
               early_stop_window: int, convergence_threshold: float,
               stacked_params, train_inputs, w_train_bags,
               val_inputs, w_val, dropout_keys, grad_mask,
               checkpoint_dir: Optional[str] = None,
               checkpoint_interval: int = 0,
               batch_rows: int = 0, perm_seed: int = 0,
               param_shardings=None, row_layout=None):
    """Non-resumable façade over train_bags_carry, with optional
    checkpointing: when checkpoint_dir is set, training runs in
    `checkpoint_interval`-epoch chunks, saving the full carry after each
    (and restoring an existing checkpoint before starting).

    Placement happens HERE, once, for every caller (NN/LR/WDL/MTL): row
    tensors shard over the default data mesh — the psum XLA inserts for
    the gradient mean over sharded rows IS the reference's master
    aggregation (nn/NNMaster.java:248-259) — while parameters,
    optimizer state, keys and grad masks replicate. Zero-weight row
    padding is inert because every loss/metric normalizes by sum(w).
    `stacked_params` are handed over (see init_train_carry): device
    arrays among them are gone when this returns.

    batch_rows > 0 enables mini-batch SGD: rows are put in
    `minibatch_row_order(n_rows, perm_seed)` and reshape to (n_batches,
    batch_rows), zero-weight rows padding the last batch — host inputs
    on the host in one allocation, device inputs on the device with no
    read-back (`shifu:train.shuffle`) — the within-batch row axis shards
    over the mesh, and the epoch becomes an in-graph scan over shuffled
    batches (see train_bags_carry) — activation memory scales with
    batch_rows × bags instead of rows × bags.

    `row_layout` (full batch on one device only: `train_nn`'s kernel
    path) takes `(*train_inputs, w_train_bags)` and returns them as
    `loss_fn` reads them, once a job, in place of the row sharding."""
    mesh = mesh_mod.default_mesh()
    # .shape, not np.asarray(...).shape: the inputs can be device arrays
    # (on-device data generation), and asarray would pull the whole
    # array back to host just to read a dimension
    n_rows = int(train_inputs[0].shape[0])
    n_batches = 1
    if batch_rows and 0 < batch_rows < n_rows:
        if row_layout is not None:
            raise ValueError("row_layout lays out a full batch; "
                             f"MiniBatchRows={batch_rows} cuts it")
        n_batches = -(-n_rows // batch_rows)
        with obs_trace.span("train.shuffle", rows=n_rows, batches=n_batches):
            # break any on-disk row ordering (sorted/grouped data would
            # otherwise make every mini-batch class-homogeneous): rows are
            # permuted once here, and the in-graph scan additionally
            # shuffles BATCH order every epoch
            perm = minibatch_row_order(n_rows, perm_seed)
            if any(isinstance(t, jax.Array) for t in train_inputs):
                # device inputs (on-device data generation) stay there:
                # the order goes up, the rows never come down
                perm = jnp.asarray(perm)
                to_batches = partial(_device_batches, perm=perm,
                                     n_batches=n_batches,
                                     batch_rows=batch_rows)
            else:
                to_batches = partial(_host_batches, perm=perm,
                                     n_batches=n_batches,
                                     batch_rows=batch_rows)
            train_inputs = tuple(to_batches(t, axis_rows=0)
                                 for t in train_inputs)
            w_train_bags = to_batches(w_train_bags, axis_rows=1)
    with obs_trace.span("train.place"):
        if n_batches > 1:
            train_inputs = tuple(mesh_mod.shard_axis(mesh, t, 1)
                                 for t in train_inputs)
            w_train_bags = mesh_mod.shard_axis(mesh, w_train_bags, axis=2)
        elif row_layout is not None:
            *train_inputs, w_train_bags = row_layout(*train_inputs,
                                                     w_train_bags)
            train_inputs = tuple(train_inputs)
        else:
            train_inputs = tuple(mesh_mod.shard_axis(mesh, t, 0)
                                 for t in train_inputs)
            w_train_bags = mesh_mod.shard_axis(mesh, w_train_bags, axis=1)
        val_inputs = tuple(mesh_mod.shard_axis(mesh, t, 0) for t in val_inputs)
        w_val = mesh_mod.shard_axis(mesh, w_val, 0)
        if param_shardings is not None and mesh.shape.get("model", 1) > 1:
            # model-axis layout (SHIFU_TPU_MESH_MODEL > 1): vocab-heavy
            # leaves (WDL embedding/wide tables, MTL head rows) shard over
            # 'model' instead of replicating per chip; optimizer moments
            # get the same layout via _init_opt_state's out_shardings
            stacked_params = mesh_mod.place_stacked(stacked_params,
                                                    param_shardings)
            # grad_mask is UNSTACKED (applied per-bag inside the vmap)
            if grad_mask is not None:
                grad_mask = mesh_mod.place(grad_mask, param_shardings)
        else:
            if mesh.shape.get("model", 1) > 1:
                log.warning(
                    "SHIFU_TPU_MESH_MODEL=%d but this trainer has no "
                    "model-axis layout — params replicate and rows shard "
                    "over only the %d-device data axis (the model axis "
                    "helps only resident WDL/MTL)",
                    mesh.shape["model"], mesh.shape["data"])
            stacked_params = mesh_mod.place_replicated(mesh, stacked_params)
            grad_mask = mesh_mod.place_replicated(mesh, grad_mask)
        dropout_keys = mesh_mod.place_replicated(
            mesh, jnp.asarray(dropout_keys))

        carry = init_train_carry(optimizer, stacked_params, dropout_keys)
        del stacked_params
    done = 0
    tr_chunks, va_chunks = [], []
    if checkpoint_dir and checkpoint_interval > 0:
        from shifu_tpu.train import checkpoint as ckpt
        # topology-portable restore: the sharding sidecar re-places
        # each leaf onto THIS run's mesh, so a checkpoint written on 8
        # devices resumes here on 1, 4 or 16 (same-topology restores
        # take the identical path)
        restored = ckpt.restore_resharded(checkpoint_dir, carry,
                                          mesh=mesh, max_step=n_epochs)
        if restored is not None:
            last, carry = restored
            done = last
            log.info("checkpoint: resumed at epoch %d from %s", last,
                     checkpoint_dir)
        # SIGTERM/SIGINT → finish the current chunk, keep its
        # checkpoint, raise Preempted (rc 75); SHIFU_TPU_RESUME=1 (or
        # resilience.supervise) resumes at `done`
        with resilience.graceful_shutdown("train"):
            try:
                while done < n_epochs:
                    chunk = min(checkpoint_interval, n_epochs - done)
                    with obs_trace.span("train.program", steps=chunk):
                        # a copy goes in: the background checkpoint
                        # writer may still be reading the last carry
                        carry, tr, va = train_bags_carry(
                            loss_fn, metric_fn, optimizer, chunk,
                            early_stop_window, convergence_threshold,
                            _own(carry), train_inputs,
                            w_train_bags, val_inputs, w_val, grad_mask,
                            n_batches)
                    # keep the per-chunk error curves ON DEVICE — the
                    # host sync happens once after the loop, so chunk
                    # k+1 dispatches while k's errors are still in
                    # flight
                    tr_chunks.append(tr)
                    va_chunks.append(va)
                    done += chunk
                    ckpt.save_checkpoint(checkpoint_dir, done, carry)
                    if resilience.preempt_requested() and done < n_epochs:
                        ckpt.flush_saves()
                        raise resilience.Preempted(
                            f"train preempted after epoch "
                            f"{done}/{n_epochs}; checkpoint saved")
                ckpt.flush_saves()  # trainer-exit join barrier
            except BaseException:
                # make the last interval save durable without masking
                # the unwinding exception
                ckpt.flush_saves(reraise=False)
                raise
        if tr_chunks:
            with obs_trace.span("train.wait"):
                train_errs = np.concatenate(
                    [pipe.host_fetch(t) for t in tr_chunks], axis=1)
            with obs_trace.span("train.fetch"):
                val_errs = np.concatenate(
                    [pipe.host_fetch(v) for v in va_chunks], axis=1)
        else:  # resumed an already-finished run
            n_bags = w_train_bags.shape[0]
            train_errs = np.zeros((n_bags, 0), np.float32)
            val_errs = np.asarray(carry[2]["val"], np.float32).reshape(-1, 1)
    else:
        with obs_trace.span("train.program", steps=n_epochs):
            carry, train_errs, val_errs = train_bags_carry(
                loss_fn, metric_fn, optimizer, n_epochs, early_stop_window,
                convergence_threshold, carry, train_inputs, w_train_bags,
                val_inputs, w_val, grad_mask, n_batches)
        # the first read of a result blocks until the program is done:
        # the host waiting on the device, not host work
        with obs_trace.span("train.wait"):
            train_errs = np.asarray(train_errs)
    with obs_trace.span("train.fetch"):
        val_errs = np.asarray(val_errs)
        best = carry[2]
        best_epoch = jnp.argmin(jnp.asarray(val_errs), axis=1)
    return best["params"], train_errs, val_errs, best["val"], best_epoch


def mlp_kernel_serves(spec: nn_mod.MLPSpec, full_batch: bool) -> bool:
    """Whether a job's epoch program is the fused loss-and-gradient
    kernel (`ops/pallas_mlp.py`) or XLA's: the kernel on a TPU, for a
    net it implements (one output, hidden layers of at most 128, no
    dropout, float32), full batch, the rows on one device (a Mosaic
    kernel is not partitioned over a mesh by itself). Decided from what
    the job can observe; there is no knob."""
    return (pallas_mlp.on_chip() and full_batch
            and pallas_mlp.serves(spec)
            and mesh_mod.default_mesh().size == 1)


@program_static
def nn_objectives(spec: nn_mod.MLPSpec, kernel: bool = False):
    """(loss_fn, metric_fn) of an NN/LR job as `train_bags_carry` takes
    them, one pair a spec and path: the spec is all either reads. With
    `kernel` the loss reads rows laid out by `pallas_mlp.lay_rows`, and
    its value and gradient are one kernel call."""
    def nn_loss(params, inputs, w, key):
        x_, y_ = inputs
        if kernel:
            return pallas_mlp.loss(
                spec, params, x_, y_, w,
                interpret=jax.default_backend() != "tpu")
        dkey = key if spec.dropout_rate > 0 else None
        return nn_mod.loss_fn(spec, params, x_, y_, w, dkey)

    def nn_metric(params, inputs, w):
        x_, y_ = inputs
        return nn_mod.mse(spec, params, x_, y_, w)

    return nn_loss, nn_metric


@program_static
def objectives(model, spec):
    """(loss_fn, metric_fn) as `train_bags_carry` takes them, one pair
    a spec, of a family that trains without a key: `model` is its module
    (`models.wdl`, `models.mtl`), whose `loss_fn` and `mse` take (spec,
    params, *inputs, w) and read nothing but the spec."""
    def loss(params, inputs, w, key):
        return model.loss_fn(spec, params, *inputs, w)

    def metric(params, inputs, w):
        return model.mse(spec, params, *inputs, w)

    return loss, metric


@partial(jax.jit, static_argnames=("spec", "n_bags"))
def fresh_nn_state(key, spec: nn_mod.MLPSpec, n_bags: int):
    """(dropout keys, stacked initial parameters, all-ones gradient
    mask) of a `train_nn` job that starts from its seed's key: what
    `train_nn` computes op by op for a job with `init_params`,
    `fixed_layers` or a `grad_mask`, as ONE program a (spec, bags),
    found again by jit's own cache on the hashable spec, so a repeat
    job dispatches once where it ran a split, a draw and a fill a
    layer. The bits are the eager lines': each draw is the program
    `jax.random` compiles by itself, only joined here. The bags are a
    `lax.map`, not a `vmap`: a draw on a batch of keys is lowered as a
    vmap of threefry, op by op in Python, which made the first call of
    a process seconds longer; one bag's draws on one key lower like the
    eager programs, and compute the same numbers."""
    bag_keys = jax.random.split(key, n_bags + 1)[:-1]
    stacked = jax.lax.map(lambda k: nn_mod.init_params(spec, k), bag_keys)
    grad_mask = jax.tree.map(lambda l: jnp.ones_like(l[0]), stacked)
    return bag_keys, stacked, grad_mask


def train_nn(train_conf: ModelTrainConf, x: np.ndarray, y: np.ndarray,
             w: np.ndarray, seed: int = 12306,
             spec: Optional[nn_mod.MLPSpec] = None,
             init_params: Optional[Any] = None,
             fixed_layers: Optional[List[int]] = None,
             grad_mask: Optional[Any] = None,
             val_data: Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]] = None,
             checkpoint_dir: Optional[str] = None,
             checkpoint_interval: int = 0,
             ) -> TrainResult:
    """Train `baggingNum` NN models at once.

    val_data overrides the random validSetRate split (the reference's
    separate validation dir, ShifuInputFormat). init_params enables
    continuous training (resume from models/model0.nn); fixed_layers
    freezes those 1-BASED layers (FixedLayers=[1] = the input→hidden1
    weights, `NNMaster.getFixedWights:611-624`); grad_mask overrides
    with an element-wise {0,1} pytree (structure-growth absorption).

    `shifu:train.prepare` is a few dispatches: the bag weights come
    from `bag_row_weights` (one bag at rate >= 1.0 without replacement
    is `w` itself with a leading axis, a view of a host array; labels
    come to the host only for a stratified or neg-only draw), and a
    job with none of `init_params`, `fixed_layers` and `grad_mask`
    takes its keys, parameters and mask from `fresh_nn_state`.
    """
    t0 = time.time()
    spec = spec or nn_mod.MLPSpec.from_train_params(
        train_conf.params, input_dim=x.shape[1])
    n_bags = max(train_conf.baggingNum, 1)
    # train#params MiniBatchRows: mini-batch SGD for data whose
    # bags × activations exceed HBM full-batch (0 = full batch)
    batch_rows = int(train_conf.get_param("MiniBatchRows", 0) or 0)
    kernel = mlp_kernel_serves(
        spec, full_batch=not 0 < batch_rows < int(x.shape[0]))

    with obs_trace.span("train.job", family="nn", rows=int(x.shape[0]),
                        steps=train_conf.numTrainEpochs, bags=n_bags,
                        bags_drawn=int(bags_drawn(train_conf, n_bags)),
                        mlp_kernel=int(kernel)):
        with obs_trace.span("train.prepare"):
            if val_data is not None:
                x_tr, y_tr, w_tr = x, y, w
                x_v, y_v, w_v = val_data
            else:
                tr_mask, val_mask = split_validation(
                    len(y), train_conf.validSetRate, seed)
                x_tr, y_tr, w_tr = x[tr_mask], y[tr_mask], w[tr_mask]
                x_v, y_v, w_v = x[val_mask], y[val_mask], w[val_mask]

            if spec.compute_dtype == "bfloat16":
                # store the feature matrix itself in bf16: forward would cast
                # on-chip anyway, but a bf16-resident x halves the HBM bytes
                # every epoch actually streams (labels/weights stay f32 — they
                # feed the f32 loss reduction)
                x_tr = x_tr.astype(jnp.bfloat16)
                x_v = x_v.astype(jnp.bfloat16)

            neg_only = train_conf.sampleNegOnly
            if neg_only and spec.output_dim > 1:
                # native multi-class y holds CLASS INDICES — "negative" (< 0.5)
                # would mean class 0 only; the reference's sampleNegOnly is a
                # binary/one-vs-all semantics (WDLWorker.sampleNegOnly checks
                # isRegression/isOneVsAll), so warn-and-ignore like
                # upSampleWeight does for multi-class
                log.warning("sampleNegOnly ignored for native multi-class "
                            "training (binary/one-vs-all semantics only)")
                neg_only = False
            bag_w = bag_row_weights(train_conf, y_tr, w_tr, n_bags, seed,
                                    neg_only=neg_only)

            key = jax.random.PRNGKey(seed)
            if init_params is None and grad_mask is None \
                    and not fixed_layers:
                dropout_keys, stacked, grad_mask = fresh_nn_state(
                    key, spec, n_bags)
            else:
                # continuous training, frozen layers, structure growth:
                # op by op, as fresh_nn_state's lines are for its case
                dropout_keys = jax.random.split(key, n_bags + 1)[:-1]
                if init_params is not None:
                    stacked = jax.tree.map(
                        lambda p: jnp.broadcast_to(p, (n_bags,) + p.shape),
                        init_params)
                else:
                    stacked = jax.vmap(
                        lambda k: nn_mod.init_params(spec, k))(dropout_keys)

                if grad_mask is None:
                    grad_mask = jax.tree.map(
                        jnp.ones_like, jax.tree.map(lambda l: l[0], stacked)
                        if init_params is None else init_params)
                    if fixed_layers:
                        # 1-based like the reference's FixedLayers: 1
                        # freezes the input→hidden1 weight matrix
                        # (NNMaster.getFixedWights)
                        mask_list = []
                        for i, layer in enumerate(grad_mask):
                            z = 0.0 if (i + 1) in fixed_layers else 1.0
                            mask_list.append({k: jnp.full_like(v, z)
                                              for k, v in layer.items()})
                        grad_mask = mask_list
                else:
                    grad_mask = jax.tree.map(jnp.asarray, grad_mask)

            optimizer = optimizer_from_params(train_conf.params)
            early_window = train_conf.earlyStoppingRounds
            nn_loss, nn_metric = nn_objectives(spec, kernel)

        best_params, train_errs, val_errs, best_val, best_epoch = train_bags(
            nn_loss, nn_metric, optimizer, train_conf.numTrainEpochs,
            early_window if early_window and early_window > 0 else 0,
            float(train_conf.convergenceThreshold or 0.0),
            stacked, (x_tr, y_tr), bag_w,
            (x_v, y_v), w_v,
            dropout_keys, grad_mask,
            checkpoint_dir=checkpoint_dir,
            checkpoint_interval=checkpoint_interval,
            batch_rows=batch_rows, perm_seed=seed,
            row_layout=pallas_mlp.lay_rows if kernel else None)

        with obs_trace.span("train.fetch"):
            params_per_bag = [
                jax.tree.map(lambda p, i=i: np.asarray(p[i]), best_params)
                for i in range(n_bags)]
            res = TrainResult(
                spec=spec, params_per_bag=params_per_bag,
                train_errors=np.asarray(train_errs),
                val_errors=np.asarray(val_errs),
                best_val=np.asarray(best_val),
                best_epoch=np.asarray(best_epoch),
                wall_seconds=time.time() - t0)
    log.info("train: %d bag(s), %d epochs, best val err %s in %.2fs",
             n_bags, train_conf.numTrainEpochs,
             np.round(res.best_val, 6).tolist(), res.wall_seconds)
    return res
