"""Every trainer entry learns a planted signal at small size (tier-1).

One case an entry: the callable a cell of the benchmark or a CLI step
enters (`train_nn`, `train_wdl`, `build_gbt`, `build_rf`, the streaming
and the scheduled paths), on a seeded table from `tests/synth.py` whose
label a planted margin decides, held to a quality gate — the model must
have learnt the margin, and where the importances are planted, ranked
them. A signature that drifts or a trainer that stops learning fails
here, on the CPU, before a chip run finds it.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shifu_tpu.config.model_config import ModelTrainConf
from shifu_tpu.models import gbdt, mtl, nn as nn_mod, wdl
from shifu_tpu.ops.metrics import auc
from shifu_tpu.train import trainer
from tests import synth


def _conf(epochs, hidden, act, lr, valid_rate=0.05, **params):
    """Fixed-length training (no early stop), one bag, ADAM."""
    conf = ModelTrainConf()
    conf.params = {"NumHiddenLayers": len(hidden),
                   "NumHiddenNodes": list(hidden),
                   "ActivationFunc": [act] * len(hidden),
                   "Propagation": "ADAM", "LearningRate": lr, **params}
    conf.numTrainEpochs = epochs
    conf.baggingNum = 1
    conf.validSetRate = valid_rate
    conf.earlyStoppingRounds = 0
    conf.convergenceThreshold = 0.0
    return conf


def _auc(scores, y):
    return float(auc(jnp.asarray(scores), jnp.asarray(y)))


def _nn_auc(res, x, y):
    params = jax.tree.map(jnp.asarray, res.params_per_bag[0])
    return _auc(nn_mod.forward(res.spec, params, jnp.asarray(x)), y)


def _tree_auc(cfg, trees, bins, y, reduce):
    scores = np.asarray(gbdt.predict_trees(
        jax.tree.map(jnp.asarray, trees), jnp.asarray(bins.T),
        cfg.max_depth, cfg.n_bins))
    return _auc(reduce(scores, axis=0), y)


def nn_narrow(rng, tmp_path):
    """`train_nn`, Shifu's flagship shape: one narrow tanh layer."""
    x, y, w = synth.planted_linear_table(
        rng, 20_000, rng.normal(0, 1, 16), scale=0.7)
    res = trainer.train_nn(_conf(40, (16,), "tanh", 0.05), x, y, w, seed=1)
    assert _nn_auc(res, x, y) > 0.75


def _nn_wide(rng, **params):
    beta = rng.normal(0, 1, 24) / np.sqrt(24)
    x, y, w = synth.planted_linear_table(rng, 4_000, beta, scale=2.0)
    res = trainer.train_nn(_conf(40, (16, 8), "relu", 0.02, **params),
                           x, y, w, seed=1)
    assert _nn_auc(res, x, y) > 0.75
    return res


def nn_wide(rng, tmp_path):
    """`train_nn`, two ReLU layers over more features than nodes."""
    assert _nn_wide(rng).spec.compute_dtype == "float32"


def nn_wide_bf16(rng, tmp_path):
    """The same with `ComputeDtype: bfloat16`: bf16 operands, f32
    master weights, and the model still learns."""
    res = _nn_wide(rng, ComputeDtype="bfloat16")
    assert res.spec.compute_dtype == "bfloat16"
    assert all(np.asarray(leaf).dtype == np.float32
               for leaf in jax.tree.leaves(res.params_per_bag[0]))


def lr_sensitivity(rng, tmp_path):
    """LR (`train_nn` with no hidden layer) and the SE-sensitivity
    ablation kernel, in blocks with an uneven trailing one (50k rows in
    blocks of 20k): the planted importances (beta_c ∝ c + 1) come back
    in rank order."""
    from shifu_tpu.processor import sensitivity
    n, cols, block = 50_000, 8, 20_000
    beta = (np.arange(cols, dtype=np.float32) + 1.0) / cols
    x, y, w = synth.planted_linear_table(rng, n, beta)
    res = trainer.train_nn(_conf(40, (), "relu", 0.05), x, y, w, seed=1)
    assert _nn_auc(res, x, y) > 0.75
    params = jax.tree.map(jnp.asarray, res.params_per_bag[0])
    total = jnp.zeros(cols, jnp.float32)
    for s in range(0, n, block):
        xb = jnp.asarray(x[s:s + block])
        rows = xb.shape[0]
        deltas, _ = sensitivity.shifu_ablate(
            res.spec, params, xb, rows,
            sensitivity.ablate_blocks(rows, cols, 1))
        total = total + deltas * (rows / n)
    ranks = np.empty(cols, np.int64)
    ranks[np.argsort(np.asarray(total))] = np.arange(cols)
    assert np.corrcoef(ranks, np.arange(cols))[0, 1] > 0.9


def wdl_entry(rng, tmp_path):
    """`train_wdl`, the entry `wdl-criteo.train` times: embeddings, the
    wide part and the deep tower learn ids' planted effects."""
    from shifu_tpu.processor.train_wdl import train_wdl
    dense, idx, y, w = synth.planted_wdl_table(rng, 6_000, 5, 3, 50)
    res = train_wdl(_conf(30, (8,), "relu", 0.02, EmbedSize=4),
                    dense, idx, y, w, (50,) * 3, seed=1)
    params = jax.tree.map(
        jnp.asarray, wdl.device_params(res.spec, res.params_per_bag[0]))
    scores = wdl.forward(res.spec, params, jnp.asarray(dense),
                         jnp.asarray(idx))
    assert _auc(scores, y) > 0.7


def mtl_trunk_and_heads(rng, tmp_path):
    """The multi-task model through `train_bags`, as `run_mtl` drives
    it: three heads over one trunk, each label under a margin of its
    own, gated on the first task."""
    from shifu_tpu.train.optimizers import optimizer_from_params
    n, feats, tasks = 6_000, 12, 3
    betas = rng.normal(0, 1, (feats, tasks)) / np.sqrt(feats)
    x, y, w = synth.planted_linear_table(rng, n, betas, scale=2.0)
    spec = mtl.MTLSpec(input_dim=feats, n_tasks=tasks, hidden_dims=(16, 8),
                       activations=("relu", "relu"))
    tr, val = trainer.split_validation(n, 0.05, 7)
    keys = jax.random.split(jax.random.PRNGKey(1), 1)
    stacked = jax.vmap(lambda k: mtl.init_params(spec, k))(keys)
    best = trainer.train_bags(
        lambda p, inputs, w_, key: mtl.loss_fn(spec, p, *inputs, w_),
        lambda p, inputs, w_: mtl.mse(spec, p, *inputs, w_),
        optimizer_from_params({"Propagation": "ADAM", "LearningRate": 0.02}),
        30, 0, 0.0, stacked, (x[tr], y[tr]), w[tr][None, :],
        (x[val], y[val]), w[val], keys,
        jax.tree.map(lambda l: jnp.ones_like(l[0]), stacked))[0]
    scores = mtl.forward(spec, jax.tree.map(lambda p: p[0], best),
                         jnp.asarray(x))
    assert scores.shape == (n, tasks)
    assert _auc(scores[:, 0], y[:, 0]) > 0.7


def gbt_placed(rng, tmp_path):
    """`build_gbt` as `gbt-higgs.train` enters it: the (columns, rows)
    bin matrix already on the device."""
    bins, y, w = synth.planted_binned_table(rng, 20_000, 8, 64)
    cfg = gbdt.TreeConfig(max_depth=3, n_bins=64, learning_rate=0.2,
                          loss="log")
    trees, val_errs = gbdt.build_gbt(cfg, jnp.asarray(bins.T),
                                     jnp.asarray(y), jnp.asarray(w),
                                     n_trees=3)
    assert val_errs == []
    assert _tree_auc(cfg, trees, bins, y, np.sum) > 0.6


def gbt_host_rows(rng, tmp_path):
    """`build_gbt` as `train_tree.run_tree` enters it: host rows, which
    it transposes and places; the ensemble comes back on the host at
    the sizes asked."""
    bins, y, w = synth.planted_binned_table(rng, 20_000, 8, 64)
    cfg = gbdt.TreeConfig(max_depth=6, n_bins=64, learning_rate=0.2,
                          loss="log")
    trees, _ = gbdt.build_gbt(cfg, bins, y, w, n_trees=3)
    nodes = 2 ** (cfg.max_depth + 1) - 1
    assert all(isinstance(a, np.ndarray) for a in trees.values())
    assert trees["feature"].shape == (3, nodes)
    assert trees["leaf_value"].shape == (3, nodes)
    assert int(trees["feature"].max()) < 8
    assert _tree_auc(cfg, trees, bins, y, np.sum) > 0.6


def rf_forest(rng, tmp_path):
    """`build_rf`: four trees grown in lockstep on Poisson-bagged rows
    and feature subsets, scored as their mean."""
    bins, y, w = synth.planted_binned_table(rng, 20_000, 8, 64)
    cfg = gbdt.TreeConfig(max_depth=6, n_bins=64, learning_rate=1.0,
                          loss="squared")
    trees = gbdt.build_rf(cfg, bins, y, w, n_trees=4,
                          subset_strategy="TWOTHIRDS", bagging_rate=1.0,
                          seed=7)
    assert trees["feature"].shape[0] == 4
    assert _tree_auc(cfg, trees, bins, y, np.mean) > 0.6


def nn_streaming(rng, tmp_path):
    """`train_nn_streaming`, the `trainOnDisk` path: rows arrive in
    chunks of 1,024 from memory-mapped files, the trailing rows
    validate."""
    from shifu_tpu.train.streaming import mmap_layout, train_nn_streaming
    beta = rng.normal(0, 1, 12) / np.sqrt(12)
    arrays = dict(zip(("dense", "tags", "weights"),
                      synth.planted_linear_table(rng, 6_000, beta,
                                                 scale=2.0)))
    for name, a in arrays.items():
        np.save(os.path.join(tmp_path, name + ".npy"), a)
    dense, tags, weights = mmap_layout(str(tmp_path), *arrays)
    res = train_nn_streaming(
        _conf(30, (8,), "relu", 0.02, valid_rate=0.02),
        lambda a, b: (np.asarray(dense[a:b]), np.asarray(tags[a:b]),
                      np.asarray(weights[a:b])),
        6_000, 12, seed=1, chunk_rows=1_024)
    assert _nn_auc(res, arrays["dense"], arrays["tags"]) > 0.75


def pipeline_dag(rng, tmp_path):
    """init → stats → norm → train → eval as CLI subprocesses through
    the DAG scheduler: a single model keeps the plain `train` node, and
    the eval set's AUC says the whole chain learnt."""
    from shifu_tpu.pipeline.nodes import pipeline_nodes
    from shifu_tpu.pipeline.scheduler import run_dag
    root = synth.make_model_set(tmp_path, rng, n_rows=5_000)
    mc_path = os.path.join(root, "ModelConfig.json")
    with open(mc_path) as f:
        mc = json.load(f)
    mc["train"]["numTrainEpochs"] = 5
    with open(mc_path, "w") as f:
        json.dump(mc, f)
    report = run_dag(pipeline_nodes(root, eval_sets=["Eval1"],
                                    algorithms=["NN"], resume=False),
                     workers=1, root=root, label="pipeline")
    assert {n["node"]: n["state"] for n in report["nodes"]} == dict.fromkeys(
        ("init", "stats", "norm", "train", "eval.Eval1"), "done")
    with open(os.path.join(root, "evals", "Eval1",
                           "EvalPerformance.json")) as f:
        assert json.load(f)["areaUnderRoc"] > 0.75


CASES = [nn_narrow, nn_wide, nn_wide_bf16, lr_sensitivity, wdl_entry,
         mtl_trunk_and_heads, gbt_placed, gbt_host_rows, rf_forest,
         nn_streaming, pipeline_dag]


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__)
def test_entry_learns_planted_signal(case, tmp_path):
    case(np.random.default_rng(20260731), tmp_path)
