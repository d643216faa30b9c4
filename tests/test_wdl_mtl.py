"""WDL and MTL model-family tests (reference analogs: wdl/mtl packages,
WideAndDeep layer graph, MultiTaskModel shared trunk)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shifu_tpu.models import mtl, wdl


def test_wdl_forward_shapes(rng):
    spec = wdl.WDLSpec(dense_dim=5, n_cat=3, vocab_sizes=(7, 7, 7), embed_size=4,
                       hidden_dims=(8,), activations=("relu",))
    params = wdl.init_params(spec, jax.random.PRNGKey(0))
    d = jnp.asarray(rng.normal(0, 1, (10, 5)).astype(np.float32))
    i = jnp.asarray(rng.integers(0, 7, (10, 3)).astype(np.int32))
    p = wdl.forward(spec, params, d, i)
    assert p.shape == (10,)
    assert ((p > 0) & (p < 1)).all()


def test_wdl_learns_categorical_signal(rng):
    """Label depends only on a categorical column — embeddings + wide
    must capture it."""
    n = 3000
    idx = rng.integers(0, 6, (n, 2)).astype(np.int32)
    y = (idx[:, 0] >= 3).astype(np.float32)
    d = rng.normal(0, 1, (n, 3)).astype(np.float32)
    spec = wdl.WDLSpec(dense_dim=3, n_cat=2, vocab_sizes=(7, 7), embed_size=4,
                       hidden_dims=(8,), activations=("relu",))
    params = wdl.init_params(spec, jax.random.PRNGKey(1))
    import optax
    opt = optax.adam(0.05)
    state = opt.init(params)
    jd, ji, jy = jnp.asarray(d), jnp.asarray(idx), jnp.asarray(y)
    jw = jnp.ones(n)

    @jax.jit
    def step(params, state):
        loss, g = jax.value_and_grad(
            lambda p: wdl.loss_fn(spec, p, jd, ji, jy, jw))(params)
        upd, state = opt.update(g, state, params)
        return optax.apply_updates(params, upd), state, loss

    for _ in range(120):
        params, state, loss = step(params, state)
    p = np.asarray(wdl.forward(spec, params, jd, ji))
    acc = ((p > 0.5) == (y > 0.5)).mean()
    assert acc > 0.95


def test_mtl_forward_and_masked_loss(rng):
    spec = mtl.MTLSpec(input_dim=4, n_tasks=3, hidden_dims=(8,),
                       activations=("tanh",))
    params = mtl.init_params(spec, jax.random.PRNGKey(0))
    x = jnp.asarray(rng.normal(0, 1, (20, 4)).astype(np.float32))
    p = mtl.forward(spec, params, x)
    assert p.shape == (20, 3)
    y = np.full((20, 3), np.nan, np.float32)
    y[:, 0] = 1.0  # only task 0 labeled
    loss = mtl.loss_fn(spec, params, x, jnp.asarray(y), jnp.ones(20))
    assert np.isfinite(float(loss))


def test_full_pipeline_wdl(tmp_path, rng):
    from tests.synth import make_model_set
    from tests.test_train import run_pipeline
    root = make_model_set(
        tmp_path, rng, n_rows=2500, algorithm="WDL",
        norm_type="ZSCALE_INDEX",
        train_params={"NumHiddenLayers": 1, "NumHiddenNodes": [16],
                      "ActivationFunc": ["relu"], "LearningRate": 0.02,
                      "Propagation": "ADAM", "EmbedSize": 4})
    ctx = run_pipeline(root)
    with open(ctx.path_finder.eval_performance_path("Eval1")) as f:
        perf = json.load(f)
    assert perf["areaUnderRoc"] > 0.85
    assert os.path.exists(ctx.path_finder.model_path(0, "wdl"))


def test_full_pipeline_mtl(tmp_path, rng):
    """Two tasks: the synthetic 'diagnosis' plus a second derived tag
    column added to the raw files."""
    from tests.synth import make_model_set
    from shifu_tpu.processor.base import ProcessorContext
    from shifu_tpu.processor import (init as init_proc, stats as stats_proc,
                                     norm as norm_proc, train as train_proc)
    root = make_model_set(
        tmp_path, rng, n_rows=2000, algorithm="MTL",
        train_params={"NumHiddenLayers": 1, "NumHiddenNodes": [16],
                      "ActivationFunc": ["relu"], "LearningRate": 0.05,
                      "Propagation": "ADAM"})
    # add a second target column correlated with num_0
    import pandas as pd
    for sub in ("data", "evaldata"):
        dpath = os.path.join(root, sub, "part-00000")
        hpath = os.path.join(root, sub, ".pig_header")
        header = open(hpath).read().strip().split("|")
        df = pd.read_csv(dpath, sep="|", names=header, dtype=str)
        v = pd.to_numeric(df["num_0"], errors="coerce").fillna(0)
        df["second_tag"] = np.where(v > v.median(), "M", "B")
        df.to_csv(dpath, sep="|", header=False, index=False)
        with open(hpath, "w") as f:
            f.write("|".join(header + ["second_tag"]) + "\n")
    # point config at both targets
    mc_path = os.path.join(root, "ModelConfig.json")
    mc = json.load(open(mc_path))
    mc["dataSet"]["targetColumnName"] = "diagnosis|second_tag"
    json.dump(mc, open(mc_path, "w"), indent=2)

    for proc in (init_proc, stats_proc, norm_proc, train_proc):
        ctx = ProcessorContext.load(root)
        assert proc.run(ctx) == 0
    assert os.path.exists(ctx.path_finder.model_path(0, "mtl"))

    # both task heads predictive on train data
    from shifu_tpu.models.spec import load_model
    kind, meta, params = load_model(ctx.path_finder.model_path(0, "mtl"))
    data, _ = norm_proc.load_normalized(ctx.path_finder.normalized_data_path())
    scores = mtl.predict_tasks(meta, params, data["dense"])
    assert scores.shape[1] == 2
    from shifu_tpu.ops.metrics import auc
    a0 = float(auc(jnp.asarray(scores[:, 0]), jnp.asarray(data["tags"])))
    assert a0 > 0.8


def test_wdl_streaming_train_on_disk(tmp_path, rng):
    """train#trainOnDisk routes WDL through the chunk-streamed core
    (mmap'd dense + embedding-index blocks; Criteo-scale analog)."""
    import json

    from tests.synth import make_model_set
    from shifu_tpu.processor import (eval as eval_proc, init as init_proc,
                                     norm as norm_proc,
                                     stats as stats_proc,
                                     train as train_proc)
    from shifu_tpu.processor.base import ProcessorContext

    root = make_model_set(tmp_path, rng, n_rows=2500, algorithm="WDL",
                          norm_type="ZSCALE_INDEX",
                          train_params={"NumHiddenNodes": [8],
                                        "ActivationFunc": ["relu"],
                                        "EmbedSize": 4,
                                        "LearningRate": 0.05,
                                        "Propagation": "ADAM",
                                        "ChunkRows": 500})
    mcp = os.path.join(root, "ModelConfig.json")
    mc = json.load(open(mcp))
    mc["train"]["trainOnDisk"] = True
    mc["train"]["numTrainEpochs"] = 30
    json.dump(mc, open(mcp, "w"))
    for proc in (init_proc, stats_proc, norm_proc, train_proc, eval_proc):
        ctx = ProcessorContext.load(root)
        assert proc.run(ctx) == 0
    models = os.listdir(ctx.path_finder.models_path())
    assert models == ["model0.wdl"]
    perf = json.load(open(ctx.path_finder.eval_performance_path("Eval1")))
    assert perf["areaUnderRoc"] > 0.85, perf["areaUnderRoc"]


def test_mtl_streaming_train_on_disk(tmp_path, rng):
    """train#trainOnDisk routes MTL through the streaming core with the
    (R, T) task-tag block persisted in the mmap layout."""
    import json

    from tests.synth import make_model_set
    from shifu_tpu.processor import (init as init_proc, norm as norm_proc,
                                     stats as stats_proc,
                                     train as train_proc)
    from shifu_tpu.processor.base import ProcessorContext

    root = make_model_set(tmp_path, rng, n_rows=2500, algorithm="MTL",
                          train_params={"NumHiddenNodes": [8],
                                        "ActivationFunc": ["relu"],
                                        "LearningRate": 0.05,
                                        "Propagation": "ADAM",
                                        "ChunkRows": 500})
    mcp = os.path.join(root, "ModelConfig.json")
    mc = json.load(open(mcp))
    # two tasks over the same synthetic label (the second task is the
    # first's complement column; synth writes a single diagnosis column,
    # so duplicate it as task 2)
    mc["dataSet"]["targetColumnName"] = "diagnosis|diagnosis"
    mc["train"]["trainOnDisk"] = True
    mc["train"]["numTrainEpochs"] = 25
    json.dump(mc, open(mcp, "w"))
    for proc in (init_proc, stats_proc, norm_proc, train_proc):
        ctx = ProcessorContext.load(root)
        assert proc.run(ctx) == 0
    nd = ctx.path_finder.normalized_data_path()
    assert os.path.exists(os.path.join(nd, "task_tags.npy"))
    models = os.listdir(ctx.path_finder.models_path())
    assert models == ["model0.mtl"]
    from shifu_tpu.models.spec import load_model
    kind, meta2, params = load_model(ctx.path_finder.model_path(0, "mtl"))
    assert kind == "mtl" and meta2["spec"]["n_tasks"] == 2
