"""The narrow MLP's fused loss-and-gradient kernel (tier-1, CPU, the
kernel in interpret mode).

(a) loss and every gradient leaf against `jax.value_and_grad(nn.loss_fn)`
    over the activations and losses it implements, on rows that fill no
    whole tile, with zero and non-unit weights, over several row tiles
    and partial blocks, and for two bags under `vmap`;
(b) `train_nn` through both paths agrees on every epoch's errors;
(c) who takes the kernel: the rule of `trainer.mlp_kernel_serves`, case
    by case, and no `pallas_call` in the program of anyone else.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shifu_tpu.config.model_config import ModelTrainConf
from shifu_tpu.models import nn as nn_mod
from shifu_tpu.ops import pallas_mlp
from shifu_tpu.train import trainer
from tests.test_train_spans import _pallas_eqns


def _rows(spec, r, bags=1, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (r, spec.input_dim))
    y = (jax.random.uniform(ks[1], (r,)) < 0.4).astype(jnp.float32)
    # a fifth of the rows weigh nothing, the rest between 0 and 3
    w = 3.0 * jax.random.uniform(ks[2], (bags, r)) \
        * (jax.random.uniform(ks[3], (bags, r)) > 0.2)
    params = jax.vmap(lambda k: nn_mod.init_params(spec, k))(
        jax.random.split(ks[4], bags))
    # biases off zero, so that every leaf's gradient is read somewhere
    return x, y, w, jax.tree.map(lambda p: p + 0.05, params)


def _both(spec, x, y, w, params):
    """value_and_grad of XLA's loss and of the kernel's, bag by bag."""
    xT, y2, w2 = pallas_mlp.lay_rows(x, y, w)
    ref = jax.vmap(lambda p, ww: jax.value_and_grad(
        lambda q: nn_mod.loss_fn(spec, q, x, y, ww))(p))(params, w)
    got = jax.vmap(lambda p, ww: jax.value_and_grad(
        lambda q: pallas_mlp.loss(spec, q, xT, y2, ww, interpret=True))(p))(
            params, w2)
    return ref, got


def _up8(n):
    return -(-n // 8) * 8


def _assert_close(ref, got, rtol=2e-5):
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=rtol,
                                   atol=rtol * float(jnp.max(jnp.abs(a))))


CASES = {
    # the cell's: 28-64-1, tanh then sigmoid, squared error
    "nn-higgs": nn_mod.MLPSpec(28, (64,), ("tanh",)),
    "two-relu-log": nn_mod.MLPSpec(28, (16, 8), ("relu", "relu"),
                                   loss="log"),
    "absolute": nn_mod.MLPSpec(13, (50,), ("sigmoid",), loss="absolute",
                               output_activation="tanh"),
    "leaky-linear-l2": nn_mod.MLPSpec(
        9, (24, 40), ("leakyrelu", "linear"), output_activation="linear",
        l2=0.01),
    "l1": nn_mod.MLPSpec(28, (64,), ("tanh",), loss="log", l1=0.003),
}


@pytest.fixture()
def small_tiles(monkeypatch):
    """A row tile of 8 chunks and two tiles a partial block: the kernel
    is the same code at any count, and a CPU traces 8 chunks in an
    eighth of the time."""
    monkeypatch.setattr(pallas_mlp, "CHUNKS", 8)
    monkeypatch.setattr(pallas_mlp, "ROW_TILE", 8 * pallas_mlp.CHUNK)
    monkeypatch.setattr(pallas_mlp, "TILES_PER_PARTIAL", 2)


@pytest.mark.parametrize("case", sorted(CASES))
def test_loss_and_every_gradient_leaf_match_xla(small_tiles, case):
    """5,000 rows: three row tiles, the last one part full of weight-0
    padding, so two partial blocks: the second starts from zero and XLA
    adds the two."""
    spec = CASES[case]
    assert pallas_mlp.serves(spec)
    x, y, w, params = _rows(spec, 5_000)
    xT, y2, w2 = pallas_mlp.lay_rows(x, y, w)
    assert xT.shape == (_up8(spec.input_dim), 3 * pallas_mlp.ROW_TILE)
    assert y2.shape == (24, pallas_mlp.CHUNK) and w2.shape == (1, 24,
                                                               pallas_mlp.CHUNK)
    _assert_close(*_both(spec, x, y, w, params))


def test_the_cells_net_at_the_real_tile():
    """The constants the chip runs: one grid step of 64 chunks, a third
    of it rows."""
    spec = CASES["nn-higgs"]
    assert pallas_mlp.ROW_TILE == pallas_mlp.CHUNK * pallas_mlp.CHUNKS
    _assert_close(*_both(spec, *_rows(spec, 5_000, seed=4)))


def test_two_bags_under_vmap_share_the_rows(small_tiles):
    spec = CASES["nn-higgs"]
    x, y, w, params = _rows(spec, 3_000, bags=2, seed=2)
    ref, got = _both(spec, x, y, w, params)
    _assert_close(ref, got)
    # the bags differ (their weights do), so neither was computed twice
    assert not np.allclose(np.asarray(got[0][0]), np.asarray(got[0][1]))


def test_value_alone_and_a_scaled_cotangent(small_tiles):
    """The custom_vjp's primal is the forward pass's value, and its
    backward pass scales the kept gradients by what comes in."""
    spec = CASES["two-relu-log"]
    x, y, w, params = _rows(spec, 1_000, seed=3)
    params = jax.tree.map(lambda p: p[0], params)
    xT, y2, w2 = pallas_mlp.lay_rows(x, y, w)

    def f(q):
        return pallas_mlp.loss(spec, q, xT, y2, w2[0], interpret=True)

    value, grads = jax.value_and_grad(f)(params)
    np.testing.assert_allclose(f(params), value, rtol=1e-6)
    tripled = jax.grad(lambda q: 3.0 * f(q))(params)
    _assert_close(jax.tree.map(lambda g: 3.0 * g, grads), tripled, 1e-6)


def _conf(epochs=3, bags=1, **params):
    tc = ModelTrainConf()
    tc.numTrainEpochs = epochs
    tc.baggingNum = bags
    tc.params = {"NumHiddenLayers": 1, "NumHiddenNodes": [64],
                 "ActivationFunc": ["tanh"], "LearningRate": 0.05,
                 "Propagation": "ADAM", **params}
    return tc


def _table(n=3_000, c=28, seed=3):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, c)).astype(np.float32)
    y = (x[:, 0] + 0.3 * x[:, 1] > 0).astype(np.float32)
    return x, y, rng.uniform(0.5, 2.0, size=n).astype(np.float32)


@pytest.mark.parametrize("bags", [1, 2])
def test_train_nn_agrees_through_both_paths(monkeypatch, small_tiles, bags):
    x, y, w = _table()
    monkeypatch.setenv("SHIFU_TPU_MESH_DEVICES", "1")
    assert not trainer.mlp_kernel_serves(CASES["nn-higgs"], True)
    plain = trainer.train_nn(_conf(bags=bags), x, y, w, seed=5)
    monkeypatch.setattr(pallas_mlp, "on_chip", lambda: True)
    assert trainer.mlp_kernel_serves(CASES["nn-higgs"], True)
    fused = trainer.train_nn(_conf(bags=bags), x, y, w, seed=5)
    assert plain.train_errors.shape == (bags, 3)
    np.testing.assert_allclose(fused.train_errors, plain.train_errors,
                               atol=1e-5)
    np.testing.assert_allclose(fused.val_errors, plain.val_errors,
                               atol=1e-5)
    for a, b in zip(jax.tree.leaves(plain.params_per_bag),
                    jax.tree.leaves(fused.params_per_bag)):
        np.testing.assert_allclose(b, a, atol=1e-4)


# (train#params on top of the cell's 28-64-1, spec overrides, mesh
# devices, on a TPU, takes the kernel)
ROUTES = {
    "the cell's net, full batch": ({}, {}, 1, True, True),
    "two hidden layers of 128": (
        {"NumHiddenLayers": 2, "NumHiddenNodes": [128, 128],
         "ActivationFunc": ["relu", "sigmoid"], "Loss": "log"},
        {}, 1, True, True),
    "mini-batches asked for but larger than the table": (
        {"MiniBatchRows": 10_000}, {}, 1, True, True),
    "on a CPU": ({}, {}, 1, False, False),
    "a hidden layer of 300": ({"NumHiddenNodes": [300]}, {}, 1, True, False),
    "dnn-higgs's net": (
        {"NumHiddenLayers": 4, "NumHiddenNodes": [300] * 4,
         "ActivationFunc": ["tanh"] * 4}, {}, 1, True, False),
    "no hidden layer (LR)": (
        {"NumHiddenLayers": 0, "NumHiddenNodes": [], "ActivationFunc": []},
        {}, 1, True, False),
    "dropout": ({"DropoutRate": 0.1}, {}, 1, True, False),
    "bfloat16 compute": ({"ComputeDtype": "bfloat16"}, {}, 1, True, False),
    "an activation it has no derivative for": (
        {"ActivationFunc": ["swish"]}, {}, 1, True, False),
    "mini-batches": ({"MiniBatchRows": 256}, {}, 1, True, False),
    "a softmax head": ({}, {"output_dim": 3,
                            "output_activation": "softmax"}, 1, True, False),
    "rows over eight devices": ({}, {}, 8, True, False),
}


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_who_takes_the_kernel(monkeypatch, small_tiles, route):
    """`train_nn` hands `train_bags` the kernel's loss and row layout
    exactly where the rule says, and the epoch program of everyone else
    holds no `pallas_call`."""
    params, overrides, devices, on_tpu, takes = ROUTES[route]
    monkeypatch.setenv("SHIFU_TPU_MESH_DEVICES", str(devices))
    monkeypatch.setattr(pallas_mlp, "on_chip", lambda: on_tpu)
    conf = _conf(**params)
    x, y, w = _table(600)
    if overrides:
        y = np.floor(3 * np.random.default_rng(0).random(600)) \
            .astype(np.float32)
    spec = nn_mod.MLPSpec.from_train_params(conf.params, input_dim=28)
    spec = nn_mod.MLPSpec(**{**spec.__dict__, **overrides})
    seen = {}

    def spy(loss_fn, metric_fn, optimizer, n_epochs, window, threshold,
            stacked, train_inputs, w_bags, val_inputs, w_val, keys, mask,
            **kw):
        seen.update(loss_fn=loss_fn, layout=kw["row_layout"],
                    stacked=stacked, inputs=train_inputs, w=w_bags)
        n_bags = w_bags.shape[0]
        return (stacked, np.zeros((n_bags, n_epochs), np.float32),
                np.zeros((n_bags, n_epochs), np.float32),
                np.zeros(n_bags, np.float32), np.zeros(n_bags, np.int32))

    monkeypatch.setattr(trainer, "train_bags", spy)
    trainer.train_nn(conf, x, y, w, seed=1, spec=spec)
    assert (seen["layout"] is not None) == takes
    assert (seen["layout"] is pallas_mlp.lay_rows) == takes
    inputs, w_bags = seen["inputs"], seen["w"]
    if takes:
        *inputs, w_bags = seen["layout"](*inputs, w_bags)
    one = jax.tree.map(lambda p: p[0], seen["stacked"])
    jaxpr = jax.make_jaxpr(jax.value_and_grad(seen["loss_fn"]))(
        one, tuple(inputs), w_bags[0], jax.random.PRNGKey(0))
    names = [e.params["name"] for e in _pallas_eqns(jaxpr.jaxpr, [])]
    assert names == (["shifu_mlp_loss_grad"] if takes else [])
