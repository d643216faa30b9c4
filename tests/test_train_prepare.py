"""`shifu:train.prepare` is a few dispatches, and computes what it did.

`trainer.bag_row_weights` is the three resident trainers' bag weights:
where no bag is drawn (one bag, rate >= 1.0, no replacement) the rows'
own weights with a leading axis, with no row-sized host array, multiply,
upload or label fetch; every other bagging `bagging_weights`' host draw,
the labels fetched only where it reads them. `trainer.fresh_nn_state` is
a fresh `train_nn` job's keys, initial parameters and gradient mask as
one program a (spec, bags). Both are held here, on the CPU, to the bits
of the expressions they replace, which are written out below as they
stood before.
"""

import copy
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shifu_tpu.config.model_config import ModelTrainConf
from shifu_tpu.models import nn as nn_mod
from shifu_tpu.models.spec import load_model
from shifu_tpu.obs import trace as obs_trace
from shifu_tpu.processor import train_mtl, train_wdl
from shifu_tpu.processor.base import ProcessorContext
from shifu_tpu.train import trainer
from tests.test_program_reuse import (_CacheEvents,  # noqa: F401
                                      every_program_cached)

N = 500


def _conf(**fields):
    return ModelTrainConf.from_dict(copy.deepcopy(
        {"numTrainEpochs": 3, "validSetRate": 0.25,
         "earlyStoppingRounds": 0,
         "params": {"NumHiddenLayers": 1, "NumHiddenNodes": [8],
                    "ActivationFunc": ["tanh"], "Propagation": "ADAM",
                    "LearningRate": 0.05}, **fields}))


def _labels_weights(nan_labels=False, w_dtype=np.float32):
    rng = np.random.default_rng(41)
    y = (rng.random(N) < 0.3).astype(np.float32)
    if nan_labels:
        y[rng.random(N) < 0.1] = np.nan
    w = (rng.random(N) * 3).astype(w_dtype)
    return y, w


# ---- the expressions as they stood, one a line the PR replaced ----

def old_bag_weights(train_conf, y_tr, w_tr, n_bags, seed, neg_only=None):
    """`train_nn`'s (and, with `labels` always fetched, `train_wdl`'s and
    `run_mtl`'s) bag weights before `bag_row_weights`."""
    return trainer.bagging_weights(
        len(y_tr), n_bags, train_conf.baggingSampleRate,
        train_conf.baggingWithReplacement, seed, labels=np.asarray(y_tr),
        stratified=train_conf.stratifiedSample,
        neg_only=(train_conf.sampleNegOnly if neg_only is None
                  else neg_only)) * w_tr[None, :]


def old_init_params(spec, key):
    """`nn.init_params` before the scale of a normal draw stood behind
    an optimization barrier."""
    params = []
    dims = spec.layer_dims
    for i in range(len(dims) - 1):
        key, sub = jax.random.split(key)
        fan_in, fan_out = dims[i], dims[i + 1]
        if spec.weight_init == "he":
            w = jax.random.normal(sub, (fan_in, fan_out)) \
                * math.sqrt(2.0 / fan_in)
        elif spec.weight_init == "lecun":
            w = jax.random.normal(sub, (fan_in, fan_out)) \
                * math.sqrt(1.0 / fan_in)
        elif spec.weight_init == "zero":
            w = jnp.zeros((fan_in, fan_out))
        else:
            limit = math.sqrt(6.0 / (fan_in + fan_out))
            w = jax.random.uniform(sub, (fan_in, fan_out), minval=-limit,
                                   maxval=limit)
        params.append({"w": w.astype(jnp.float32),
                       "b": jnp.zeros((fan_out,), jnp.float32)})
    return params


def old_nn_state(key, spec, n_bags):
    """`train_nn`'s eager lines for a job that starts from its seed."""
    bag_keys = jax.random.split(key, n_bags + 1)
    stacked = jax.vmap(lambda k: old_init_params(spec, k))(bag_keys[:-1])
    grad_mask = jax.tree.map(jnp.ones_like,
                             jax.tree.map(lambda l: l[0], stacked))
    return bag_keys[:-1], stacked, grad_mask


def _same_bits(got, want):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for g, v in zip(got, want):
        assert isinstance(g, jax.Array) == isinstance(v, jax.Array)
        assert g.shape == v.shape and g.dtype == v.dtype
        assert np.array_equal(np.asarray(g), np.asarray(v), equal_nan=True)


class Unreadable:
    """Labels that must not come to the host."""
    shape = (N,)

    def __len__(self):
        return N

    def __array__(self, *a, **k):
        raise AssertionError("the labels were converted")


class Counted(Unreadable):
    """Labels that count how often they are converted."""

    def __init__(self, y):
        self.y, self.reads = y, 0

    def __array__(self, *a, **k):
        self.reads += 1
        return self.y


# ---- (i) no bag drawn: the rows' own weights ----

FLAGS = {"plain": {}, "stratified": {"stratifiedSample": True},
         "neg_only": {"sampleNegOnly": True},
         "both_at_rate_2": {"stratifiedSample": True, "sampleNegOnly": True,
                            "baggingSampleRate": 2.0}}


@pytest.mark.parametrize("on_device", [False, True], ids=["host", "device"])
@pytest.mark.parametrize("flags", sorted(FLAGS))
def test_one_full_bag_is_the_rows_own_weights(flags, on_device, monkeypatch):
    conf = _conf(baggingNum=1, **FLAGS[flags])
    y, w = _labels_weights()
    want = old_bag_weights(conf, y, jnp.asarray(w) if on_device else w, 1, 7)
    monkeypatch.setattr(trainer, "bagging_weights", None)  # never called
    assert not trainer.bags_drawn(conf, 1)
    if on_device:
        w = jnp.asarray(w)
        with jax.transfer_guard("disallow"):  # nothing goes up or comes down
            got = trainer.bag_row_weights(conf, Unreadable(), w, 1, 7)
    else:
        got = trainer.bag_row_weights(conf, Unreadable(), w, 1, 7)
        assert np.shares_memory(got, w)  # a view: no (1, n) array is built
    assert got.shape == (1, N)
    _same_bits(got, want)


@pytest.mark.parametrize("on_device", [False, True], ids=["host", "device"])
@pytest.mark.parametrize("dtype", ["float64", "float16", "int32", "int16"])
def test_one_full_bag_has_the_products_dtype(dtype, on_device):
    """`float32 ones * w` promotes as numpy does on the host and as jax
    does on the device; so does the case that multiplies nothing."""
    conf = _conf(baggingNum=1)
    y, w = _labels_weights(w_dtype=np.dtype(dtype))
    w = jnp.asarray(w) if on_device else w
    _same_bits(trainer.bag_row_weights(conf, y, w, 1, 7),
               old_bag_weights(conf, y, w, 1, 7))


# ---- (ii) every other bagging: the host draw, as it was ----

DRAWS = {
    # name: (conf fields, bags, NaN labels, labels are read)
    "poisson": ({"baggingWithReplacement": True}, 1, False, False),
    "bernoulli_0.7": ({"baggingSampleRate": 0.7}, 1, False, False),
    "three_bags_rate_1": ({}, 3, False, False),
    "stratified": ({"baggingSampleRate": 0.5, "stratifiedSample": True},
                   2, False, True),
    "stratified_three_full_bags": ({"stratifiedSample": True}, 3, False,
                                   True),
    "neg_only": ({"baggingSampleRate": 0.3, "sampleNegOnly": True}, 2,
                 False, True),
    "neg_only_poisson": ({"baggingWithReplacement": True,
                          "sampleNegOnly": True}, 1, False, True),
    "nan_labels_stratified": ({"baggingSampleRate": 0.5,
                               "stratifiedSample": True}, 2, True, True),
    "nan_labels_neg_only": ({"baggingSampleRate": 0.3,
                             "sampleNegOnly": True}, 2, True, True),
    "nan_labels_bernoulli": ({"baggingSampleRate": 0.7}, 2, True, False),
}


@pytest.mark.parametrize("on_device", [False, True], ids=["host", "device"])
@pytest.mark.parametrize("draw", sorted(DRAWS))
def test_a_drawn_bagging_is_the_host_draw(draw, on_device):
    fields, n_bags, nan_labels, reads_labels = DRAWS[draw]
    conf = _conf(baggingNum=n_bags, **fields)
    y, w = _labels_weights(nan_labels)
    w = jnp.asarray(w) if on_device else w
    assert trainer.bags_drawn(conf, n_bags)
    for seed in (7, 3000000019):
        labels = Counted(y)
        got = trainer.bag_row_weights(conf, labels, w, n_bags, seed)
        assert (labels.reads > 0) == reads_labels
        assert got.shape == (n_bags, N)
        _same_bits(got, old_bag_weights(conf, y, w, n_bags, seed))


def test_neg_only_override_is_the_callers():
    """`train_nn` drops `sampleNegOnly` for native multi-class: the draw
    is then the plain one and reads no label."""
    conf = _conf(baggingNum=2, baggingSampleRate=0.5, sampleNegOnly=True)
    y, w = _labels_weights()
    got = trainer.bag_row_weights(conf, Unreadable(), w, 2, 7,
                                  neg_only=False)
    _same_bits(got, old_bag_weights(conf, y, w, 2, 7, neg_only=False))
    assert not np.array_equal(got, old_bag_weights(conf, y, w, 2, 7))


# ---- (iii) a fresh job's state: one program a (spec, bags) ----

def _spec(weight_init="xavier", hidden=(6, 3)):
    return nn_mod.MLPSpec(input_dim=5, hidden_dims=hidden,
                          activations=("tanh",) * len(hidden),
                          weight_init=weight_init)


@pytest.mark.parametrize("n_bags", [1, 3])
@pytest.mark.parametrize("weight_init",
                         ["xavier", "he", "lecun", "zero", "default"])
def test_compiled_state_is_the_eager_lines(weight_init, n_bags):
    spec = _spec(weight_init)
    for seed in (5, 3000000019):
        key = jax.random.PRNGKey(seed)
        _same_bits(trainer.fresh_nn_state(key, spec, n_bags),
                   old_nn_state(key, spec, n_bags))
    # and `init_params` called op by op (the streaming trainer, `train_nn`
    # with frozen layers) draws what it drew
    _same_bits(nn_mod.init_params(spec, key), old_init_params(spec, key))


def test_an_equal_job_finds_the_state_program(every_program_cached):
    jax.clear_caches()
    key = jax.random.PRNGKey(5)
    _CacheEvents.count, _CacheEvents.armed = 0, True
    first = trainer.fresh_nn_state(key, _spec(), 2)
    asked_by_first = _CacheEvents.count
    _CacheEvents.count = 0
    again = trainer.fresh_nn_state(jax.random.PRNGKey(5), _spec(), 2)
    asked_again = _CacheEvents.count
    _CacheEvents.armed = False
    assert asked_by_first > 0
    assert (trainer.fresh_nn_state._cache_size(), asked_again) == (1, 0)
    _same_bits(first, again)
    # another spec or another number of bags is another program
    trainer.fresh_nn_state(key, _spec(hidden=(4,)), 2)
    trainer.fresh_nn_state(key, _spec(), 3)
    assert trainer.fresh_nn_state._cache_size() == 3


def _rows():
    rng = np.random.default_rng(29)
    x = rng.normal(size=(N, 5)).astype(np.float32)
    idx = rng.integers(0, 11, (N, 3)).astype(np.int32)
    y = (x[:, 0] + 0.3 * rng.normal(size=N) > 0).astype(np.float32)
    return x, idx, y, (rng.random(N) + 0.5).astype(np.float32)


def _result_leaves(res):
    return [np.asarray(leaf) for leaf in jax.tree.leaves(
        (res.train_errors, res.val_errors, res.best_val, res.best_epoch,
         res.params_per_bag))]


def _given_state(spec):
    params = old_init_params(spec, jax.random.PRNGKey(77))
    mask = jax.tree.map(lambda p: np.ones(p.shape, np.float32), params)
    mask[0]["w"][0] = 0.0
    return {"init_params": {"init_params": params},
            "fixed_layers": {"fixed_layers": [1]},
            "grad_mask": {"grad_mask": mask},
            "init_params_fixed_layers": {"init_params": params,
                                         "fixed_layers": [2]}}


@pytest.mark.parametrize("given", ["init_params", "fixed_layers",
                                   "grad_mask", "init_params_fixed_layers"])
def test_a_job_given_its_state_takes_the_eager_lines(given, monkeypatch):
    x, _, y, w = _rows()
    conf = _conf(baggingNum=2)
    spec = nn_mod.MLPSpec.from_train_params(conf.params, input_dim=5)
    monkeypatch.setattr(trainer, "fresh_nn_state", None)  # never called
    res = trainer.train_nn(conf, x, y, w, seed=5, **_given_state(spec)[given])
    assert np.isfinite(res.val_errors).all()
    first = res.params_per_bag[0][0]["w"]
    if given == "fixed_layers":  # layer 1 stays as drawn, in both bags
        drawn = old_nn_state(jax.random.PRNGKey(5), spec, 2)[1][0]["w"]
        assert np.array_equal(first, np.asarray(drawn[0]))
    if given == "grad_mask":  # the masked row stays; the rest trains
        drawn = old_nn_state(jax.random.PRNGKey(5), spec, 2)[1][0]["w"][0]
        assert np.array_equal(first[0], np.asarray(drawn[0]))
        assert not np.array_equal(first[1:], np.asarray(drawn[1:]))


# ---- (iv) the three trainers return what the old expressions gave ----

BAGGINGS = {"one_full_bag": {"baggingNum": 1},
            "poisson_two_bags": {"baggingNum": 2,
                                 "baggingWithReplacement": True},
            "stratified": {"baggingNum": 2, "baggingSampleRate": 0.6,
                           "stratifiedSample": True},
            "neg_only": {"baggingNum": 1, "baggingSampleRate": 0.4,
                         "sampleNegOnly": True}}


def _the_old_way(monkeypatch):
    for module in (trainer, train_wdl, train_mtl):
        monkeypatch.setattr(module, "bag_row_weights", old_bag_weights)
    monkeypatch.setattr(trainer, "fresh_nn_state", old_nn_state)


def run_nn(bagging, on_device=False):
    x, _, y, w = _rows()
    if on_device:
        x, y, w = (jnp.asarray(a) for a in (x, y, w))
    conf = _conf(**BAGGINGS[bagging])
    if bagging == "neg_only":  # one case through a normal draw's scale
        conf.params["WeightInitializer"] = "he"
    return _result_leaves(trainer.train_nn(conf, x, y, w, seed=5))


def run_nn_val_data(bagging):
    """As the benchmark's cells call it: device rows, a validation set
    of the caller's."""
    x, _, y, w = (jnp.asarray(a) for a in _rows())
    return _result_leaves(trainer.train_nn(
        _conf(**BAGGINGS[bagging]), x[:400], y[:400], w[:400], seed=5,
        val_data=(x[400:], y[400:], w[400:])))


def run_wdl(bagging):
    x, idx, y, w = _rows()
    conf = _conf(**BAGGINGS[bagging])
    conf.params.update({"EmbedSize": 4, "MiniBatchRows": 64})
    return _result_leaves(train_wdl.train_wdl(conf, x, idx, y, w, (11,) * 3,
                                              seed=5))


def run_mtl(bagging, tmp_path, rng):
    """`run_mtl` on a model set whose `norm` output is stood in for."""
    from tests.synth import make_model_set
    root = make_model_set(os.path.join(str(tmp_path), bagging), rng,
                          n_rows=50, algorithm="MTL")
    ctx = ProcessorContext.load(root)
    mc = ctx.model_config
    mc.dataSet.targetColumnName = "diagnosis|second_tag"
    mc.train = _conf(**BAGGINGS[bagging])
    x, _, y, w = _rows()
    y2 = np.stack([y, 1 - y], axis=1)
    y2[::17, 1] = np.nan
    norm_dir = ctx.path_finder.normalized_data_path()
    os.makedirs(norm_dir, exist_ok=True)
    np.savez(os.path.join(norm_dir, "data.npz"), dense=x, weights=w,
             task_tags=y2)
    with open(os.path.join(norm_dir, "meta.json"), "w") as f:
        f.write('{"denseNames": ["a", "b", "c", "d", "e"]}')
    train_mtl.run_mtl(ctx, seed=5)
    leaves = []
    for i in range(mc.train.baggingNum):
        path = ctx.path_finder.model_path(i, "mtl")
        leaves += [np.asarray(leaf)
                   for leaf in jax.tree.leaves(load_model(path)[2])]
        os.remove(path)
    return leaves


@pytest.mark.parametrize("bagging", sorted(BAGGINGS))
@pytest.mark.parametrize("entry", ["train_nn", "train_nn_device_rows",
                                   "train_nn_val_data", "train_wdl",
                                   "run_mtl"])
def test_trainers_return_what_the_old_expressions_gave(
        entry, bagging, monkeypatch, tmp_path, rng):
    run = {"train_nn": lambda: run_nn(bagging),
           "train_nn_device_rows": lambda: run_nn(bagging, on_device=True),
           "train_nn_val_data": lambda: run_nn_val_data(bagging),
           "train_wdl": lambda: run_wdl(bagging),
           "run_mtl": lambda: run_mtl(bagging, tmp_path, rng)}[entry]
    now = run()
    with monkeypatch.context() as old:
        _the_old_way(old)
        before = run()
    assert len(now) >= 4
    _same_bits(now, before)


# ---- (v) the job's span says which branch it took ----

@pytest.mark.parametrize("bagging,drawn", [("one_full_bag", 0),
                                           ("poisson_two_bags", 1),
                                           ("neg_only", 1)])
@pytest.mark.parametrize("entry", ["train_nn", "train_wdl", "run_mtl"])
def test_the_job_span_carries_bags_drawn(entry, bagging, drawn, monkeypatch,
                                         tmp_path, rng):
    seen = []
    span = obs_trace.span

    def recording(name, **attrs):
        seen.append((name, attrs))
        return span(name, **attrs)

    monkeypatch.setattr(obs_trace, "span", recording)
    {"train_nn": lambda: run_nn(bagging),
     "train_wdl": lambda: run_wdl(bagging),
     "run_mtl": lambda: run_mtl(bagging, tmp_path, rng)}[entry]()
    jobs = [attrs for name, attrs in seen if name == "train.job"]
    assert len(jobs) == 1
    assert jobs[0]["bags_drawn"] == drawn
    assert jobs[0]["bags"] == BAGGINGS[bagging]["baggingNum"]
