"""A repeat job finds its program: `train_bags_carry`'s static arguments
are the same objects for equal settings (`optimizers.program_static`).

Three things are held here, on the CPU. A second job with equal settings
adds no program to jit's cache, asks the persistent compile cache nothing
and returns the first job's bits, through each entry that hands statics
over (`train_nn`, `train_wdl`, the MTL model through `train_bags`). No
two settings share a program: for every value a trace bakes in, training
with A (in a process that has seen nothing), then B, then A again gives
A's result twice and another for B, the one B gives in a process that
never saw A. And the memo is
bounded.
"""

import copy
import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shifu_tpu.config.model_config import ModelTrainConf
from shifu_tpu.models import mtl, nn as nn_mod
from shifu_tpu.processor import train_wdl
from shifu_tpu.train import optimizers, trainer

CACHE_EVENTS = ("/jax/compilation_cache/cache_hits",
                "/jax/compilation_cache/cache_misses")
MEMOS = (optimizers.make_optimizer, trainer.nn_objectives,
         trainer.objectives, train_wdl._tables_scoped)


class _CacheEvents:
    """The persistent compile cache's hits and misses while `armed`, as
    `benchmark/run.py` counts a window's."""
    count, armed = 0, False

    @classmethod
    def on(cls, event, **_):
        cls.count += cls.armed and event in CACHE_EVENTS


jax.monitoring.register_event_listener(_CacheEvents.on)

N, FEATS, CATS, VOCAB, TASKS = 384, 6, 3, 11, 2
BASE = {"NumHiddenLayers": 1, "NumHiddenNodes": [8],
        "ActivationFunc": ["tanh"], "Propagation": "ADAM",
        "LearningRate": 0.05}


def _rows():
    rng = np.random.default_rng(29)
    x = rng.normal(size=(N, FEATS)).astype(np.float32)
    idx = rng.integers(0, VOCAB, (N, CATS)).astype(np.int32)
    y = (x[:, 0] + 0.3 * rng.normal(size=N) > 0).astype(np.float32)
    return x, idx, y, np.ones(N, np.float32)


def _conf(params):
    """A fresh `ModelTrainConf` from a fresh dict: equal values, no
    object shared with an earlier call."""
    return ModelTrainConf.from_dict(copy.deepcopy(
        {"numTrainEpochs": 3, "baggingNum": 1, "validSetRate": 0.25,
         "earlyStoppingRounds": 0, "params": params}))


def _leaves(*trees):
    return [np.asarray(leaf) for leaf in jax.tree.leaves(trees)]


def run_nn(params):
    x, _, y, w = _rows()
    res = trainer.train_nn(_conf(params), x, y, w, seed=5)
    return _leaves(res.train_errors, res.val_errors, res.params_per_bag)


def run_wdl(params):
    x, idx, y, w = _rows()
    res = train_wdl.train_wdl(
        _conf({"EmbedSize": 4, "MiniBatchRows": 64, **params}),
        x, idx, y, w, (VOCAB,) * CATS, seed=5)
    return _leaves(res.train_errors, res.val_errors, res.params_per_bag)


def run_mtl(params):
    """The MTL model through `train_bags`, as `run_mtl` drives it."""
    x, _, y, w = _rows()
    y = np.stack([y, 1 - y], axis=1)
    conf = _conf(params)
    spec = mtl.MTLSpec.from_train_params(conf.params, FEATS, TASKS)
    keys = jax.random.split(jax.random.PRNGKey(5), 1)
    stacked = jax.vmap(lambda k: mtl.init_params(spec, k))(keys)
    tr, val = trainer.split_validation(N, conf.validSetRate, 5)
    best, tr_errs, val_errs, _, _ = trainer.train_bags(
        *trainer.objectives(mtl, spec),
        optimizers.optimizer_from_params(conf.params),
        conf.numTrainEpochs, 0, 0.0, stacked, (x[tr], y[tr]),
        w[tr][None, :], (x[val], y[val]), w[val], keys,
        jax.tree.map(lambda l: jnp.ones_like(l[0]), stacked))
    return _leaves(tr_errs, val_errs, best)


ENTRIES = {"train_nn": run_nn, "train_wdl": run_wdl, "mtl": run_mtl}


def _equal(a, b):
    return len(a) == len(b) and all(
        x.shape == y.shape and x.dtype == y.dtype
        and np.array_equal(x, y, equal_nan=True) for x, y in zip(a, b))


def _forget():
    """What a fresh process starts with: no program, no memoised static."""
    jax.clear_caches()
    for memo in MEMOS:
        memo.cache_clear()


@pytest.fixture
def every_program_cached():
    """Every program goes through the persistent compile cache, however
    fast it compiled, as in a run of the benchmark: a program built anew
    then shows as a miss, one traced and lowered anew as a hit."""
    names = {"jax_persistent_cache_min_compile_time_secs": 0.0,
             "jax_persistent_cache_min_entry_size_bytes": -1}
    before = {name: getattr(jax.config, name) for name in names}
    for name, value in names.items():
        jax.config.update(name, value)
    yield
    for name, value in before.items():
        jax.config.update(name, value)


@pytest.mark.parametrize("entry", sorted(ENTRIES))
def test_an_equal_job_reuses_the_program(entry, every_program_cached):
    run = ENTRIES[entry]
    _forget()
    _CacheEvents.count, _CacheEvents.armed = 0, True
    first = run(dict(BASE))
    asked_by_first = _CacheEvents.count
    programs = trainer.train_bags_carry._cache_size()
    _CacheEvents.count = 0
    again = run(dict(BASE))
    _CacheEvents.armed = False
    # the listener hears this process's compile cache at all
    assert asked_by_first > 0
    assert (programs, trainer.train_bags_carry._cache_size(),
            _CacheEvents.count) == (1, 1, 0)
    assert _equal(first, again)


# one case a value that a trace of the program bakes in: (entry, A, B)
BAKED_IN = {
    "LearningRate": ("train_nn", {}, {"LearningRate": 0.2}),
    "Propagation": ("train_nn", {}, {"Propagation": "B"}),
    "AdamBeta1": ("train_nn", {}, {"AdamBeta1": 0.5}),
    "AdamBeta2": ("train_nn", {}, {"AdamBeta2": 0.9}),
    "LearningDecay": ("train_nn", {}, {"LearningDecay": 0.3}),
    "Momentum": ("train_nn", {"Propagation": "M"},
                 {"Propagation": "M", "Momentum": 0.9}),
    "NumHiddenNodes": ("train_nn", {}, {"NumHiddenNodes": [5]}),
    "ActivationFunc": ("train_nn", {}, {"ActivationFunc": ["relu"]}),
    "Loss": ("train_nn", {}, {"Loss": "log"}),
    "RegularizedConstant": ("train_nn", {}, {"RegularizedConstant": 0.05}),
    "L1orL2": ("train_nn", {"RegularizedConstant": 0.05},
               {"RegularizedConstant": 0.05, "L1orL2": "L1"}),
    "DropoutRate": ("train_nn", {}, {"DropoutRate": 0.5}),
    "ComputeDtype": ("train_nn", {}, {"ComputeDtype": "bfloat16"}),
    "wdl.EmbedSize": ("train_wdl", {}, {"EmbedSize": 8}),
    "wdl.LearningRate": ("train_wdl", {}, {"LearningRate": 0.2}),
    "wdl.RegularizedConstant": ("train_wdl", {},
                                {"RegularizedConstant": 0.05}),
    "mtl.RegularizedConstant": ("mtl", {}, {"RegularizedConstant": 0.05}),
}


@pytest.mark.parametrize("value", sorted(BAKED_IN))
def test_no_two_settings_share_a_program(value):
    entry, a, b = BAKED_IN[value]
    run, a, b = ENTRIES[entry], {**BASE, **a}, {**BASE, **b}
    _forget()
    first_a, then_b, a_again = run(a), run(b), run(a)
    assert not _equal(first_a, then_b)
    assert _equal(first_a, a_again)
    _forget()
    assert _equal(run(b), then_b)


def test_the_memo_is_bounded():
    """More distinct settings than `STATICS_KEPT` leave no more than
    that alive: the oldest's functions are collected."""
    kept, more = optimizers.STATICS_KEPT, 4
    _forget()
    made = []
    for i in range(kept + more):
        spec = nn_mod.MLPSpec(input_dim=4, hidden_dims=(i + 1,),
                              activations=("tanh",))
        optimizer = optimizers.make_optimizer("ADAM", 0.01 * (i + 1))
        made += [weakref.ref(trainer.nn_objectives(spec)[0]),
                 weakref.ref(optimizer.update)]
        del optimizer
    gc.collect()
    for memo in (trainer.nn_objectives, optimizers.make_optimizer):
        assert memo.cache_info().currsize == kept
    assert sum(ref() is not None for ref in made) == 2 * kept
    assert all(ref() is None for ref in made[:2 * more])
