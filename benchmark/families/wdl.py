"""The `wdl` family: resident mini-batch Wide & Deep training through
`shifu_tpu.processor.train_wdl.train_wdl`, what `run_wdl` calls once
`norm`'s dense and index blocks are loaded."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families import wdl_reference

PROGRAM_MODULES = ("shifu_tpu",)
RATE_METRIC = "train_rows_per_s"


def _entry():
    """The program's entry; a checkout from before it has none and the
    run ends here, before anything is made."""
    from shifu_tpu.processor import train_wdl
    return train_wdl.train_wdl


def make_data(config, seed: int, chips: int):
    """Training and validation rows on the device, one program each; both
    share the seed's id effects."""
    _entry()
    if chips != 1:
        raise ValueError("the wdl family places its rows on one chip")
    dataset = importlib.import_module(
        "benchmark.datasets." + config["dataset"])
    effects = dataset.id_effects(config, seed)

    def rows(stream, n_rows):
        make = jax.jit(lambda key, eff: dataset.fill(key, n_rows, config,
                                                     eff))
        return make(dataset.seed_key(seed, stream), effects)

    dense, ids, y = rows(0, config["train_rows"])
    dense_v, ids_v, yv = rows(1, config["valid_rows"])
    return {"dense": dense, "ids": ids, "y": y, "w": jnp.ones_like(y),
            "dense_v": dense_v, "ids_v": ids_v, "yv": yv,
            "wv": jnp.ones_like(yv)}


def units_per_call(config, traffic) -> int:
    return config["train_rows"] * traffic["steps_per_call"]


def make_call(config, traffic, data, job_seed: int):
    """The job call: `steps_per_call` epochs of shuffled mini-batch updates
    from a fresh initialisation, ending in the fetch of the best epoch's
    parameters, tables included."""
    from shifu_tpu.config.model_config import ModelTrainConf

    train_wdl = _entry()
    hidden = list(config["hidden_dims"])
    conf = ModelTrainConf.from_dict({
        "baggingNum": config["bags"], "baggingSampleRate": 1.0,
        "baggingWithReplacement": False,
        "numTrainEpochs": traffic["steps_per_call"],
        "params": {"NumHiddenNodes": hidden,
                   "ActivationFunc": [config["activation"]] * len(hidden),
                   "EmbedSize": config["embed_size"],
                   "Propagation": config["optimizer"],
                   "LearningRate": config["learning_rate"],
                   "MiniBatchRows": config["batch_rows"],
                   "RegularizedConstant": 0.0}})
    val = (data["dense_v"], data["ids_v"], data["yv"], data["wv"])

    def call():
        return train_wdl(conf, data["dense"], data["ids"], data["y"],
                         data["w"], config["vocab_sizes"], seed=job_seed,
                         val_data=val)

    return call


def outputs(result):
    """What the comparison reads of a job call's return, as host arrays."""
    return {"train_errors": np.asarray(result.train_errors)[0],
            "val_errors": np.asarray(result.val_errors)[0],
            "best_epoch": int(np.asarray(result.best_epoch)[0]),
            "params": result.params_per_bag[0]}


def _host(sim):
    return {**{k: sim[k] for k in ("train_errors", "val_errors",
                                   "best_epoch")},
            "params": jax.tree.map(np.asarray, sim["params"])}


def check(config, traffic, data, job_seed: int, got, control: bool = False):
    ref = wdl_reference.simulate(config, traffic, data, job_seed)
    found = {"checks": wdl_reference.compare(config, data, got, ref)}
    if control:
        low = wdl_reference.simulate(config, traffic, data, job_seed,
                                     dtype=config["control_precision"])
        found["control_checks"] = wdl_reference.compare(config, data,
                                                        _host(low), ref)
    return found


def faults(config, traffic, data, job_seed: int, got):
    """The faults a training cell of this family can have, each as a
    function that returns what a job call with the fault would return:
    the two of every training family through the program's own entry, the
    table path's three planted in the reference (`TABLE_FAULTS`)."""

    def state_unchanged():
        init = jax.tree.map(np.asarray,
                            wdl_reference.init_params(config, job_seed))
        return {"train_errors": np.full_like(got["train_errors"],
                                             got["train_errors"][0]),
                "val_errors": np.full_like(got["val_errors"],
                                           got["val_errors"][0]),
                "best_epoch": 0, "params": init}

    def half_batch():
        half = config["train_rows"] // 2
        part = {**data, **{k: data[k][:half]
                           for k in ("dense", "ids", "y", "w")}}
        return outputs(make_call(config, traffic, part, job_seed)())

    def planted(fault):
        return lambda: _host(wdl_reference.simulate(
            config, traffic, data, job_seed, fault=fault))

    return {"state_unchanged": state_unchanged, "half_batch": half_batch,
            **{f: planted(f) for f in wdl_reference.TABLE_FAULTS}}
