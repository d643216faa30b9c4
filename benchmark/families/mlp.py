"""The `mlp` family: resident full-batch NN training through
`shifu_tpu.train.trainer.train_nn`, what `processor/train.py::_train_dense`
calls once the normalized matrix is loaded."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families import mlp_reference

PROGRAM_MODULES = ("shifu_tpu",)
RATE_METRIC = "train_rows_per_s"
STD_DEV_CUTOFF = 4.0


def _normalized(xT):
    """What `shifu norm` (ZSCALE) hands to train for N(0,1) columns:
    values clipped at the cut-off, a missing value at the column's mean."""
    return jnp.clip(jnp.nan_to_num(xT, nan=0.0), -STD_DEV_CUTOFF,
                    STD_DEV_CUTOFF)


def _rows(dataset, key, n_rows: int):
    def write(outs, xT, y, start):
        x, ys = outs
        x = jax.lax.dynamic_update_slice(x, _normalized(xT).T, (start, 0))
        return x, jax.lax.dynamic_update_slice(ys, y, (start,))

    outs = (jnp.zeros((n_rows, dataset.N_COLS), jnp.float32),
            jnp.zeros((n_rows,), jnp.float32))
    return dataset.fill(key, n_rows, outs, write)


def make_data(config, seed: int, chips: int):
    """Training and validation rows on the device, one program each."""
    if chips != 1:
        raise ValueError("the mlp family places its rows on one chip")
    dataset = importlib.import_module(
        "benchmark.datasets." + config["dataset"])
    make = jax.jit(_rows, static_argnums=(0, 2))
    x, y = make(dataset, dataset.seed_key(seed, 0), config["train_rows"])
    xv, yv = make(dataset, dataset.seed_key(seed, 1), config["valid_rows"])
    return {"x": x, "y": y, "w": jnp.ones_like(y),
            "xv": xv, "yv": yv, "wv": jnp.ones_like(yv)}


def units_per_call(config, traffic) -> int:
    return config["train_rows"] * traffic["steps_per_call"]


def make_call(config, traffic, data, job_seed: int):
    """The job call: `steps_per_call` full-batch epochs from a fresh
    initialisation, ending in the fetch of the trained parameters."""
    from shifu_tpu.config.model_config import ModelTrainConf
    from shifu_tpu.train import trainer

    hidden = list(config["hidden_dims"])
    conf = ModelTrainConf.from_dict({
        "baggingNum": config["bags"], "baggingSampleRate": 1.0,
        "baggingWithReplacement": False,
        "numTrainEpochs": traffic["steps_per_call"],
        "params": {"NumHiddenLayers": len(hidden),
                   "NumHiddenNodes": hidden,
                   "ActivationFunc": [config["activation"]] * len(hidden),
                   "Propagation": config["optimizer"],
                   "LearningRate": config["learning_rate"],
                   "AdamBeta1": config["adam_beta1"],
                   "AdamBeta2": config["adam_beta2"],
                   "Loss": config["loss"],
                   "WeightInitializer": config["weight_init"],
                   "RegularizedConstant": 0.0}})
    val = (data["xv"], data["yv"], data["wv"])

    def call():
        return trainer.train_nn(conf, data["x"], data["y"], data["w"],
                                seed=job_seed, val_data=val)

    return call


def outputs(result):
    """What the comparison reads of a job call's return, as host arrays."""
    return {"train_errors": np.asarray(result.train_errors)[0],
            "val_errors": np.asarray(result.val_errors)[0],
            "best_epoch": int(np.asarray(result.best_epoch)[0]),
            "params": result.params_per_bag[0]}


def check(config, traffic, data, job_seed: int, got, control: bool = False):
    ref = mlp_reference.simulate(config, traffic, data, job_seed)
    found = {"checks": mlp_reference.compare(config, got, ref)}
    if control:
        low = mlp_reference.simulate(config, traffic, data, job_seed,
                                     dtype=config["control_precision"])
        found["control_checks"] = mlp_reference.compare(config, low, ref)
    return found


def faults(config, traffic, data, job_seed: int, got):
    """The faults a training cell of this family can have, each as a
    function that returns what a job call with the fault would return."""

    def state_unchanged():
        init = jax.tree.map(np.asarray,
                            mlp_reference.init_params(config, job_seed))
        return {"train_errors": np.full_like(got["train_errors"],
                                             got["train_errors"][0]),
                "val_errors": np.full_like(got["val_errors"],
                                           got["val_errors"][0]),
                "best_epoch": 0, "params": init}

    def half_batch():
        half = config["train_rows"] // 2
        part = {**data, **{k: data[k][:half] for k in ("x", "y", "w")}}
        return outputs(make_call(config, traffic, part, job_seed)())

    return {"state_unchanged": state_unchanged, "half_batch": half_batch}
