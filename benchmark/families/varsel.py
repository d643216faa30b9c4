"""The `varsel` family: Shifu's SE variable selection through
`shifu_tpu.processor.sensitivity.sensitivity_select`, what
`processor/varselect.py::_filter_by_sensitivity` calls for `varsel` with
filterBy SE once the candidate columns are loaded and normalized: the
quick train on every column, then every column wiped and every row
scored again, on rows that lie on the device."""

import importlib
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families import mlp, mlp_reference, varsel_reference

# `processor/sensitivity.py` holds the entry on resident rows and the
# cached ablation: a checkout without it has no such entry
PROGRAM_MODULES = ("shifu_tpu", "shifu_tpu.processor.sensitivity")
RATE_METRIC = "train_rows_per_s"


def _rows(dataset, key, n_rows: int, n_cols: int, informative: int):
    def write(outs, xT, y, start):
        x, ys = outs
        x = jax.lax.dynamic_update_slice(x, mlp._normalized(xT).T,
                                         (start, 0))
        return x, jax.lax.dynamic_update_slice(ys, y, (start,))

    outs = (jnp.zeros((n_rows, n_cols), jnp.float32),
            jnp.zeros((n_rows,), jnp.float32))
    return dataset.fill(key, n_rows, n_cols, informative, outs, write)


def make_data(config, seed: int, chips: int):
    """The whole table on the device, one program: what `shifu norm`
    (ZSCALE) hands to varsel, rows (rows, columns), labels, weights."""
    if chips != 1:
        raise ValueError("the varsel family places its rows on one chip")
    dataset = importlib.import_module(
        "benchmark.datasets." + config["dataset"])
    make = jax.jit(_rows, static_argnums=(0, 2, 3, 4))
    x, y = make(dataset, dataset.seed_key(seed), config["train_rows"],
                config["input_dim"], config["informative_columns"])
    return {"x": x, "y": y, "w": jnp.ones_like(y)}


def units_per_call(config, traffic) -> int:
    """The table's rows x the quick train's epochs."""
    return config["train_rows"] * traffic["steps_per_call"]


def train_conf(config):
    from shifu_tpu.config.model_config import ModelTrainConf
    hidden = list(config["hidden_dims"])
    return ModelTrainConf.from_dict({
        "baggingNum": config["bags"], "baggingSampleRate": 1.0,
        "baggingWithReplacement": False,
        "validSetRate": config["valid_set_rate"],
        "numTrainEpochs": config["num_train_epochs"],
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


def make_call(config, traffic, data, job_seed: int):
    """The job call: one fresh SE job, ending in the fetch of every
    column's delta; call k of a run takes the job seed `job_seed + k`,
    and so its own validSetRate split, of the same size every call."""
    from shifu_tpu.processor import sensitivity
    conf = train_conf(config)
    epochs = sensitivity.quick_train_conf(conf).numTrainEpochs
    if epochs != traffic["steps_per_call"]:
        raise ValueError(f"the quick train runs {epochs} epochs, the "
                         f"traffic counts {traffic['steps_per_call']}")
    seeds = itertools.count(job_seed)

    def call():
        seed = next(seeds) % (2 ** 31 - 1)
        return seed, sensitivity.sensitivity_select(
            conf, data["x"], data["y"], data["w"], seed=seed,
            filter_num=config["filter_num"], by=config["filter_by"])

    return call


def outputs(result):
    """What the comparison reads of a job call's return, as host arrays,
    with the job seed the call took."""
    seed, selection = result
    train = selection.train
    return {"seed": seed,
            "train_errors": np.asarray(train.train_errors)[0],
            "val_errors": np.asarray(train.val_errors)[0],
            "best_epoch": int(np.asarray(train.best_epoch)[0]),
            "params": train.params_per_bag[0],
            "deltas": np.asarray(selection.deltas, np.float64),
            "selected": np.asarray(selection.selected)}


def check(config, traffic, data, job_seed: int, got, control: bool = False):
    return varsel_reference.check(config, traffic, data, got["seed"], got,
                                  control)


def faults(config, traffic, data, job_seed: int, got):
    """The faults an SE job can have, each as a function that returns
    what a job call with the fault would return; where the fault is in
    the ablation, the reference's own ablation stands in for it."""
    k = config["filter_num"]

    def with_deltas(deltas):
        return {**got, "deltas": deltas,
                "selected": varsel_reference.top(deltas, k)}

    def column_block_dropped():
        from shifu_tpu.processor import sensitivity
        deltas = got["deltas"].copy()
        deltas[-min(len(deltas), sensitivity.COL_BLOCK):] = 0.0
        return with_deltas(deltas)

    def wiped_to_one():
        return with_deltas(varsel_reference.ablate(
            config, got["params"], data["x"], wipe=1.0))

    def untrained_params():
        init = mlp_reference.init_params(config, got["seed"])
        return with_deltas(varsel_reference.ablate(config, init, data["x"]))

    def half_rows():
        half = config["train_rows"] // 2
        part = {key: data[key][:half] for key in ("x", "y", "w")}
        return outputs(make_call(config, traffic, part, got["seed"])())

    def answer_altered():
        order = np.argsort(-got["deltas"], kind="stable")
        selected = got["selected"].copy()
        selected[selected == order[0]] = order[k]
        return {**got, "selected": selected}

    return {"column_block_dropped": column_block_dropped,
            "wiped_to_one": wiped_to_one,
            "untrained_params": untrained_params, "half_rows": half_rows,
            "answer_altered": answer_altered}
