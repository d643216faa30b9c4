"""The `gbt` family: resident gradient-boosted trees through
`shifu_tpu.models.gbdt.build_gbt` on a placed (columns, rows) bin matrix,
what `processor/train_tree.py::run_tree` calls once the rows are binned."""

import importlib
import statistics

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families import gbt_reference

PROGRAM_MODULES = ("shifu_tpu",)
RATE_METRIC = "train_rows_per_s"


def equal_frequency_cuts(value_bins: int):
    """The value_bins - 1 cuts that split N(0,1) into equally likely bins."""
    normal = statistics.NormalDist()
    return np.asarray([normal.inv_cdf(k / value_bins)
                       for k in range(1, value_bins)], np.float32)


def _binned(dataset, cuts, n_bins: int, key, n_rows: int):
    def write(outs, xT, y, start):
        binsT, ys = outs
        # bin = number of cuts at or under the value: a chain of compares,
        # which a TPU streams; a binary search is a gather a level
        b = jnp.zeros(xT.shape, jnp.int32)
        for k in range(cuts.shape[0]):
            b = b + (xT >= cuts[k]).astype(jnp.int32)
        b = jnp.where(jnp.isnan(xT), n_bins - 1, b)
        binsT = jax.lax.dynamic_update_slice(binsT, b, (0, start))
        return binsT, jax.lax.dynamic_update_slice(ys, y, (start,))

    outs = (jnp.zeros((dataset.N_COLS, n_rows), jnp.int32),
            jnp.zeros((n_rows,), jnp.float32))
    return dataset.fill(key, n_rows, outs, write)


def make_data(config, seed: int, chips: int):
    """The binned rows on the device, never as floats outside one block."""
    if chips != 1:
        raise ValueError("the gbt family places its rows on one chip")
    dataset = importlib.import_module(
        "benchmark.datasets." + config["dataset"])
    cuts = jnp.asarray(equal_frequency_cuts(config["value_bins"]))
    make = jax.jit(_binned, static_argnums=(0, 2, 4))
    binsT, y = make(dataset, cuts, config["n_bins"],
                    dataset.seed_key(seed, 0), config["train_rows"])
    return {"binsT": binsT, "y": y, "w": jnp.ones_like(y)}


def units_per_call(config, traffic) -> int:
    return config["train_rows"] * traffic["steps_per_call"]


def make_call(config, traffic, data, job_seed: int):
    """The job call: a fresh build of `steps_per_call` trees, ending in
    the fetch of the ensemble. The build draws nothing, so the job's seed
    goes unused."""
    from shifu_tpu.models import gbdt

    if config["feature_subset"] != "ALL" or config["valid_rows"]:
        raise ValueError("the gbt family builds on all features, "
                         "with no validation rows")
    cfg = gbdt.TreeConfig(
        max_depth=config["max_depth"], n_bins=config["n_bins"],
        min_instances_per_node=config["min_instances_per_node"],
        min_info_gain=config["min_info_gain"],
        reg_lambda=config["reg_lambda"],
        learning_rate=config["learning_rate"], loss=config["loss"])

    def call():
        trees, _ = gbdt.build_gbt(cfg, data["binsT"], data["y"], data["w"],
                                  n_trees=traffic["steps_per_call"])
        return trees

    return call


def outputs(result):
    return {k: np.asarray(v) for k, v in result.items()}


def check(config, traffic, data, job_seed: int, got, control: bool = False):
    return gbt_reference.follow(config, data, got, control=control)


def faults(config, traffic, data, job_seed: int, got):
    """The faults a training cell of this family can have, each as a
    function that returns what a job call with the fault would return."""

    def state_unchanged():
        # the boosting state is the prediction: left unchanged, the
        # second round builds the first round's tree again
        return {k: np.concatenate([v[:1], v[:1], v[2:]])
                for k, v in got.items()}

    def half_batch():
        half = config["train_rows"] // 2
        part = {"binsT": data["binsT"][:, :half], "y": data["y"][:half],
                "w": data["w"][:half]}
        return outputs(make_call(config, traffic, part, job_seed)())

    def answer_altered():
        # one split of the first tree, one bin off where it is produced
        out = {k: v.copy() for k, v in got.items()}
        node = 2 ** (config["max_depth"] - 1) - 1
        out["bin"][0, node] = (out["bin"][0, node] + 8) % (config["n_bins"] - 2)
        return out

    return {"state_unchanged": state_unchanged, "half_batch": half_batch,
            "answer_altered": answer_altered}
