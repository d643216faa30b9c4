"""The `rf` family: Shifu's random forest through
`shifu_tpu.models.gbdt.build_rf` on a placed (columns, rows) bin matrix,
what `processor/train_tree.py::run_tree` and `combo` call for
`train.algorithm: RF` once the rows are binned. The table is the `gbt`
family's (`gbt._binned`: at equal seed the bin matrix is `gbt-higgs`'s bit
for bit); a job call is one fresh forest from a job seed of its own, so
every call draws new bags and feature subsets inside the timed path."""

import contextlib
import itertools

import jax.numpy as jnp
import numpy as np

from benchmark.families import gbt, rf_reference

# `models/rf_draw.py` is where `build_rf` draws a lockstep group's instance
# weights on the device by its documented rule: a checkout without it
# draws numpy's on the host, takes host rows only and holds the whole
# forest in one group, and does not support this deployment
PROGRAM_MODULES = ("shifu_tpu", "shifu_tpu.models.rf_draw")
RATE_METRIC = "train_rows_per_s"

make_data = gbt.make_data
units_per_call = gbt.units_per_call


def _tree_config(config):
    from shifu_tpu.models import gbdt
    return gbdt.TreeConfig(
        max_depth=config["max_depth"], n_bins=config["n_bins"],
        min_instances_per_node=config["min_instances_per_node"],
        min_info_gain=config["min_info_gain"],
        reg_lambda=config["reg_lambda"])


def _build(config, traffic, data, seed: int):
    """One job: the configuration's whole forest from `seed` (the
    traffic's `steps_per_call` trees; a rehearsal's forest is smaller),
    fetched; the seed rides with the trees so that the comparison draws
    the same bags."""
    from shifu_tpu.models import gbdt
    if not config["bagging_with_replacement"]:
        raise ValueError("build_rf draws its bags with replacement")
    trees = gbdt.build_rf(
        _tree_config(config), data["binsT"], data["y"], data["w"],
        n_trees=config["n_trees"], subset_strategy=config["feature_subset"],
        bagging_rate=config["bagging_rate"], seed=seed)
    return {**trees, "seed": np.asarray(seed, np.int64)}


def make_call(config, traffic, data, job_seed: int):
    """The job call: a fresh `build_rf` of the whole forest, ending in
    the fetch of the stacked trees; call k of a run takes the job seed
    `job_seed + k`."""
    seeds = itertools.count(job_seed)

    def call():
        return _build(config, traffic, data, next(seeds) % (2 ** 31 - 1))

    return call


def outputs(result):
    return {k: np.asarray(v) for k, v in result.items()}


def _forest(got):
    return {k: v for k, v in got.items() if k != "seed"}


def check(config, traffic, data, job_seed: int, got, control: bool = False):
    return rf_reference.follow(config, data, int(got["seed"]), _forest(got),
                               control=control)


@contextlib.contextmanager
def _planted(name: str, broken):
    """`rf_draw.<name>` replaced by `broken(original)` for the length of
    one build: how a fault of the draw is planted underneath `build_rf`."""
    from shifu_tpu.models import rf_draw
    original = getattr(rf_draw, name)
    setattr(rf_draw, name, broken(original))
    try:
        yield
    finally:
        setattr(rf_draw, name, original)


def faults(config, traffic, data, job_seed: int, got):
    """The faults a training cell of this family can have, each as a
    function that returns what a job call with the fault would return."""
    seed = int(got["seed"])

    def built(data=data):
        return outputs(_build(config, traffic, data, seed))

    def bags_shared():
        # every tree is given tree 0's bag
        with _planted("bags", lambda draw: lambda key, ids, *a, **k:
                      draw(key, np.zeros_like(ids), *a, **k)):
            return built()

    def bag_unweighted():
        with _planted("bags", lambda draw: lambda *a, **k:
                      jnp.ones_like(draw(*a, **k))):
            return built()

    def mask_ignored():
        with _planted("masks", lambda draw: lambda *a, **k:
                      jnp.ones_like(draw(*a, **k))):
            return built()

    def half_batch():
        half = config["train_rows"] // 2
        return built({"binsT": data["binsT"][:, :half],
                      "y": data["y"][:half], "w": data["w"][:half]})

    def answer_altered():
        # one split of the first tree, one bin off where it is produced
        out = {k: v.copy() for k, v in got.items()}
        node = 2 ** (config["max_depth"] - 1) - 1
        out["bin"][0, node] = (out["bin"][0, node] + 8) % (config["n_bins"] - 2)
        return out

    return {"bags_shared": bags_shared, "bag_unweighted": bag_unweighted,
            "mask_ignored": mask_ignored, "half_batch": half_batch,
            "answer_altered": answer_altered}
