"""`gbdt.build_rf` as the cell `rf-higgs.train` runs it, at small sizes on
the CPU: the draw rule made again outside the program, lockstep groups
that change no tree, a device-resident (columns, rows) table built where
it lies, the group sizing from bytes, and the forest against the `rf`
family's plain reference with every fault of the family failing.
"""

import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import run as harness  # noqa: E402
from benchmark.families import rf, rf_reference  # noqa: E402
from shifu_tpu.models import gbdt, rf_draw  # noqa: E402

SEED = 2 ** 31 + 33          # past 32 signed bits, as the driver's are
V5E_BYTES = 16_909_336_064   # a v5e's `bytes_limit` (PERF.md section 4)


@pytest.fixture(scope="module")
def table():
    rng = np.random.default_rng(33)
    r, c, b = 3001, 9, 16
    bins = rng.integers(0, b, (r, c)).astype(np.int32)
    logit = (bins[:, 0] - 7.5) / 4 + (bins[:, 1] > 9) - (bins[:, 2] < 3)
    y = (rng.random(r) < 1 / (1 + np.exp(-logit))).astype(np.float32)
    return bins, y, np.ones(r, np.float32), gbdt.TreeConfig(max_depth=4,
                                                            n_bins=b)


def _forest(table, n_trees=7, **kw):
    bins, y, w, cfg = table
    return gbdt.build_rf(cfg, bins, y, w, n_trees, "TWOTHIRDS", 1.0,
                         seed=SEED, **kw)


def _same(a, b):
    return all(np.array_equal(a[k], b[k]) for k in a)


# --- the draw ----------------------------------------------------------------

@pytest.mark.parametrize("rate", [1.0, 0.5, 2.5])
def test_the_documented_draw_is_made_again_outside_the_program(rate):
    """Weights and masks of every tree, equal: the program's vmapped,
    jitted draw against the reference's own, a tree at a time, from the
    rule in `rf_draw`'s docstring (and the weights are Poisson)."""
    n_trees, rows, cols, k = 5, 4099, 28, 18
    ids = np.arange(n_trees, dtype=np.int32)
    key = jax.random.key(SEED)
    edges = rf_draw.poisson_thresholds(rate)
    assert list(edges) == rf_reference.poisson_thresholds(rate).tolist()
    iw = np.asarray(rf_draw.bags(key, ids, rows, edges))
    for t in range(n_trees):
        np.testing.assert_array_equal(
            iw[t], np.asarray(rf_reference.instance_weights(SEED, t, rows,
                                                            rate)))
    masks = np.asarray(rf_draw.masks(key, ids, cols, k))
    np.testing.assert_array_equal(
        masks > 0, rf_reference.feature_masks(SEED, n_trees, cols, k))
    assert (masks.sum(axis=1) == k).all()
    assert len({tuple(m) for m in masks}) > 1
    assert abs(iw.mean() - rate) < 0.05 and abs(iw.var() - rate) < 0.15
    # a group draws what the whole forest draws for the same trees
    np.testing.assert_array_equal(
        np.asarray(rf_draw.bags(key, ids[3:], rows, edges)), iw[3:])
    # and padding the rows changes no real row's weight
    np.testing.assert_array_equal(
        np.asarray(rf_draw.bags(key, ids, rows + 5, edges))[:, :rows], iw)


def test_poisson_thresholds_invert_the_distribution_function():
    edges = rf_draw.poisson_thresholds(1.0)
    assert len(edges) == 12 and list(edges) == sorted(set(edges))
    assert edges[0] == math.floor(math.exp(-1.0) * 2 ** 32)
    assert edges[-1] < 2 ** 32 - 1
    assert rf_reference.subset_count("TWOTHIRDS", 28) == \
        gbdt.feature_subset_count("TWOTHIRDS", 28) == 18


# --- groups -------------------------------------------------------------------

@pytest.mark.parametrize("group", [1, 3])
def test_groups_give_the_one_group_forest_bit_for_bit(table, monkeypatch,
                                                      group):
    whole = _forest(table)
    monkeypatch.setattr(gbdt, "_rf_group_trees", lambda *a: group)
    assert _same(whole, _forest(table))


def test_group_size_is_read_from_bytes():
    """On a 16 GB chip: 2^24 rows of 28 columns take 4 trees at a time
    (8 would need 17.9 GB), 2^23 rows 8, 2^25 rows 2; a forest that fits
    whole is one group; the CPU reports no limit and takes the forest
    whole. The benchmark's configuration records what its rows give."""
    class Chip:
        def __init__(self, limit):
            self.limit = limit

        def memory_stats(self):
            return {"bytes_limit": self.limit} if self.limit else None

    v5e = Chip(V5E_BYTES)
    assert gbdt._rf_group_trees(10, 2 ** 24, 28, v5e) == 4
    assert gbdt._rf_group_trees(100, 2 ** 24, 28, v5e) == 4
    assert gbdt._rf_group_trees(100, 2 ** 23, 28, v5e) == 8
    assert gbdt._rf_group_trees(10, 2 ** 25, 28, v5e) == 2
    assert gbdt._rf_group_trees(3, 2 ** 24, 28, v5e) == 3
    assert gbdt._rf_group_trees(100, 2 ** 20, 28, v5e) == 100
    assert gbdt._rf_group_trees(100, 2 ** 24, 28, Chip(None)) == 100
    assert gbdt.rf_group_bytes(8, 2 ** 24, 28) > 0.75 * V5E_BYTES \
        > gbdt.rf_group_bytes(4, 2 ** 24, 28) > 0.25 * V5E_BYTES
    with open(os.path.join(REPO, "benchmark/configs/rf-higgs.json")) as f:
        config = json.load(f)
    assert config["lockstep_group_trees"] == gbdt._rf_group_trees(
        config["n_trees"], config["train_rows"], config["input_dim"], v5e)
    assert config["feature_subset_cols"] == gbdt.feature_subset_count(
        config["feature_subset"], config["input_dim"])
    with open(os.path.join(REPO, "benchmark/traffic/jobs-10-trees.json")) as f:
        assert json.load(f)["steps_per_call"] == config["n_trees"]


# --- inputs -------------------------------------------------------------------

def test_a_device_table_gives_the_forest_of_the_same_host_rows(table):
    """(columns, rows) on one device: no fetch, no transpose; the host
    rows go over the rig's eight-device mesh, padded to 3,008, and the
    draw hangs on a row's index, so the forests are equal."""
    bins, y, w, cfg = table
    device = jax.devices()[0]
    placed = [jax.device_put(jnp.asarray(a), device)
              for a in (bins.T, y, w)]
    on_device = gbdt.build_rf(cfg, *placed, 7, "TWOTHIRDS", 1.0, seed=SEED)
    assert _same(on_device, _forest(table))


def test_job_span_says_how_the_forest_was_grown(table, monkeypatch,
                                                tmp_path):
    from shifu_tpu.obs import trace as obs_trace
    from tests.test_train_spans import _profiled_spans
    assert obs_trace.span_registered("train.bag")
    assert "bag" in obs_trace.DEVICE_SCOPES
    assert obs_trace.device_scopes(
        "jit(bags)/bag/vmap(jit(_bits))/threefry2x32") == ("bag",)
    monkeypatch.setattr(gbdt, "_rf_group_trees", lambda *a: 3)
    by_line = _profiled_spans(tmp_path, lambda: _forest(table))
    evs = [e for line in by_line.values() for e in line]
    job = [e for e in evs if e[0] == "shifu:train.job"]
    assert len(job) == 1
    stats = job[0][3]
    assert stats["family"] == "rf" and int(stats["steps"]) == 7
    assert (int(stats["trees"]), int(stats["group_trees"]),
            int(stats["groups"]), int(stats["subset_cols"])) == (7, 3, 3, 6)
    assert float(stats["bag_rate"]) == 1.0
    bags = [e for e in evs if e[0] == "shifu:train.bag"]
    programs = [e for e in evs if e[0] == "shifu:train.program"]
    assert [int(e[3]["trees"]) for e in bags] == [3, 3, 1]
    assert [int(e[3]["steps"]) for e in programs] == [3, 3, 1]
    for bag, program in zip(bags, programs):
        assert job[0][1] <= bag[1] and bag[2] <= program[1], \
            "side by side, a group's draw before its program"


@pytest.mark.parametrize("flags", [{"stratified": True}, {"neg_only": True}])
def test_stratified_and_neg_only_keep_the_host_draw(table, flags):
    """Their instance weights are `trainer.bagging_weights`' (exact
    class counts need the labels on the host), a group at a time; the
    feature subsets are the device's."""
    trees = _forest(table, n_trees=3, **flags)
    assert trees["feature"].shape[0] == 3
    assert np.isfinite(trees["leaf_value"]).all()
    masks = rf_reference.feature_masks(SEED, 3, 9, 6)
    assert rf_reference.forest_counts(trees, masks)[0] == 0


# --- against the plain reference ------------------------------------------------

@pytest.fixture(scope="module")
def rehearsed():
    """The cell's rehearsal: 20,000 seeded rows, depth 4, 4 trees."""
    _, cell, config, traffic, family = harness.open_cell("rf-higgs.train",
                                                         rehearse=True)
    assert family is not None and cell["chips"] == 1
    data = jax.block_until_ready(rf.make_data(config, SEED, 1))
    got = rf.outputs(rf.make_call(config, traffic, data, SEED % (2 ** 31 - 1))())
    return config, traffic, data, got


def _passes(checks):
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks)


def test_build_rf_agrees_with_the_plain_reference(rehearsed):
    config, traffic, data, got = rehearsed
    found = rf.check(config, traffic, data, 0, got, control=True)
    assert [c["name"] for c in found["checks"]] == [
        "split_regret", "gain_gap", "leaf_gap", "mask_violations",
        "twin_trees"]
    assert _passes(found["checks"]), found["checks"]
    assert not _passes(found["control_checks"]), found["control_checks"]


@pytest.mark.parametrize("fault", ["bags_shared", "bag_unweighted",
                                   "mask_ignored", "half_batch",
                                   "answer_altered"])
def test_every_fault_of_the_family_fails(rehearsed, fault):
    config, traffic, data, got = rehearsed
    broken = rf.faults(config, traffic, data, 0, got)[fault]()
    checks = rf.check(config, traffic, data, 0, broken)["checks"]
    assert not _passes(checks), (fault, checks)
    if fault == "mask_ignored":
        assert {c["name"]: c["value"] for c in checks}["mask_violations"] > 0


def test_trees_alike_are_counted_as_twins(rehearsed):
    config, _, _, got = rehearsed
    trees = {k: v.copy() for k, v in got.items() if k != "seed"}
    for k in trees:
        trees[k][2] = trees[k][1]
    masks = np.ones((4, config["input_dim"]), bool)
    assert rf_reference.forest_counts(trees, masks) == (0, 1)
