"""`gbdt.build_gbt_bagged` as `shifu train` runs it for `train.algorithm:
GBT` with `baggingNum` > 1 and as the cell `gbt-higgs-bagged.train` runs
it, at small sizes on the CPU: the documented draw made again in numpy,
each bag against a sequential `build_gbt` with its drawn weights, groups
sized from bytes that change no tree, validation rows on the device, the
job's spans, the `gbt_bagged` family's plain reference with every fault
failing, and `run_tree` saving one ensemble a bag.
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
from benchmark.families import gbt_bagged  # noqa: E402
from shifu_tpu.models import gbdt, rf_draw  # noqa: E402

SEED = 2 ** 31 + 37          # past 32 signed bits, as the driver's are
V5E_BYTES = 16_909_336_064   # a v5e's `bytes_limit` (PERF.md section 4)


def _case(rng, n=900, c=7, n_bins=16, miss=0.05):
    bins = rng.integers(0, n_bins - 1, size=(n, c)).astype(np.int32)
    bins[rng.random((n, c)) < miss] = n_bins - 1
    y = (bins[:, 0] >= (n_bins - 1) // 2).astype(np.float32)
    flip = rng.random(n) < 0.1
    return bins, np.where(flip, 1 - y, y).astype(np.float32)


def _cfg(loss="squared", depth=3):
    return gbdt.TreeConfig(max_depth=depth, n_bins=16,
                           min_instances_per_node=2, min_info_gain=0.0,
                           reg_lambda=1.0, learning_rate=0.1, loss=loss)


def _parity(a, b):
    """Structure exact; leaf values and gains at float32 rounding (the
    lockstep build stacks per-bag sums XLA may order differently)."""
    for k in ("feature", "bin", "is_leaf", "default_left"):
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                      err_msg=k)
    for k in ("leaf_value", "gain"):
        np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]),
                                   rtol=1e-5, atol=1e-6, err_msg=k)


def _same(a, b):
    return all(np.array_equal(np.asarray(a[k]), np.asarray(b[k])) for k in a)


def _drawn(n_bags, rows, rate, with_replacement):
    """Every bag's instance weights as the program draws them."""
    edges = rf_draw.bag_thresholds(rate, with_replacement)
    return np.asarray(rf_draw.bags(jax.random.key(SEED),
                                   np.arange(n_bags, dtype=np.int32), rows,
                                   edges))


# --- the draw ------------------------------------------------------------------

def _numpy_redraw(bag, rows, rate, with_replacement):
    """The rule of `rf_draw`'s docstring in numpy, from jax's public bits:
    thresholds in float64, a count of the thresholds at or under a row's
    32 bits."""
    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(SEED), bag),
                             0)
    u = np.asarray(jax.random.bits(key, (rows,), jnp.uint32)).astype(np.int64)
    if with_replacement:
        edges, pmf, cdf, k = [], math.exp(-rate), 0.0, 0
        while math.floor((cdf + pmf) * 2.0 ** 32) < 2 ** 32 - 1:
            cdf += pmf
            edges.append(math.floor(cdf * 2.0 ** 32))
            k += 1
            pmf *= rate / k
    else:
        edges = [min(math.floor(max(1.0 - rate, 0.0) * 2.0 ** 32),
                     2 ** 32 - 1)]
    iw = (u[:, None] >= np.asarray(edges, np.int64)[None, :]).sum(axis=1)
    return np.where(iw.sum() == 0, 1, iw).astype(np.float32)


@pytest.mark.parametrize("rate,with_replacement", [
    (1.0, True), (2.5, True), (0.5, False), (1.0, False), (0.1, False)])
def test_the_device_draw_is_the_documented_rule(rate, with_replacement):
    """Poisson(rate) with replacement, Bernoulli(rate) without: the
    jitted, vmapped draw equals a numpy re-draw bag by bag."""
    n_bags, rows = 4, 5003
    iw = _drawn(n_bags, rows, rate, with_replacement)
    for b in range(n_bags):
        np.testing.assert_array_equal(
            iw[b], _numpy_redraw(b, rows, rate, with_replacement))
    if with_replacement:
        assert abs(iw.mean() - rate) < 0.1 and abs(iw.var() - rate) < 0.2
    else:
        assert set(np.unique(iw)) <= {0.0, 1.0}
        assert abs(iw.mean() - min(rate, 1.0)) < 0.03
    # the family's reference draws what a job draws: without replacement
    # at rate 1 or more a job's bags are Poisson(rate)
    from benchmark.families import gbt_bagged_reference as ref
    job = _drawn(2, rows, rate, with_replacement or rate >= 1.0)
    np.testing.assert_array_equal(
        job[1], np.asarray(ref.bag_weights(SEED, 1, rows, rate,
                                           with_replacement)))


# --- each bag is a sequential build -------------------------------------------

@pytest.mark.parametrize("case", ["validation_early_stop", "scan_no_val",
                                  "bernoulli_validation"])
def test_each_bag_equals_a_sequential_build_gbt(rng, monkeypatch, case):
    """Each bag of the lockstep build equals `build_gbt` on its drawn
    weights: with validation rows and per-bag early stop (bags may stop
    at different rounds and keep what their own loop would have), and
    without, where the rounds scan on the device in
    SHIFU_TPU_GBT_SCAN_GROUP-sized pieces."""
    bins, y = _case(rng)
    w = rng.uniform(0.5, 2.0, len(y)).astype(np.float32)
    cfg = _cfg()
    n_bags, rounds = 3, 5
    rate, repl = (0.6, False) if case.startswith("bernoulli") else (1.0, True)
    val, stop = None, 0
    if case == "scan_no_val":
        monkeypatch.setenv("SHIFU_TPU_GBT_SCAN_GROUP", "2")
    else:
        val, stop = _case(rng, n=300), 2
    out = gbdt.build_gbt_bagged(cfg, bins, y, w, rounds, n_bags, rate, repl,
                                SEED, val_data=val, early_stop_window=stop)
    iw = _drawn(n_bags, len(y), rate, repl)
    for b in range(n_bags):
        seq_t, seq_e = gbdt.build_gbt(cfg, bins, y, w * iw[b], rounds,
                                      val_data=val, early_stop_window=stop)
        lk_t, lk_e = out[b]
        assert seq_t["feature"].shape == lk_t["feature"].shape
        _parity(seq_t, lk_t)
        assert len(seq_e) == len(lk_e) == (0 if val is None
                                           else seq_t["feature"].shape[0])
        np.testing.assert_allclose(seq_e, lk_e, rtol=1e-6, atol=1e-7)
    assert not np.array_equal(out[0][0]["leaf_value"],
                              out[1][0]["leaf_value"])


# --- groups ---------------------------------------------------------------------

def _device_job(rng, n=1200, c=6, n_val=400):
    """Training and validation rows laid (columns, rows) on one device."""
    bins, y = _case(rng, n=n, c=c)
    vb, vy = _case(rng, n=n_val, c=c)
    dev = jax.devices()[0]
    put = lambda a: jax.device_put(jnp.asarray(a), dev)  # noqa: E731
    return (put(bins.T), put(y), put(np.ones_like(y)), put(vb.T), put(vy),
            (bins, y, vb, vy))


def test_a_small_group_gives_the_one_group_trees_bit_for_bit(rng,
                                                             monkeypatch):
    """A device that reports memory for two bags at a time (the rule's
    own argument, `_bytes_limit`) grows five bags as 2 + 2 + 1; bags are
    independent and bag b's draw hangs on b alone, so every tree and every
    error equals the one-group job's."""
    binsT, y, w, vbT, vy, _ = _device_job(rng)
    cfg = _cfg("log")

    def job():
        return gbdt.build_gbt_bagged(cfg, binsT, y, w, 3, 5, 1.0, True,
                                     SEED, val_data=(vbT, vy))

    whole = job()
    c, r, v = binsT.shape[0], binsT.shape[1], vbT.shape[1]
    need = [gbdt.rf_group_bytes(g, r, c, n_bags=5,
                                val_rows=v) for g in (2, 4)]
    monkeypatch.setattr(gbdt, "_bytes_limit", lambda device: sum(need) / 2
                        / gbdt._RF_MEMORY_SHARE)
    assert gbdt._rf_group_trees(5, r, c, None, boosted=True,
                                val_rows=v) == 2
    grouped = job()
    for (a, ea), (b, eb) in zip(whole, grouped):
        assert _same(a, b)
        assert ea == eb


@pytest.mark.parametrize("group", [1, 2, 4, 5])
def test_a_tree_at_a_time_update_gives_the_vmapped_lookups_bits(group):
    """A round's boosting update looks each tree's leaf values up on its
    own (R,) node vector (`_group_leaf_values`): bit for bit what `vmap`
    of `_lookup` over the group gives, -0.0 kept, for groups of 1, 2, 4
    and 5 bags, with rows parked at -1 and at ids outside the 511-slot
    table reading 0."""
    rng = np.random.default_rng(4200 + group)
    n_nodes, rows = 511, 1000
    values = rng.standard_normal((group, n_nodes)).astype(np.float32)
    values[rng.random((group, n_nodes)) < 0.05] = -0.0
    node = rng.integers(0, n_nodes, (group, rows)).astype(np.int32)
    parked = rng.random((group, rows))
    node[parked < 0.05] = -1
    node[parked > 0.95] = n_nodes + rng.integers(0, 600, (group, rows))[
        parked > 0.95]
    values, node = jnp.asarray(values), jnp.asarray(node)
    got = np.asarray(jax.jit(gbdt._group_leaf_values)(values, node))
    want = np.asarray(jax.jit(jax.vmap(gbdt._lookup))(values, node))
    assert got.shape == (group, rows)
    np.testing.assert_array_equal(got.view(np.int32), want.view(np.int32))
    outside = (np.asarray(node) < 0) | (np.asarray(node) >= n_nodes)
    assert outside.any() and not got[outside].view(np.int32).any()
    assert (got.view(np.int32) == np.float32(-0.0).view(np.int32)).any()


def _per_tree_pass(binsT, slot_T, grad_T, hess_T, n_level_nodes, n_bins):
    """A lockstep level's histograms a bag at a time: each bag's own
    (R,) kernel call (interpret mode), stacked."""
    from shifu_tpu.ops.pallas_hist import level_histograms_pallas
    each = [level_histograms_pallas(binsT, slot_T[t], grad_T[t], hess_T[t],
                                    n_level_nodes, n_bins, interpret=True)
            for t in range(slot_T.shape[0])]
    return tuple(jnp.stack(a) for a in zip(*each))


@pytest.mark.parametrize("placement", ["one_device", "mesh"])
def test_grouped_kernel_gives_the_per_tree_kernels_bags(monkeypatch,
                                                        placement):
    """The histogram kernel forced on (interpret mode): five bags whose
    levels take a group's row state in one grouped pass grow, bit for
    bit, the trees and validation errors of bags whose levels take each
    bag's own kernel call; on one device, and on the rig's eight-device
    data mesh (host rows placed by row, `_forest_level_histograms`'
    `shard_map` and its one `psum`)."""
    binsT, y, w, vbT, vy, (bins, y_h, vb, vy_h) = _device_job(
        np.random.default_rng(41))
    if placement == "mesh":
        binsT, y, w = bins, y_h, np.ones_like(y_h)
        val = (vb, vy_h)
    else:
        val = (vbT, vy)
    monkeypatch.setenv("SHIFU_TPU_HIST", "pallas")

    def job():
        jax.clear_caches()       # the route is read while a program traces
        return gbdt.build_gbt_bagged(_cfg("log"), binsT, y, w, 2, 5, 1.0,
                                     True, SEED, val_data=val)

    try:
        grouped = job()
        monkeypatch.setattr(gbdt, "_local_forest_histograms", _per_tree_pass)
        per_tree = job()
    finally:
        jax.clear_caches()
    for (a, ea), (b, eb) in zip(grouped, per_tree):
        assert _same(a, b)
        assert ea == eb
    assert not _same(grouped[0][0], grouped[1][0])


def test_group_size_is_read_from_bytes():
    """On a 16 GB chip five bags of 2^24 rows of 28 columns with 2^22
    validation rows take 4 bags at a time (the five would pad to a tile
    of 8); the job's held state counts every bag, and the forest's sizing
    is as it was. The benchmark's configuration records what its rows
    give."""
    class Chip:
        def memory_stats(self):
            return {"bytes_limit": V5E_BYTES}

    room = gbdt._RF_MEMORY_SHARE * V5E_BYTES
    assert gbdt._rf_group_trees(5, 2 ** 24, 28, Chip(), boosted=True,
                                val_rows=2 ** 22) == 4
    assert gbdt.rf_group_bytes(5, 2 ** 24, 28, n_bags=5,
                               val_rows=2 ** 22) > room \
        > gbdt.rf_group_bytes(4, 2 ** 24, 28, n_bags=5,
                              val_rows=2 ** 22)
    # what a boosted group holds beyond a forest group of as many trees:
    # the predictions and weights of bags 4 + 1 (tiles 4 + 1), the
    # validation table and five raw scores
    assert gbdt.rf_group_bytes(4, 2 ** 24, 28, n_bags=5,
                               val_rows=2 ** 22) \
        - gbdt.rf_group_bytes(4, 2 ** 24, 28) \
        == 8 * 5 * 2 ** 24 + 2 ** 22 * (4 * 34 + 4 * 5)
    assert gbdt._rf_group_trees(10, 2 ** 24, 28, Chip()) == 4
    with open(os.path.join(REPO,
                           "benchmark/configs/gbt-higgs-bagged.json")) as f:
        config = json.load(f)
    assert config["lockstep_group_bags"] == gbdt._rf_group_trees(
        config["n_bags"], config["train_rows"], config["input_dim"], Chip(),
        boosted=True, val_rows=config["valid_rows"])


# --- validation rows --------------------------------------------------------------

@pytest.mark.parametrize("builder", ["build_gbt", "build_gbt_bagged"])
def test_device_validation_rows_give_the_host_rows_errors(rng, builder):
    """(columns, rows) validation bins on the device, taken where they
    lie (nothing fetched or transposed), against the same rows from the
    host, transposed and placed by the builder: the same errors, in both
    builders (`tree_validation.place`)."""
    binsT, y, w, vbT, vy, (bins, y_h, vb, vy_h) = _device_job(rng)
    cfg = _cfg("log")

    def errors(val):
        if builder == "build_gbt":
            return [gbdt.build_gbt(cfg, binsT, y, w, 3, val_data=val)[1]]
        return [e for _, e in gbdt.build_gbt_bagged(
            cfg, binsT, y, w, 3, 3, 1.0, True, SEED, val_data=val)]

    on_device, on_host = errors((vbT, vy)), errors((vb, vy_h))
    assert all(len(e) == 3 for e in on_device)
    np.testing.assert_allclose(on_device, on_host, rtol=1e-6)


@pytest.mark.parametrize("route,shared", [("xla", 0), ("pallas", 48)])
def test_the_job_span_and_its_phases(rng, monkeypatch, request, tmp_path,
                                     route, shared):
    """`shifu:train.job` says how the bags were grown (bags, groups,
    group size, validation rows), how many leaf lookups ran a tree at a
    time (each round's update and walk of every bag) and how many of its
    tree-levels came from a histogram pass shared with another bag: 3
    rounds of groups of 2, 2 and 1 at depth 3, where on the kernel's
    route each group of two takes both bags a pass at all four levels and
    on the scatter's none does; a group's draw is one `train.bag`, a
    round one `train.validate` after its groups' programs."""
    from shifu_tpu.obs import trace as obs_trace
    from tests.test_train_spans import _profiled_spans
    monkeypatch.setenv("SHIFU_TPU_HIST", route)
    jax.clear_caches()           # the route is read while a program traces
    request.addfinalizer(jax.clear_caches)
    assert obs_trace.span_registered("train.validate")
    binsT, y, w, vbT, vy, _ = _device_job(rng)
    monkeypatch.setattr(gbdt, "_rf_group_trees", lambda *a, **k: 2)
    by_line = _profiled_spans(tmp_path, lambda: gbdt.build_gbt_bagged(
        _cfg("log"), binsT, y, w, 3, 5, 1.0, True, SEED,
        val_data=(vbT, vy)))
    evs = [e for line in by_line.values() for e in line]
    job = [e for e in evs if e[0] == "shifu:train.job"]
    assert len(job) == 1
    stats = job[0][3]
    assert (int(stats["bags"]), int(stats["groups"]),
            int(stats["group_trees"]), int(stats["val_rows"]),
            int(stats["steps"])) == (5, 3, 2, 400, 3)
    assert (int(stats["hist_tree_levels"]),
            int(stats["hist_shared_levels"])) == (3 * 5 * 4, shared)
    # each round's update and walk of every bag, a tree at a time
    assert int(stats["leaf_lookups_per_tree"]) == 3 * 5 * 2
    bags = [e for e in evs if e[0] == "shifu:train.bag"]
    assert [int(e[3]["trees"]) for e in bags] == [2, 2, 1]
    validate = sorted(e for e in evs if e[0] == "shifu:train.validate")
    programs = sorted(e for e in evs if e[0] == "shifu:train.program")
    assert len(validate) == 3 and len(programs) == 9
    for t, span in enumerate(validate):
        assert all(p[2] <= span[1] for p in programs[3 * t:3 * t + 3])


# --- against the plain reference ------------------------------------------------

@pytest.fixture(scope="module")
def rehearsed():
    """The cell's rehearsal: 20,000 seeded rows, 5,000 validation rows,
    depth 4, five bags, two rounds."""
    _, cell, config, traffic, family = harness.open_cell(
        "gbt-higgs-bagged.train", rehearse=True)
    assert family is not None and cell["chips"] == 1
    data = jax.block_until_ready(gbt_bagged.make_data(config, SEED, 1))
    got = gbt_bagged.outputs(gbt_bagged.make_call(
        config, traffic, data, SEED % (2 ** 31 - 1))())
    return config, traffic, data, got


def _passes(checks):
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks)


def test_build_gbt_bagged_agrees_with_the_plain_reference(rehearsed):
    config, traffic, data, got = rehearsed
    assert got["feature"].shape == (5, 2, 2 ** (config["max_depth"] + 1) - 1)
    assert got["val_error"].shape == (5, 2)
    found = gbt_bagged.check(config, traffic, data, 0, got, control=True)
    assert [c["name"] for c in found["checks"]] == [
        "split_regret", "gain_gap", "leaf_gap", "val_gap", "twin_bags"]
    assert _passes(found["checks"]), found["checks"]
    assert not _passes(found["control_checks"]), found["control_checks"]


@pytest.mark.parametrize("fault", ["bags_shared", "bag_unweighted",
                                   "val_stale", "half_batch",
                                   "answer_altered"])
def test_every_fault_of_the_family_fails(rehearsed, fault):
    config, traffic, data, got = rehearsed
    broken = gbt_bagged.faults(config, traffic, data, 0, got)[fault]()
    checks = gbt_bagged.check(config, traffic, data, 0, broken)["checks"]
    assert not _passes(checks), (fault, checks)
    assert rf_draw.bags.__name__ == "bags", "the plant was taken out again"


# --- through `shifu train` --------------------------------------------------------

def test_run_tree_saves_one_ensemble_a_bag(tmp_path, rng, monkeypatch):
    """`shifu train` for GBT with baggingNum 3 and a validation share
    goes through `build_gbt_bagged` (bags drawn on the device, no host
    weights) and saves three different ensembles."""
    from tests.synth import make_model_set
    from shifu_tpu.models.spec import load_model
    from shifu_tpu.processor import (init as init_proc, norm as norm_proc,
                                     stats as stats_proc, train as train_proc)
    from shifu_tpu.processor.base import ProcessorContext
    from shifu_tpu.train import trainer
    root = make_model_set(tmp_path, rng, n_rows=1200, algorithm="GBT",
                          train_params={"TreeNum": 3, "MaxDepth": 3,
                                        "LearningRate": 0.3, "Loss": "log"})
    path = os.path.join(root, "ModelConfig.json")
    with open(path) as f:
        mc = json.load(f)
    mc["train"].update(baggingNum=3, baggingWithReplacement=True)
    with open(path, "w") as f:
        json.dump(mc, f)
    for proc in (init_proc, stats_proc, norm_proc):
        proc.run(ProcessorContext.load(root))
    calls = []
    real = gbdt.build_gbt_bagged
    monkeypatch.setattr(gbdt, "build_gbt_bagged",
                        lambda *a, **k: calls.append(k) or real(*a, **k))
    monkeypatch.setattr(trainer, "bagging_weights", None)  # never called
    ctx = ProcessorContext.load(root)
    train_proc.run(ctx)
    assert len(calls) == 1 and calls[0]["val_data"] is not None
    saved = [load_model(ctx.path_finder.model_path(b, "gbt"))[2]["trees"]
             for b in range(3)]
    assert all(t["feature"].shape[0] == 3 for t in saved)
    for a in range(3):
        for b in range(a + 1, 3):
            assert not np.array_equal(saved[a]["leaf_value"],
                                      saved[b]["leaf_value"])
