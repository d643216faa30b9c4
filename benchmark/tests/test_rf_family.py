"""Tests of what the `rf` family brings to the yardstick: its configuration
beside `gbt-higgs`, its work functions, its reader on a hand-made trace,
its rehearsal, its comparison failing the control and every fault, and
faults planted underneath a run.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_rf_family.py -q
"""

import json
import math
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark import run as harness  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402

CELL = "rf-higgs.train"
MANIFEST = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
FAULTS = ["bags_shared", "bag_unweighted", "mask_ignored", "half_batch",
          "answer_altered"]


def _config(cell=CELL):
    return harness.find_cell(MANIFEST, cell)[1]


def _passes(checks):
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks)


# --- the configuration and the cell -------------------------------------------

def test_the_forest_runs_on_gbt_higgs_table_at_the_sources_widths():
    rf, gbt = _config(), _config("gbt-higgs.train")
    same = ["dataset", "input_dim", "value_bins", "n_bins", "train_rows",
            "valid_rows", "reg_lambda", "min_instances_per_node",
            "min_info_gain", "dtype", "matmul_operand_dtype",
            "control_precision"]
    assert {k: rf[k] for k in same} == {k: gbt[k] for k in same}
    assert rf["family"] == "rf" and rf["reduced"] == ["train_rows"]
    assert (rf["n_trees"], rf["max_depth"], rf["feature_subset"],
            rf["feature_subset_cols"], rf["bagging_rate"]) == (
                10, 10, "TWOTHIRDS", 18, 1.0)
    assert rf["bagging_with_replacement"] is True
    assert set(rf["limits"]) == {"split_regret", "gain_gap", "leaf_gap",
                                 "mask_violations", "twin_trees"}
    assert rf["limits"]["mask_violations"] == rf["limits"]["twin_trees"] == 0
    cell, _, traffic = harness.find_cell(MANIFEST, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "jobs-10-trees"
    assert traffic["steps_per_call"] == rf["n_trees"]
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1


def test_the_cell_reports_what_gbt_higgs_reports_and_the_draw():
    def names(cell):
        return {m["name"] for m in harness.metrics_of(MANIFEST, "per_layer",
                                                      cell)}
    assert names(CELL) == names("gbt-higgs.train") | {"train_bag_ms"}
    bag = {m["name"]: m for m in MANIFEST["per_layer"]}["train_bag_ms"]
    assert bag["workloads"] == [CELL]
    assert bag["layer"] == "trainers, host side"
    assert bag["moves"] == "train_rows_per_s"


def test_work_counts_a_trees_own_columns_and_one_table_read_a_group():
    work = harness.load("work", "rf")
    config = _config()
    rows = config["train_rows"]
    step = work.step_work(config)
    assert step["flops"] == 5.5 * 2 * rows * 18
    assert step["bytes"] == pytest.approx(
        5.5 * rows * (28 / 10 + 12) + 12 * rows)
    call = work.kernel_call_work(config, 1)
    # groups of 4, 4 and 2: three calls a level, ten trees' row state
    assert call["bytes"] == pytest.approx(rows * (28 + 12 * 10 / 3))
    assert call["flops"] == pytest.approx(2 * rows * 18 * 10 / 3)
    # never more than the gbt family charges the same trees one by one
    one = harness.load("work", "gbt").pass_work(config, rows)
    assert call["bytes"] < 4 * one["bytes"] and call["flops"] < 4 * one["flops"]


# --- the reader on a hand-made trace ---------------------------------------------

def test_train_bag_ms_reads_the_span_or_nothing():
    bag = harness.load("layer_metrics", "train_bag_ms")
    host = [("bench:window", 0, 10), ("bench:call", 0, 4),
            ("shifu:train.job", 0.1, 3.9), ("shifu:train.bag", 0.2, 0.3),
            ("shifu:train.program", 0.3, 0.4), ("shifu:train.bag", 2.0, 2.2),
            ("bench:call", 4, 8), ("shifu:train.job", 4.1, 7.9),
            ("shifu:train.bag", 4.2, 4.3)]

    def context(events):
        spans = [tr.Event(n, s, e) for n, s, e in events
                 if n.startswith("bench:")]
        return {"trace": tr.Reduced(10.0, [tr.Device(0)], spans,
                                    [tr.Event(n, s, e) for n, s, e in events])}

    assert bag.read(context(host)) == pytest.approx(1e3 * 0.4 / 2)
    no_job = [h for h in host if not h[0].startswith("shifu:")]
    assert bag.read(context(no_job)) is None
    dropped = [h for h in host if h[0] != "shifu:train.bag"]
    assert bag.read(context(dropped)) == 0.0
    # a draw's span is named, so it is no part of what no span names
    from benchmark import program_spans
    assert program_spans.unnamed_ms(context(host)["trace"]) == pytest.approx(
        program_spans.unnamed_ms(context(dropped)["trace"]) - 1e3 * 0.4 / 2)


# --- rehearsal, control and faults --------------------------------------------

def test_rehearsal_runs_the_cell_and_prints_no_device_metric(capsys):
    rc = harness.main(["--workload", CELL, "--seed", str(2 ** 31 + 7),
                       "--seconds", "0.5", "--rehearse"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["metrics"] == {}
    assert line["correct"] is True and line["attempted"] >= 2
    assert set(line["checks"]) == {"split_regret", "gain_gap", "leaf_gap",
                                   "mask_violations", "twin_trees"}
    assert line["checks"]["mask_violations"] == {"value": 0.0, "limit": 0}
    assert line["checks"]["twin_trees"] == {"value": 0.0, "limit": 0}


@pytest.fixture(scope="module")
def calibrated():
    _, config, traffic = harness.find_cell(MANIFEST, CELL)
    config = {**config, **config["rehearsal"]}
    family = harness.load("families", config["family"])
    seed = 2 ** 31 + 9
    job_seed = seed % (2 ** 31 - 1)
    data = family.make_data(config, seed, 1)
    call = family.make_call(config, traffic, data, job_seed)
    first, got = family.outputs(call()), family.outputs(call())
    return family, config, traffic, data, job_seed, first, got


def test_every_call_draws_a_forest_of_its_own(calibrated):
    family, config, traffic, data, job_seed, first, got = calibrated
    assert (int(first["seed"]), int(got["seed"])) == (job_seed, job_seed + 1)
    assert not np.array_equal(first["feature"], got["feature"])
    assert got["feature"].shape == (config["n_trees"],
                                    2 ** (config["max_depth"] + 1) - 1)


def test_control_in_bfloat16_is_not_correct(calibrated):
    family, config, traffic, data, job_seed, _, got = calibrated
    found = family.check(config, traffic, data, job_seed, got, control=True)
    assert _passes(found["checks"]), found["checks"]
    assert not _passes(found["control_checks"]), found["control_checks"]


def test_a_forest_is_compared_under_the_seed_it_was_drawn_from(calibrated):
    family, config, traffic, data, job_seed, first, got = calibrated
    swapped = {**got, "seed": first["seed"]}
    assert not _passes(family.check(config, traffic, data, job_seed,
                                    swapped)["checks"])


@pytest.mark.parametrize("fault", FAULTS)
def test_every_fault_is_not_correct(calibrated, fault):
    family, config, traffic, data, job_seed, _, got = calibrated
    broken = family.faults(config, traffic, data, job_seed, got)
    assert list(broken) == FAULTS
    checks = family.check(config, traffic, data, job_seed,
                          broken[fault]())["checks"]
    assert not _passes(checks), checks
    from shifu_tpu.models import rf_draw
    assert rf_draw.bags.__name__ == "bags", "the plant was taken out again"


def _plant(monkeypatch, fault):
    """Break the timed path underneath a run: the program's own draw and
    its own entry."""
    import jax.numpy as jnp
    from shifu_tpu.models import gbdt, rf_draw
    if fault == "bags_shared":
        real = rf_draw.bags
        monkeypatch.setattr(rf_draw, "bags", lambda key, ids, *a, **k: real(
            key, np.zeros_like(ids), *a, **k))
    elif fault == "mask_ignored":
        real = rf_draw.masks
        monkeypatch.setattr(rf_draw, "masks", lambda *a, **k: jnp.ones_like(
            real(*a, **k)))
    elif fault == "half_batch":
        real = gbdt.build_rf
        monkeypatch.setattr(
            gbdt, "build_rf", lambda cfg, bins, y, w, *a, **k: real(
                cfg, bins[:, :len(y) // 2], y[:len(y) // 2],
                w[:len(y) // 2], *a, **k))


@pytest.mark.parametrize("fault", ["bags_shared", "mask_ignored",
                                   "half_batch"])
def test_a_run_on_a_broken_program_reports_not_correct(
        fault, monkeypatch, capsys):
    _plant(monkeypatch, fault)
    rc = harness.main(["--workload", CELL, "--seed", "23", "--seconds",
                       "0.2", "--rehearse"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is False, line["checks"]
