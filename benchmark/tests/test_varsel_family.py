"""Tests of what the `varsel` family brings to the yardstick: its
configuration beside `nn-higgs`, its work functions, its three readers on
a hand-made trace, its rehearsal, its comparison failing the control and
every fault, and faults planted underneath a run.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_varsel_family.py -q
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

CELL = "se-epsilon.varsel"
MANIFEST = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
FAULTS = ["column_block_dropped", "wiped_to_one", "untrained_params",
          "half_rows", "answer_altered"]
NEW = ["ablate_share", "ablate_roofline", "train_ablate_ms"]


def _config(cell=CELL):
    return harness.find_cell(MANIFEST, cell)[1]


def _passes(checks):
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks)


# --- the configuration and the cell -------------------------------------------

def test_the_quick_train_is_nn_higgs_net_family_at_the_sources_sizes():
    se, nn = _config(), _config("nn-higgs.train")
    same = ["activation", "output_dim", "output_activation", "loss",
            "optimizer", "learning_rate", "adam_beta1", "adam_beta2",
            "weight_init", "bags", "batch", "dtype", "matmul_operand_dtype",
            "control_precision"]
    assert {k: se[k] for k in same} == {k: nn[k] for k in same}
    assert se["family"] == "varsel" and se["reduced"] == []
    assert (se["input_dim"], se["hidden_dims"], se["train_rows"]) == (
        2000, [50], 400_000)
    assert (se["num_train_epochs"], se["valid_set_rate"], se["filter_by"],
            se["filter_num"]) == (100, 0.2, "SE", 200)
    # the quick train's epoch is not judged (`varsel_reference.compare`)
    assert set(se["limits"]) == (set(nn["limits"]) - {"best_epoch_regret"}
                                 | {"delta_gap", "selection_miss"})
    assert se["limits"]["selection_miss"] == 0
    cell, _, traffic = harness.find_cell(MANIFEST, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "jobs-se-50-epochs"
    assert traffic["steps_per_call"] == 50 and traffic["step"] == "epoch"
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1


def test_the_cell_reports_its_three_metrics():
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "train_rows_per_s"
    reported = {m["name"] for m in harness.metrics_of(MANIFEST, "per_layer",
                                                      CELL)}
    assert set(NEW) <= reported
    assert "mlp_kernel_share" not in reported, "no kernel at 2,000 inputs"


def test_work_is_the_quick_train_and_one_ablation():
    work = harness.load("work", "varsel")
    config = _config()
    rows, cols = config["train_rows"], config["input_dim"]
    epoch = work.epoch_work(config)
    assert epoch["bytes"] == 320_000 * cols * 4
    assert epoch["flops"] == 3 * 2 * (cols * 50 + 50) * 320_000
    ablate = work.ablate_work(config)
    assert ablate["bytes"] == rows * cols * 4
    assert ablate["flops"] == 2 * rows * cols * 50 + rows * cols * (
        2 * 50 + 51 + 2 * 50 + 3)
    step = work.step_work(config)
    assert step["bytes"] == pytest.approx(epoch["bytes"]
                                          + ablate["bytes"] / 50)


# --- the readers on a hand-made trace ---------------------------------------------

def _context(host, modules=(), ops=()):
    dev = tr.Device(0, ops=[tr.Event(n, s, e) for n, s, e in ops],
                    modules=[tr.Event(n, s, e) for n, s, e in modules])
    dev.busy = tr.merge((e.start, e.end) for e in dev.ops)
    spans = [tr.Event(n, s, e) for n, s, e in host if n.startswith("bench:")]
    return {"trace": tr.Reduced(10.0, [dev], spans,
                                [tr.Event(n, s, e) for n, s, e in host]),
            "fullest_device": 0, "config": _config(),
            "work": harness.load("work", "varsel"),
            "peak": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


OPS = [("fusion.1", 0.0, 3.0), ("fusion.2", 3.5, 4.0), ("fusion.3", 5.0, 8.0)]
MODULES = [("jit_train_bags_carry(11)", 0.0, 3.0),
           ("jit_shifu_ablate(12)", 3.2, 4.2),
           ("jit_train_bags_carry(11)", 5.0, 8.0)]


def test_ablate_share_and_roofline_read_the_ablations_executions():
    share = harness.load("layer_metrics", "ablate_share")
    roofline = harness.load("layer_metrics", "ablate_roofline")
    # busy 6.5 s, of which 0.5 s inside the ablation program's run
    assert share.read(_context([], MODULES, OPS)) == pytest.approx(
        100 * 0.5 / 6.5)
    least = 400_000 * 2000 * 4 / 819e9     # x read once binds
    assert roofline.read(_context([], MODULES, OPS)) == pytest.approx(
        100 * least / 1.0)
    assert share.read(_context([], MODULES[::2], OPS)) is None
    assert roofline.read(_context([], MODULES[::2], OPS)) is None


def test_train_ablate_ms_reads_the_span_beside_the_job():
    ablate = harness.load("layer_metrics", "train_ablate_ms")
    host = [("bench:window", 0, 10), ("bench:call", 0, 4),
            ("shifu:train.job", 0.1, 3.0), ("shifu:train.ablate", 3.0, 3.8),
            ("bench:call", 4, 8), ("shifu:train.job", 4.1, 7.0),
            ("shifu:train.ablate", 7.0, 7.6)]
    assert ablate.read(_context(host)) == pytest.approx(1e3 * 1.4 / 2)
    no_job = [h for h in host if not h[0].startswith("shifu:")]
    assert ablate.read(_context(no_job)) is None


# --- rehearsal, control and faults --------------------------------------------

def test_rehearsal_runs_the_cell_and_prints_no_device_metric(capsys):
    rc = harness.main(["--workload", CELL, "--seed", str(2 ** 31 + 7),
                       "--seconds", "0.5", "--rehearse"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["metrics"] == {}
    assert line["correct"] is True and line["attempted"] >= 1
    assert line["checks"]["selection_miss"] == {"value": 0.0, "limit": 0}


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


def test_call_k_takes_the_job_seed_plus_k(calibrated):
    family, config, traffic, data, job_seed, first, got = calibrated
    assert (first["seed"], got["seed"]) == (job_seed, job_seed + 1)
    assert not np.array_equal(first["deltas"], got["deltas"])
    assert first["deltas"].shape == got["deltas"].shape
    assert got["deltas"].shape == (config["input_dim"],)
    assert got["selected"].shape == (config["filter_num"],)
    assert got["train_errors"].shape == (traffic["steps_per_call"],)


def test_control_in_bfloat16_is_not_correct(calibrated):
    family, config, traffic, data, job_seed, _, got = calibrated
    found = family.check(config, traffic, data, job_seed, got, control=True)
    assert _passes(found["checks"]), found["checks"]
    assert not _passes(found["control_checks"]), found["control_checks"]


@pytest.mark.parametrize("fault", FAULTS)
def test_every_fault_is_not_correct(calibrated, fault):
    family, config, traffic, data, job_seed, _, got = calibrated
    broken = family.faults(config, traffic, data, job_seed, got)
    assert list(broken) == FAULTS
    checks = family.check(config, traffic, data, job_seed,
                          broken[fault]())["checks"]
    assert not _passes(checks), checks


def _plant(monkeypatch, fault):
    """Break the timed path underneath a run: the program's own ablation
    and its own quick train."""
    from shifu_tpu.processor import sensitivity
    if fault == "column_block_dropped":
        real = sensitivity.shifu_ablate

        def dropped(*a, **k):
            deltas, var = real(*a, **k)
            return deltas.at[-sensitivity.COL_BLOCK:].set(0.0), var
        monkeypatch.setattr(sensitivity, "shifu_ablate", dropped)
    elif fault == "half_rows":
        real = sensitivity.train_nn
        monkeypatch.setattr(sensitivity, "train_nn",
                            lambda conf, x, y, w, **k: real(
                                conf, x[:len(y) // 2], y[:len(y) // 2],
                                w[:len(y) // 2], **k))


@pytest.mark.parametrize("fault", ["column_block_dropped", "half_rows"])
def test_a_run_on_a_broken_program_reports_not_correct(
        fault, monkeypatch, capsys):
    _plant(monkeypatch, fault)
    rc = harness.main(["--workload", CELL, "--seed", "23", "--seconds",
                       "0.2", "--rehearse"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is False, line["checks"]
