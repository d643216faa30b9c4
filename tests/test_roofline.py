"""The roofline block: `profiling.roofline` and the analytic row-cost
models behind it, and the one place the program writes it — the NN
train step's `steps.jsonl` record (`processor/train.py`)."""

import json
import os

import pytest

from shifu_tpu import profiling


def test_train_step_record_carries_roofline(model_set):
    """`shifu train` (NN) attaches a `roofline` block of EXACTLY
    `profiling.ROOFLINE_FIELDS` to its steps.jsonl record (the schema
    tools/check_steps_schema.py pins the README to). The run is on a
    CPU, which has no entry in the peaks table: the utilization fields
    are null, never a TPU's numbers."""
    from shifu_tpu.cli import main as cli_main
    for step in ("init", "stats", "norm", "train"):
        assert cli_main(["--dir", model_set, step]) == 0
    with open(os.path.join(model_set, "tmp", "metrics",
                           "steps.jsonl")) as f:
        by_step = {r["step"]: r for r in map(json.loads, f)}
    assert "roofline" not in by_step["norm"]
    roof = by_step["train"]["roofline"]
    assert set(roof) == set(profiling.ROOFLINE_FIELDS)
    assert roof["family"] == "NN"
    assert roof["compute_dtype"] == "float32"
    assert roof["bound"] is None and roof["mxu_util"] is None
    assert roof["hbm_util"] is None and roof["ridge_intensity"] is None
    # the measured rows/s reconciles with the derived rates
    assert roof["rows_per_s"] > 0
    assert roof["flops_per_s"] == pytest.approx(
        roof["flops_per_row"] * roof["rows_per_s"], rel=1e-6)
    assert roof["bytes_per_s"] == pytest.approx(
        roof["bytes_per_row"] * roof["rows_per_s"], rel=1e-6)


def test_row_cost_models_closed_form():
    """Analytic per-row costs for known specs, by hand: the roofline's
    inputs must be auditable numbers, not plausible-looking ones."""
    # MLP 10 -> 20 -> 5 -> 1: matmul FLOPs 2*(200+100+5) = 610, x3 for
    # a train step; activation bytes 2*4B*(10+20+5+1), x2 backward
    flops, bytes_ = profiling.mlp_row_costs(10, (20, 5), 1)
    assert flops == 3 * 610
    assert bytes_ == 2 * 4 * 36 * 2
    # inference, bf16: single forward pass, half the bytes
    flops_i, bytes_i = profiling.mlp_row_costs(10, (20, 5), 1,
                                               train=False, dtype_bytes=2)
    assert flops_i == 610
    assert bytes_i == 2 * 2 * 36
    # tree level building with sibling subtraction: depth 3, 8 cols,
    # 16 bins -> 2*2*(1 + 1 + 2)*8*16 FLOPs, 3 levels re-reading the
    # int32 bin row + grad/hess
    tf, tb = profiling.tree_row_costs(8, 16, 3)
    assert tf == 2 * 2 * (1 + 1 + 2) * 8 * 16
    assert tb == 3 * (4 * 8 + 8)


def test_roofline_math_known_values():
    """roofline() arithmetic on hand-checkable numbers (fields round to
    4 decimals, so explicit peaks keep the expectations exact)."""
    roof = profiling.roofline("NN", 1830.0, 576.0, 1e6,
                              peak_flops=1e12, peak_bytes_per_s=1e10)
    assert roof["flops_per_s"] == pytest.approx(1.83e9)
    assert roof["bytes_per_s"] == pytest.approx(5.76e8)
    assert roof["arith_intensity"] == round(1830 / 576, 4)
    assert roof["ridge_intensity"] == 100.0
    assert roof["mxu_util"] == round(1.83e9 / 1e12, 4)
    assert roof["hbm_util"] == round(5.76e8 / 1e10, 4)
    # AI (~3.2) far below the ridge (100) -> memory bound
    assert roof["bound"] == "memory"
    # peaks come from the one table keyed by device_kind: a v5e run
    # is held to its published 197 TFLOP/s / 819 GB/s whatever the
    # compute dtype (the MXU has one published peak) ...
    v5e = profiling.roofline("NN", 1830.0, 576.0, 1e9,
                             compute_dtype="bfloat16",
                             device_kind="TPU v5 lite")
    assert v5e["compute_dtype"] == "bfloat16"
    assert v5e["mxu_util"] == round(1.83e12 / 197e12, 4)
    assert v5e["hbm_util"] == round(5.76e11 / 819e9, 4)
    assert v5e["ridge_intensity"] == round(197e12 / 819e9, 4)
    assert v5e["bound"] == "memory"
    # ... and a device that is not in the table has no roofline at all
    for kind in ("cpu", "TPU v99"):
        none = profiling.roofline("NN", 1830.0, 576.0, 1e9,
                                  device_kind=kind)
        assert set(none) == set(profiling.ROOFLINE_FIELDS)
        assert none["flops_per_s"] == pytest.approx(1.83e12)
        assert [none[k] for k in ("ridge_intensity", "mxu_util",
                                  "hbm_util", "bound")] == [None] * 4
