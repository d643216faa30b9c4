"""Smoke-test the bench.py task functions at tiny shapes on CPU.

Round-2 advisor finding: bench.task_hist silently drifted out of sync
with _level_histograms' transposed (C, R) API and the orchestrator
swallowed the shape error into diagnostics — the advertised evidence
never got measured. These tests call the task functions directly (the
same code the TPU bench runs, shapes patched down) so any API drift
fails the suite loudly instead of failing silently at capture time.
"""

import json

import pytest

import bench


@pytest.fixture(autouse=True)
def _tolerant_delta_timing(monkeypatch):
    # a loaded CI host can invert the two-length delta timing for real
    # (short run descheduled behind a concurrent suite) — give the
    # smoke runs more re-measures than the TPU default of 2
    monkeypatch.setenv("SHIFU_TPU_BENCH_ATTEMPTS", "5")


def _patch_small(monkeypatch):
    monkeypatch.setattr(bench, "N_ROWS", 20_000)
    monkeypatch.setattr(bench, "N_FEATURES", 16)
    monkeypatch.setattr(bench, "HIDDEN", 16)
    monkeypatch.setattr(bench, "BENCH_EPOCHS_SHORT", 2)
    monkeypatch.setattr(bench, "BENCH_EPOCHS", 40)
    monkeypatch.setattr(bench, "HIST_ROWS", 5_000)
    monkeypatch.setattr(bench, "HIST_COLS", 8)
    monkeypatch.setattr(bench, "HIST_BINS", 8)
    monkeypatch.setattr(bench, "HIST_SLOTS", 8)
    monkeypatch.setattr(bench, "HIST_REPS", 1)
    monkeypatch.setattr(bench, "GBT_ROWS", 20_000)
    monkeypatch.setattr(bench, "GBT_COLS", 8)
    monkeypatch.setattr(bench, "GBT_TREES", 3)
    monkeypatch.setattr(bench, "GBT_DEPTH", 3)


def _last_json(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def test_task_nn(monkeypatch, capsys):
    _patch_small(monkeypatch)
    bench.task_nn()  # asserts AUC > 0.75 internally
    rec = _last_json(capsys)
    assert rec["row_epochs_per_sec"] > 0
    assert rec["auc"] > 0.75


@pytest.mark.parametrize("mode", ["xla", "pallas"])
def test_task_hist(monkeypatch, capsys, mode):
    _patch_small(monkeypatch)
    monkeypatch.setenv("SHIFU_TPU_HIST", mode)
    bench.task_hist(mode)
    rec = _last_json(capsys)
    assert rec["cells_per_sec"] > 0
    assert rec["checksum"] > 0


def test_hist_modes_agree(monkeypatch, capsys):
    """XLA scatter and Pallas (interpret) kernels must produce the same
    histogram — the checksum printed by each task is comparable."""
    _patch_small(monkeypatch)
    sums = {}
    for mode in ("xla", "pallas"):
        monkeypatch.setenv("SHIFU_TPU_HIST", mode)
        bench.task_hist(mode)
        sums[mode] = _last_json(capsys)["checksum"]
    assert sums["xla"] == pytest.approx(sums["pallas"], rel=1e-5)


def test_task_gbt(monkeypatch, capsys):
    _patch_small(monkeypatch)
    bench.task_gbt()
    rec = _last_json(capsys)
    assert rec["row_trees_per_sec"] > 0
    assert rec["auc"] > 0.6


def test_task_varsel(monkeypatch, capsys):
    """LR + SE-sensitivity ladder step at toy shape: the planted
    column importances must be recovered through the real trainer +
    ablation kernel (uneven trailing block included: 50k % 20k != 0)."""
    monkeypatch.setattr(bench, "VARSEL_ROWS", 50_000)
    monkeypatch.setattr(bench, "VARSEL_COLS", 8)
    monkeypatch.setattr(bench, "VARSEL_BLOCK", 20_000)
    monkeypatch.setattr(bench, "VARSEL_EPOCHS_SHORT", 2)
    monkeypatch.setattr(bench, "VARSEL_EPOCHS_LONG", 40)
    bench.task_varsel()  # gates AUC > 0.75 and spearman > 0.9 itself
    rec = _last_json(capsys)
    assert rec["lr_row_epochs_per_sec"] > 0
    assert rec["sens_col_rows_per_sec"] > 0


def test_task_nn_wide(monkeypatch, capsys):
    monkeypatch.setattr(bench, "WIDE_ROWS", 4_000)
    monkeypatch.setattr(bench, "WIDE_FEATURES", 24)
    monkeypatch.setattr(bench, "WIDE_HIDDEN", (16, 8))
    monkeypatch.setattr(bench, "WIDE_EPOCHS_SHORT", 2)
    monkeypatch.setattr(bench, "WIDE_EPOCHS_LONG", 40)
    bench.task_nn_wide()
    rec = _last_json(capsys)
    assert rec["row_epochs_per_sec"] > 0
    assert rec["achieved_tflops"] > 0
    assert rec["wall_long_s"] >= 0


def test_task_wdl(monkeypatch, capsys):
    monkeypatch.setattr(bench, "WDL_ROWS", 6_000)
    monkeypatch.setattr(bench, "WDL_DENSE", 5)
    monkeypatch.setattr(bench, "WDL_CAT", 3)
    monkeypatch.setattr(bench, "WDL_VOCAB", 50)
    monkeypatch.setattr(bench, "WDL_EMBED", 4)
    monkeypatch.setattr(bench, "WDL_HIDDEN", (8,))
    monkeypatch.setattr(bench, "WDL_EPOCHS_SHORT", 2)
    monkeypatch.setattr(bench, "WDL_EPOCHS_LONG", 30)
    bench.task_wdl()
    rec = _last_json(capsys)
    assert rec["row_epochs_per_sec"] > 0
    assert rec["auc"] > 0.7


def test_task_gbt_small(monkeypatch, capsys):
    monkeypatch.setattr(bench, "GBT_COLS", 8)
    bench.task_gbt(rows=20_000, trees=3)
    rec = _last_json(capsys)
    assert rec["rows"] == 20_000 and rec["trees"] == 3
    assert rec["row_trees_per_sec"] > 0


def _run_main(monkeypatch, capsys, results):
    """Drive bench.main() with stubbed backend + task results; returns
    the headline JSON record."""
    import sys
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    monkeypatch.setattr(
        bench, "_resolve_backend",
        lambda d: ("tpu", {}, {"timeout_s": 60, "attempts": [
            {"attempt": 1, "wall_s": 0.1, "ok": True,
             "backend": "tpu"}]}))
    monkeypatch.setattr(
        bench, "_run_or_reuse",
        lambda task, backend, diags, env_extra, timeout=1200:
        (results.get(task), None if task in results else "stubbed out"))
    # the real cpu_denom run is a ~20-minute full-shape CPU measure
    monkeypatch.setattr(
        bench, "_run_cpu_denom",
        lambda res, diags: res.update(
            {"cpu_denom": results["cpu_denom"]})
        if "cpu_denom" in results else None)
    bench.main()
    return _last_json(capsys)


def test_task_rf(monkeypatch, capsys):
    """RF at-scale ladder task at toy shape: lockstep vmapped forest
    with on-device Poisson bagging."""
    monkeypatch.setattr(bench, "RF_ROWS", 20_000)
    monkeypatch.setattr(bench, "RF_TREES", 4)
    monkeypatch.setattr(bench, "GBT_COLS", 8)
    bench.task_rf()
    rec = _last_json(capsys)
    assert rec["row_trees_per_sec"] > 0
    assert rec["trees"] == 4
    assert rec["auc"] > 0.6


def test_task_nn_wide_bf16(monkeypatch, capsys):
    """bf16 mixed-precision variant of the wide utilization task: the
    model still learns and the record is labeled."""
    monkeypatch.setattr(bench, "WIDE_ROWS", 4_000)
    monkeypatch.setattr(bench, "WIDE_FEATURES", 24)
    monkeypatch.setattr(bench, "WIDE_HIDDEN", (16, 8))
    monkeypatch.setattr(bench, "WIDE_EPOCHS_SHORT", 2)
    monkeypatch.setattr(bench, "WIDE_EPOCHS_LONG", 40)
    bench.task_nn_wide("bfloat16")
    rec = _last_json(capsys)
    assert rec["compute"] == "bfloat16"
    assert rec["row_epochs_per_sec"] > 0


def test_task_pipeline(monkeypatch, capsys, tmp_path):
    """The CLI product-path task drives the real init→stats→norm→
    train→eval surface twice (sequential walk, then the DAG scheduler)
    and records per-phase wall-clocks plus the scheduler comparison."""
    monkeypatch.setattr(bench, "PIPE_DIR", str(tmp_path / "pipe"))
    monkeypatch.setattr(bench, "PIPE_ROWS", 4_000)
    monkeypatch.setattr(bench, "PIPE_EPOCHS", 5)
    # single-model / single-eval keeps the smoke test small; the full
    # NN+GBT+WDL fan-out is covered by the real bench run and
    # tests/test_pipeline_dag.py
    monkeypatch.setattr(bench, "PIPE_ALGS", ("NN",))
    monkeypatch.setattr(bench, "PIPE_EVALS", ("Eval1",))
    bench.task_pipeline()
    rec = _last_json(capsys)
    # a single-model run keeps the plain "train" node name (no fan-out
    # clone); eval nodes are always per-eval-set
    assert set(rec["phases"]) == {"init", "stats", "norm", "train",
                                  "eval.Eval1"}
    assert all(v >= 0 for v in rec["phases"].values())
    assert rec["auc"] > 0.75
    assert rec["rows"] == 4_000
    assert rec["bitwise_identical"] is True
    assert rec["dag_speedup"] > 0 and rec["dag_workers"] == 1
    assert rec["fanout_cache_misses"] == 0


def test_headline_carries_cpu_denominator(monkeypatch, tmp_path, capsys):
    """The measured same-host denominator lands in extra with the
    TPU:CPU ratio for every task that has both sides."""
    monkeypatch.setattr(bench, "BENCH_LOCAL", str(tmp_path / "b.jsonl"))
    rec = _run_main(monkeypatch, capsys, {
        "nn_wide": {"row_epochs_per_sec": 4.0e5, "auc": 0.9,
                    "wall_s": 2.0, "achieved_tflops": 50.0,
                    "mxu_util": 0.12, "hbm_util_est": 0.3,
                    "hbm_gbps_est": 250.0},
        "cpu_denom": {"nn_wide_row_epochs_per_sec": 1.0e4,
                      "gbt_row_trees_per_sec": 1.0e5},
    })
    assert rec["extra"]["cpu_denominator"][
        "nn_wide_row_epochs_per_sec"] == 1.0e4
    assert rec["extra"]["nn_wide_vs_cpu_host_measured"] == 40.0
    assert "MEASURED same-host" in rec["baseline"]


def test_headline_prefers_wide_and_labels_baseline(monkeypatch, tmp_path,
                                                   capsys):
    """VERDICT r3 next #9: the wide (utilization) shape is the headline
    when captured, and the record self-describes its denominator."""
    monkeypatch.setattr(bench, "BENCH_LOCAL", str(tmp_path / "b.jsonl"))
    rec = _run_main(monkeypatch, capsys, {
        "nn": {"row_epochs_per_sec": 3.0e6, "auc": 0.97, "wall_s": 1.0,
               "mxu_util_est": 1e-4},
        "nn_wide": {"row_epochs_per_sec": 4.0e5, "auc": 0.9,
                    "wall_s": 2.0, "achieved_tflops": 50.0,
                    "mxu_util": 0.12, "hbm_util_est": 0.3,
                    "hbm_gbps_est": 250.0},
    })
    assert rec["metric"] == "nn_wide_train_throughput"
    assert rec["value"] == 0.4
    assert "denominator = ESTIMATED" in rec["baseline"]
    assert rec["extra"]["nn_wide_mxu_util"] == 0.12
    # workers-replaced scales with FLOPs/row: 4e5 rows/s at the wide
    # shape is far more work than the flagship baseline shape
    wide_worker = bench.REFERENCE_WORKER_FLOPS / bench._flops_per_row(
        bench.WIDE_FEATURES, bench.WIDE_HIDDEN)
    assert rec["vs_baseline"] == pytest.approx(4.0e5 / wide_worker,
                                               rel=0.01)


def test_headline_falls_back_to_flagship(monkeypatch, tmp_path, capsys):
    monkeypatch.setattr(bench, "BENCH_LOCAL", str(tmp_path / "b.jsonl"))
    rec = _run_main(monkeypatch, capsys, {
        "nn": {"row_epochs_per_sec": 3.0e6, "auc": 0.97, "wall_s": 1.0,
               "mxu_util_est": 1e-4},
    })
    assert rec["metric"] == "nn_fullbatch_train_throughput"
    assert rec["value"] == 3.0
    assert rec["vs_baseline"] == pytest.approx(1.5, rel=0.01)
    assert "baseline" in rec


def test_run_or_reuse_prefers_persisted(monkeypatch, tmp_path, capsys):
    """A persisted TPU record satisfies a task without a live run, so a
    short chip window is spent only on MISSING records."""
    monkeypatch.delenv("SHIFU_TPU_BENCH_REFRESH", raising=False)
    monkeypatch.setattr(bench, "BENCH_LOCAL", str(tmp_path / "b.jsonl"))
    bench._persist("nn", "tpu", {"row_epochs_per_sec": 123.0,
                                 "workload": bench._workload("nn")})
    called = {"n": 0}
    monkeypatch.setattr(bench, "_run_task",
                        lambda *a, **k: called.__setitem__("n", 1) or
                        (None, "should not run"))
    out, err = bench._run_or_reuse("nn", "tpu", [], {})
    assert out["row_epochs_per_sec"] == 123.0 and called["n"] == 0
    # refresh forces a live run
    monkeypatch.setenv("SHIFU_TPU_BENCH_REFRESH", "1")
    out, err = bench._run_or_reuse("nn", "tpu", [], {})
    assert called["n"] == 1


def test_task_streaming(monkeypatch, capsys, tmp_path):
    """>HBM streaming bench task at toy shape: disk layout generation +
    the real train_nn_streaming path + single measured run."""
    monkeypatch.setattr(bench, "STREAM_ROWS", 6_000)
    monkeypatch.setattr(bench, "STREAM_FEATURES", 12)
    monkeypatch.setattr(bench, "STREAM_HIDDEN", (8,))
    monkeypatch.setattr(bench, "STREAM_CHUNK_ROWS", 1_024)
    monkeypatch.setattr(bench, "STREAM_EPOCHS_LONG", 30)
    monkeypatch.setattr(bench, "STREAM_DIR", str(tmp_path / "stream"))
    bench.task_streaming()
    rec = _last_json(capsys)
    assert rec["row_epochs_per_sec"] > 0
    assert rec["auc"] > 0.75
    # re-running reuses the on-disk layout (no rewrite)
    import os
    mtime = os.path.getmtime(str(tmp_path / "stream" / "dense.npy"))
    bench.task_streaming()
    assert os.path.getmtime(str(tmp_path / "stream" / "dense.npy")) == mtime


def test_stream_layout_prefix_reuse(tmp_path, monkeypatch):
    """A larger complete layout serves a smaller generation-chunk-
    aligned request by prefix slice, bit-identical to a fresh
    generation; a mid-chunk request regenerates instead."""
    import numpy as np
    monkeypatch.setattr(bench, "STREAM_DIR", str(tmp_path / "s1"))
    big = bench._ensure_stream_layout(4_000, 5, chunk=1_000)
    big_dense = np.array(big[0][:2_000])
    big_tags = np.array(big[1][:2_000])
    import os
    mtime = os.path.getmtime(str(tmp_path / "s1" / "dense.npy"))
    # aligned prefix: reused, no rewrite
    d2, t2, w2 = bench._ensure_stream_layout(2_000, 5, chunk=1_000)
    assert os.path.getmtime(str(tmp_path / "s1" / "dense.npy")) == mtime
    assert d2.shape == (2_000, 5) and t2.shape == (2_000,)
    # prefix equals a fresh generation of the same size
    monkeypatch.setattr(bench, "STREAM_DIR", str(tmp_path / "s2"))
    f_dense, f_tags, _ = bench._ensure_stream_layout(2_000, 5,
                                                     chunk=1_000)
    np.testing.assert_array_equal(np.array(d2), np.array(f_dense))
    np.testing.assert_array_equal(np.array(t2), np.array(f_tags))
    np.testing.assert_array_equal(big_dense, np.array(f_dense))
    np.testing.assert_array_equal(big_tags, np.array(f_tags))
    # mid-chunk request: must NOT prefix-slice (content would differ)
    monkeypatch.setattr(bench, "STREAM_DIR", str(tmp_path / "s1"))
    d3, _, _ = bench._ensure_stream_layout(1_500, 5, chunk=1_000)
    assert os.path.getmtime(str(tmp_path / "s1" / "dense.npy")) != mtime
    assert d3.shape == (1_500, 5)


def test_task_mtl(monkeypatch, capsys):
    monkeypatch.setattr(bench, "MTL_ROWS", 6_000)
    monkeypatch.setattr(bench, "MTL_FEATURES", 12)
    monkeypatch.setattr(bench, "MTL_TASKS", 3)
    monkeypatch.setattr(bench, "MTL_HIDDEN", (16, 8))
    monkeypatch.setattr(bench, "MTL_EPOCHS_SHORT", 2)
    monkeypatch.setattr(bench, "MTL_EPOCHS_LONG", 30)
    bench.task_mtl()  # gates task-0 AUC > 0.7 internally
    rec = _last_json(capsys)
    assert rec["row_epochs_per_sec"] > 0
    assert rec["roofline"]["family"] == "MTL"


def test_task_records_carry_roofline(monkeypatch, capsys):
    """Every model-family task record carries a roofline block with
    EXACTLY the profiling.ROOFLINE_FIELDS schema (the same invariant
    tools/check_steps_schema.py enforces on live logs)."""
    from shifu_tpu import profiling
    _patch_small(monkeypatch)
    bench.task_nn()
    roof = _last_json(capsys)["roofline"]
    assert set(roof) == set(profiling.ROOFLINE_FIELDS)
    assert roof["family"] == "NN"
    assert roof["compute_dtype"] == "float32"
    # this run is on the CPU, which has no entry in the peaks table:
    # the utilization fields are null, never a TPU's numbers
    assert roof["bound"] is None and roof["mxu_util"] is None
    assert roof["hbm_util"] is None and roof["ridge_intensity"] is None
    # measured rows/s must reconcile with the derived rates
    assert roof["flops_per_s"] == pytest.approx(
        roof["flops_per_row"] * roof["rows_per_s"], rel=1e-6)
    bench.task_gbt()
    roof = _last_json(capsys)["roofline"]
    assert roof["family"] == "GBT"
    assert roof["flops_per_row"] > 0 and roof["bytes_per_row"] > 0


def test_resolve_backend_probe_knobs(monkeypatch):
    """SHIFU_TPU_BENCH_PROBE_ATTEMPTS/_TIMEOUT_S bound the probe, and
    an exhausted probe falls back to cpu with the path in diags."""
    monkeypatch.setenv("SHIFU_TPU_BENCH_PROBE_ATTEMPTS", "2")
    monkeypatch.setenv("SHIFU_TPU_BENCH_PROBE_TIMEOUT_S", "7")
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    calls = []

    def fake_run_task(task, env_extra=None, timeout=1200):
        calls.append((task, env_extra, timeout))
        if env_extra and env_extra.get("JAX_PLATFORMS") == "cpu":
            return {"backend": "cpu", "n_devices": 1}, None
        return None, "probe wedged"

    monkeypatch.setattr(bench, "_run_task", fake_run_task)
    monkeypatch.setattr(bench.time, "sleep", lambda s: None)
    diags = []
    backend, env_extra, probe = bench._resolve_backend(diags)
    assert backend == "cpu" and env_extra == {"JAX_PLATFORMS": "cpu"}
    # 2 default-backend attempts at the knob timeout, then the cpu probe
    assert [c[2] for c in calls] == [7, 7, 7]
    assert any("attempt 2/2" in d for d in diags)
    assert any("falling back" in d for d in diags)
    # the structured probe block mirrors the diags: per-attempt
    # outcomes plus the machine-readable fallback reason
    assert probe["timeout_s"] == 7
    assert [a["ok"] for a in probe["attempts"]] == [False, False, True]
    assert probe["attempts"][0]["error"] == "probe wedged"
    assert "fell back to cpu" in probe["fallback"]


def test_row_cost_models_closed_form():
    """Analytic per-row costs for known specs, by hand: the roofline's
    inputs must be auditable numbers, not plausible-looking ones."""
    from shifu_tpu import profiling
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
    from shifu_tpu import profiling
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
