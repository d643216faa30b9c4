"""Tests of the yardstick itself: the trace reduction, the work functions,
the harness's refusal to measure without the chip, and the comparison that
decides `correct` — which has to fail for the control and for every fault
a cell can have.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import json
import math
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark import run as harness  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402

MANIFEST = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in MANIFEST["workloads"]]
FIXTURE = os.path.join(ROOT, "benchmark", "fixtures", "probe.xplane.pb")


# --- the trace reduction -------------------------------------------------

def test_merge_clip_and_gaps():
    busy = tr.merge([(1, 2), (1.5, 3), (5, 6), (7, 7)])
    assert busy == [(1, 3), (5, 6)]
    assert tr.clip(busy, 2, 5.5) == [(2, 3), (5, 5.5)]
    assert tr.overlap_s(busy, 0, 10) == 3
    assert tr.gaps(busy, 8) == [(0.0, 1), (3, 5), (6, 8)]


def test_self_time_of_nested_events():
    evs = [tr.Event("while", 0, 10), tr.Event("a", 1, 3),
           tr.Event("inner", 4, 8), tr.Event("b", 5, 6), tr.Event("c", 12, 13)]
    tr.set_self_times(evs)
    assert {e.name: e.self_s for e in evs} == {
        "while": 4, "a": 2, "inner": 3, "b": 1, "c": 1}
    assert list(tr.op_sums(evs)) == ["while", "inner", "a", "b", "c"]


def test_gaps_take_the_innermost_span_and_host_event_open():
    events = [tr.Event("bench:window", 0, 6), tr.Event("bench:call", 0.5, 3),
              tr.Event("lower", 2, 2.5), tr.Event("fetch", 5, 7)]
    assert tr.label_gaps([(0, 1), (2, 4), (5.5, 8)], events) == [
        ["outside", 2.5], ["outside>fetch", 1.5], ["call", 1.0],
        ["call>lower", 0.5]]


def test_reduce_planes_clips_to_the_window():
    ns = 1_000_000_000
    planes = [
        ("/host:CPU", [("main", [("bench:window", 10 * ns, 4 * ns, ""),
                                 ("bench:call", 10 * ns, 3 * ns, ""),
                                 ("bench:warmup", 2 * ns, 5 * ns, "")])]),
        ("/device:TPU:0", [
            ("XLA Ops", [("fusion.1", 9 * ns, 2 * ns, ""),       # half inside
                         ("while.2", 12 * ns, 1 * ns, ""),
                         ("fusion.3", 12 * ns, ns // 2, "")]),
            ("XLA Modules", [("jit_step", 9 * ns, 5 * ns, "")])])]
    red = tr.reduce_planes(planes)
    assert red.window_s == 4
    dev = red.device(0)
    assert dev.busy == [(0.0, 1.0), (2.0, 3.0)] and dev.busy_s == 2
    assert [s.name for s in red.spans] == ["bench:window", "bench:call"]
    assert tr.op_sums(dev.ops) == {"fusion.1": 1.0, "while.2": 0.5,
                                   "fusion.3": 0.5}
    assert len(dev.modules) == 1 and dev.modules[0].seconds == 4


def test_reduction_of_the_recorded_chip_trace():
    """fixtures/probe.xplane.pb: a v5e running two small `build_gbt` and
    two small `train_nn` calls (1M rows, depth 4; PR 23's first chip call),
    recorded with the harness's spans."""
    red = tr.reduce_file(FIXTURE)
    dev = red.device(0)
    assert red.window_s == pytest.approx(1.63103743, abs=1e-6)
    assert [s.name for s in red.spans] == ["bench:window"] + ["bench:call"] * 4
    assert len(dev.ops) == 2776 and len(dev.modules) == 176
    # busy union, and the idle share read from it
    assert dev.busy_s == pytest.approx(0.459138019, abs=1e-6)
    assert 1 - dev.busy_s / red.window_s == pytest.approx(0.718499, abs=1e-5)
    # self times add up to the busy union: no op counted inside its while
    ops = tr.op_sums(dev.ops)
    assert sum(ops.values()) == pytest.approx(dev.busy_s, abs=1e-9)
    assert list(ops)[:3] == ["fusion.243", "fusion.211",
                             "_level_histograms_pallas.12"]
    assert ops["fusion.243"] == pytest.approx(0.268054115, abs=1e-6)
    # the Pallas kernels, told apart as the readers tell them
    kernels = tr.pallas_events(dev)
    hist = [e for e in kernels if tr.is_hist_kernel(e)]
    assert len(kernels) == 36 and len(hist) == 20
    assert sum(e.seconds for e in hist) == pytest.approx(0.07672023, abs=1e-6)
    assert {e.name.split(".")[0] for e in kernels if e not in hist} == {
        "closed_call", "build_tree"}
    # idle gaps by the harness span open at the time
    idle = dict(tr.label_gaps(tr.gaps(dev.busy, red.window_s), red.host, 99))
    inside = sum(v for k, v in idle.items() if k.startswith("call"))
    assert inside == pytest.approx(1.150679561, abs=1e-6)
    assert idle["outside"] == pytest.approx(0.02121985, abs=1e-6)
    assert max(idle, key=idle.get) == "call>lower_sharding_computation"


# --- the work functions, by hand ------------------------------------------

def test_mlp_work_by_hand():
    work = harness.load("work", "mlp")
    config = {"input_dim": 28, "hidden_dims": [64], "output_dim": 1,
              "train_rows": 1000, "dtype": "float32"}
    # 28*64 + 64*1 = 1856 products; x2 a multiply-add, x3 the three passes
    assert work.step_work(config) == {"flops": 3 * 2 * 1856 * 1000,
                                      "bytes": 1000 * 28 * 4}


def test_gbt_work_by_hand():
    work = harness.load("work", "gbt")
    config = {"input_dim": 28, "max_depth": 8, "train_rows": 1000}
    # one pass: 1000 rows x (28 + 12) bytes, 2 x 1000 x 28 adds
    assert work.kernel_call_work(config, 1) == {"flops": 56000, "bytes": 40000}
    assert work.kernel_call_work(config, 4) == {"flops": 14000, "bytes": 10000}
    # 1 + 7/2 = 4.5 passes, and 12 bytes a row for the gradient pass
    assert work.step_work(config) == {"flops": 4.5 * 56000,
                                      "bytes": 4.5 * 40000 + 12000}


def test_step_mfu_is_the_larger_bound():
    mfu = harness.load("layer_metrics", "step_mfu")
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert mfu.least_seconds({"flops": 200, "bytes": 10}, peak, 1) == 2.0
    assert mfu.least_seconds({"flops": 200, "bytes": 100}, peak, 2) == 5.0


# --- the datasets ----------------------------------------------------------

def test_the_drifting_table_differs_along_its_length():
    """higgs_drift is higgs_synth's rows with a logit that weakens and
    sinks along the table: the same features, fewer positives towards the
    end, so a contiguous part is no fair sample of the whole."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark.datasets import higgs_drift, higgs_synth

    n = 40_000

    def labels(dataset):
        def write(outs, xT, y, start):
            return (jax.lax.dynamic_update_slice(outs[0], xT[:1], (0, start)),
                    jax.lax.dynamic_update_slice(outs[1], y, (start,)))
        outs = (jnp.zeros((1, n)), jnp.zeros((n,)))
        x0, y = dataset.fill(dataset.seed_key(2 ** 31 + 5), n, outs, write)
        return np.asarray(x0), np.asarray(y)

    x_flat, y_flat = labels(higgs_synth)
    x_drift, y_drift = labels(higgs_drift)
    assert np.array_equal(x_flat, x_drift)
    halves = lambda y: (y[:n // 2].mean(), y[n // 2:].mean())  # noqa: E731
    first, second = halves(y_flat)
    assert abs(first - second) < 0.02
    first, second = halves(y_drift)
    assert first > second + 0.15 and abs(first - halves(y_flat)[0]) < 0.12


# --- the manifest finds its files -----------------------------------------

def test_every_name_in_the_manifest_finds_its_file():
    for cell in CELLS:
        _, config, traffic = harness.find_cell(MANIFEST, cell)
        harness.load("families", config["family"])
        harness.load("work", config["family"])
        assert traffic["steps_per_call"] > 0
    for m in MANIFEST["per_layer"]:
        assert callable(harness.load("layer_metrics", m["name"]).read)
    peaks = harness.read_json(os.path.join(ROOT, "benchmark", "peaks.json"))
    assert "TPU v5 lite" in peaks["by_device_kind"]


# --- no chip, no number ----------------------------------------------------

def _run(*args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), *args],
        capture_output=True, text=True, env=env, timeout=600)


def test_without_the_chip_the_run_is_refused():
    done = _run("--workload", CELLS[0], "--seed", "3", "--seconds", "1")
    assert done.returncode != 0 and done.stdout == ""
    assert "no accelerator" in done.stderr


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_no_device_metric(cell):
    done = _run("--workload", cell, "--seed", str(2 ** 31 + 7),
                "--seconds", "0.5", "--rehearse")
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["attempted"] >= 1
    assert list(line)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())


# --- the comparison fails what it has to fail ------------------------------

def _passes(checks):
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks)


def _rehearsal(cell_name):
    cell, config, traffic = harness.find_cell(MANIFEST, cell_name)
    config = {**config, **config["rehearsal"]}
    return cell, config, traffic, harness.load("families", config["family"])


@pytest.mark.parametrize("cell_name", CELLS)
def test_control_in_the_precision_below_is_not_correct(cell_name):
    import jax
    cell, config, traffic, family = _rehearsal(cell_name)
    for seed in (5, 6, 2 ** 31 + 9):
        job_seed = seed % (2 ** 31 - 1)
        data = family.make_data(config, seed, 1)
        got = family.outputs(family.make_call(config, traffic, data,
                                              job_seed)())
        found = family.check(config, traffic, data, job_seed, got,
                             control=True)
        assert _passes(found["checks"]), found["checks"]
        assert not _passes(found["control_checks"]), found["control_checks"]
        jax.clear_caches()


@pytest.mark.parametrize("cell_name", CELLS)
def test_every_fault_is_not_correct(cell_name):
    cell, config, traffic, family = _rehearsal(cell_name)
    seed = 17
    data = family.make_data(config, seed, 1)
    got = family.outputs(family.make_call(config, traffic, data, seed)())
    for name, broken in family.faults(config, traffic, data, seed,
                                      got).items():
        checks = family.check(config, traffic, data, seed, broken())["checks"]
        assert not _passes(checks), (name, checks)


def _plant(monkeypatch, family_name, fault):
    """Break the timed path underneath a run: the program's own step."""
    if family_name == "mlp":
        import optax
        from shifu_tpu.train import trainer
        if fault == "state_unchanged":
            monkeypatch.setattr(trainer, "optimizer_from_params",
                                lambda params: optax.set_to_zero())
        elif fault == "half_batch":
            real = trainer.train_nn
            monkeypatch.setattr(
                trainer, "train_nn",
                lambda conf, x, y, w, **kw: real(
                    conf, x[:len(y) // 2], y[:len(y) // 2],
                    w[:len(y) // 2], **kw))
    elif family_name == "gbt":
        from shifu_tpu.models import gbdt
        if fault == "state_unchanged":
            real_core = gbdt._gbt_round_core
            monkeypatch.setattr(
                gbdt, "_gbt_round_core",
                lambda cfg, b, y, w, pred, fm, **kw: (
                    real_core(cfg, b, y, w, pred, fm, **kw)[0], pred))
        elif fault == "half_batch":
            real = gbdt.build_gbt
            monkeypatch.setattr(
                gbdt, "build_gbt",
                lambda cfg, b, y, w, **kw: real(
                    cfg, b[:, ::2], y[::2], w[::2], **kw))
        elif fault == "answer_altered":
            real_splits = gbdt._best_splits

            def off_by_some(gh, cfg, fm, mesh=None):
                s = dict(real_splits(gh, cfg, fm, mesh=mesh))
                s["bin"] = (s["bin"] + 8) % (cfg.n_bins - 2)
                return s
            monkeypatch.setattr(gbdt, "_best_splits", off_by_some)


FAULTS = {"mlp": ["state_unchanged", "half_batch"],
          "gbt": ["state_unchanged", "half_batch", "answer_altered"]}


@pytest.mark.parametrize("cell_name,fault", [
    (c, f) for c in CELLS
    for f in FAULTS[harness.find_cell(MANIFEST, c)[1]["family"]]])
def test_a_run_on_a_broken_program_reports_not_correct(
        cell_name, fault, monkeypatch, capsys):
    import jax
    family_name = harness.find_cell(MANIFEST, cell_name)[1]["family"]
    jax.clear_caches()
    _plant(monkeypatch, family_name, fault)
    rc = harness.main(["--workload", cell_name, "--seed", "23",
                       "--seconds", "0.2", "--rehearse"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    jax.clear_caches()
    assert rc == 0 and line["correct"] is False, line["checks"]
