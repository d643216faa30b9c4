"""Tests of what the `gbt_mesh` family brings to the yardstick: its
configuration beside `gbt-higgs`, its rows over the chips, its readers on
a hand-made trace, and, in a child that shows four CPU devices (this
process shows one, which is what the one-chip cells' rehearsals need),
its rehearsal, its comparison failing the control and every fault, and a
chip's histograms dropped underneath a run.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_gbt_mesh_family.py -q
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark import run as harness  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402

CELL = "gbt-higgs-x4.train"
MANIFEST = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
FAULTS = ["state_unchanged", "half_batch", "answer_altered", "shard_dropped"]


def _config(cell=CELL):
    return harness.find_cell(MANIFEST, cell)[1]


# --- the configuration and the cell -------------------------------------------

def test_the_deployment_changes_rows_and_nothing_of_the_model():
    x4, one = _config(), _config("gbt-higgs.train")
    same = ["dataset", "input_dim", "value_bins", "n_bins", "max_depth",
            "learning_rate", "loss", "reg_lambda", "min_instances_per_node",
            "min_info_gain", "feature_subset", "valid_rows", "dtype",
            "matmul_operand_dtype", "control_precision"]
    assert {k: x4[k] for k in same} == {k: one[k] for k in same}
    assert x4["family"] == "gbt_mesh" and x4["reduced"] == ["train_rows"]
    assert x4["train_rows"] == 2 ** 27 == 4 * 2 * one["train_rows"]
    assert set(x4["limits"]) == {"split_regret", "gain_gap", "leaf_gap"}
    cell = harness.find_cell(MANIFEST, CELL)[0]
    assert cell["chips"] == 4 and cell["traffic"] == "jobs-2-trees"
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1


def test_the_cell_reports_what_the_one_chip_cell_reports_and_two_more():
    """But for three accepted readers that find their events by the
    instruction text of the FULLEST device's `XLA Modules` executions:
    on the four-chip host that is chip 0, most of whose executions the
    profiler leaves unnamed, and they read a tenth of the truth there
    (PERF.md section 7 (n)); they stay silent on the cell until they
    read a chip that is named in full."""
    def names(cell):
        return {m["name"] for m in harness.metrics_of(MANIFEST, "per_layer",
                                                      cell)}
    new = {"collective_exposed_share", "device_busy_skew"}
    silent = {"hist_kernel_share", "split_kernel_share",
              "dispatches_per_step"}
    assert names(CELL) == (names("gbt-higgs.train") - silent) | new
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in new:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["layer"] == "collectives"
        assert by_name[name]["moves"] == "train_rows_per_s"


def test_work_is_the_gbt_familys_with_a_chips_rows_a_kernel_call():
    work, one = harness.load("work", "gbt_mesh"), harness.load("work", "gbt")
    config = _config()
    assert work.step_work(config) == one.step_work(config)
    assert work.kernel_call_work(config, 4) == one.pass_work(config, 2 ** 25)


# --- the readers on a hand-made trace ------------------------------------------

AR = ("%psum.81 = f32[2,1,28,64]{3,2,1,0:T(8,128)S(1)} all-reduce("
      "%pad_maximum_fusion.42), channel_id=1, replica_groups={{0,1,2,3}}, "
      "to_apply=%region_1.2")
OPS = [
    # the round's scan holds everything and covers nothing itself
    ("while.3", 0.0, 9.0, "%while.3 = (s32[], f32[64]{0}) while(%t), "
     "condition=%c, body=%b"),
    ("closed_call.7", 0.5, 2.0, "%closed_call.7 = f32[16,1792]{1,0} "
     "custom-call(%a, %b), custom_call_target=\"tpu_custom_call\""),
    # a synchronous all-reduce, named by jax after the psum: 1 s alone
    ("psum.81", 2.0, 3.0, AR),
    ("fusion.9", 3.0, 4.0, "%fusion.9 = s32[64]{0} fusion(%n), kind=kLoop"),
    # an asynchronous one, 4.0 to 6.0, with 1.5 s of compute under it
    ("all-gather-start.1", 4.0, 4.1, "%all-gather-start.1 = (f32[8]{0}, "
     "f32[32]{0}) all-gather-start(%x), dimensions={0}"),
    ("fusion.10", 4.2, 5.7, "%fusion.10 = f32[64]{0} fusion(%m), kind=kLoop"),
    ("all-gather-done.1", 5.9, 6.0, "%all-gather-done.1 = f32[32]{0} "
     "all-gather-done(%all-gather-start.1)"),
    # a fusion XLA named after the collective inside it
    ("all-reduce-scatter.2", 7.0, 7.25, "%all-reduce-scatter.2 = f32[8]{0} "
     "fusion(%y), kind=kCustom"),
]


def _device(index, ops, runs=((0.0, 9.5),)):
    dev = tr.Device(index)
    dev.ops = tr.set_self_times(
        [tr.Event(name, s, e, detail=text) for name, s, e, text in ops])
    dev.modules = [tr.Event("jit__gbt_rounds(1)", s, e) for s, e in runs]
    dev.busy = tr.merge((e.start, e.end) for e in dev.ops)
    return dev


def _context(devices):
    return {"trace": tr.Reduced(10.0, devices, [], []), "fullest_device": 0}


def test_opcode_is_read_from_the_instructions_text():
    reader = harness.load("layer_metrics", "collective_exposed_share")
    assert [reader.opcode(text) for _, _, _, text in OPS] == [
        "while", "custom-call", "all-reduce", "fusion", "all-gather-start",
        "fusion", "all-gather-done", "fusion"]
    assert reader.opcode("fusion.3") == ""


def test_collective_exposed_share_counts_what_no_other_op_covers():
    reader = harness.load("layer_metrics", "collective_exposed_share")
    dev = _device(0, OPS)
    assert reader.collective_intervals(dev) == [(2.0, 3.0), (4.0, 6.0),
                                                (7.0, 7.25)]
    # 1.0 + (2.0 - 1.5) + 0.25 of a 10 s window
    assert reader.read(_context([dev])) == pytest.approx(17.5)
    no_collective = [op for op in OPS if op[0] in (
        "while.3", "closed_call.7", "fusion.9", "fusion.10")]
    assert reader.read(_context([_device(0, no_collective)])) is None


def test_collective_exposed_share_scales_over_the_executions_named_in_full():
    """The four-chip host's profiler calls most executions' events
    `region.<n>` and gives no text: what the named executions show stands
    for all of them."""
    reader = harness.load("layer_metrics", "collective_exposed_share")
    unnamed = [(f"region.{i}", 10.0 + s, 10.0 + e, f"region.{i}")
               for i, (_, s, e, _) in enumerate(OPS)]
    # the unnamed execution is on the ops line and not on the modules line
    dev = _device(0, OPS + unnamed, runs=((0.0, 9.5),))
    context = {"trace": tr.Reduced(20.0, [dev], [], []), "fullest_device": 0}
    assert reader.read(context) == pytest.approx(100 * 1.75 * 2 / 20.0)
    nameless = _device(0, unnamed, runs=((10.0, 19.5),))
    assert reader.read({"trace": tr.Reduced(20.0, [nameless], [], []),
                        "fullest_device": 0}) is None


def test_collective_exposed_share_reads_the_chip_named_in_full():
    """Chip 0 half named, chip 1 named in full with a shorter reduction:
    chip 1 is read, and nothing is scaled; of two chips named alike the
    fullest is read."""
    reader = harness.load("layer_metrics", "collective_exposed_share")
    unnamed = [(f"region.{i}", 10.0 + s, 10.0 + e, f"region.{i}")
               for i, (_, s, e, _) in enumerate(OPS)]
    half = _device(0, OPS + unnamed, runs=((0.0, 9.5),))
    quick = [("psum.81", 2.0, 2.5, AR) if op[0] == "psum.81" else op
             for op in OPS]
    full = _device(1, quick + [(n, 10.0 + s, 10.0 + e, t)
                               for n, s, e, t in quick],
                   runs=((0.0, 9.5), (10.0, 19.5)))
    for fullest in (0, 1):
        context = {"trace": tr.Reduced(20.0, [half, full], [], []),
                   "fullest_device": fullest}
        assert reader.read(context) == pytest.approx(100 * 1.25 * 2 / 20.0)
    alike = {"trace": tr.Reduced(10.0, [_device(0, OPS), _device(1, quick)],
                                 [], [])}
    assert reader.read({**alike, "fullest_device": 0}) == pytest.approx(17.5)
    assert reader.read({**alike, "fullest_device": 1}) == pytest.approx(12.5)


def test_device_busy_skew_is_busiest_less_idlest_over_the_window():
    reader = harness.load("layer_metrics", "device_busy_skew")
    devices = [_device(0, OPS), _device(1, OPS[:1]),
               _device(2, [("fusion.1", 0.0, 8.5, "%fusion.1 = f32[1]{0} "
                            "fusion(%a), kind=kLoop")])]
    assert reader.read(_context(devices)) == pytest.approx(100 * 0.5 / 10)
    assert reader.read(_context(devices[:1])) is None


# --- in a child with four devices: rehearsal, control, faults -------------------

_CHILD = """
import io, json, math, sys
from contextlib import redirect_stdout
sys.path.insert(0, {root!r})
import jax
import numpy as np
from benchmark import run as harness

CELL = {cell!r}
out = {{"devices": len(jax.devices())}}
text = io.StringIO()
with redirect_stdout(text):
    rc = harness.main(["--workload", CELL, "--seed", str(2 ** 31 + 7),
                       "--seconds", "0.5", "--rehearse"])
out["rehearsal"] = {{"rc": rc, "line": json.loads(
    text.getvalue().strip().splitlines()[-1])}}

manifest = harness.read_json({root!r} + "/BENCHMARK.json")
cell, config, traffic = harness.find_cell(manifest, CELL)
config = {{**config, **config["rehearsal"]}}
family = harness.load("families", config["family"])
seed = 2 ** 31 + 9
job_seed = seed % (2 ** 31 - 1)
data = family.make_data(config, seed, cell["chips"])
again = family.make_data(config, seed, cell["chips"])
other = family.make_data(config, seed + 1, cell["chips"])
quarter = config["train_rows"] // cell["chips"]
bins = np.asarray(data["binsT"])
out["data"] = {{
    "devices_of_binsT": len(data["binsT"].sharding.device_set),
    "spec": str(data["binsT"].sharding.spec),
    "shard_shapes": [list(s.data.shape)
                     for s in data["binsT"].addressable_shards],
    "y_lies_as_the_rows": data["y"].sharding.is_equivalent_to(
        data["w"].sharding, 1) and str(data["y"].sharding.spec)
        == "PartitionSpec('data',)",
    "chips_differ": not np.array_equal(bins[:, :quarter],
                                       bins[:, quarter:2 * quarter]),
    "same_seed_same_rows": bool(np.array_equal(np.asarray(data["binsT"]),
                                               np.asarray(again["binsT"]))),
    "other_seed_other_rows": not np.array_equal(np.asarray(data["y"]),
                                                np.asarray(other["y"])),
    "bins_in_range": bool((np.asarray(data["binsT"]) >= 0).all()
                          and (np.asarray(data["binsT"])
                               < config["n_bins"]).all())}}
got = family.outputs(family.make_call(config, traffic, data, job_seed)())
out["control"] = family.check(config, traffic, data, job_seed, got,
                              control=True)
out["faults"] = {{
    name: family.check(config, traffic, data, job_seed, broken())["checks"]
    for name, broken in family.faults(config, traffic, data, job_seed,
                                      got).items()}}

# a chip's local histograms are zero in every level's all-reduce
import jax.numpy as jnp
from shifu_tpu.models import gbdt
real = gbdt._local_level_histograms
gbdt._local_level_histograms = lambda *a: tuple(
    jnp.where(jax.lax.axis_index("data") == 3, 0.0, x) for x in real(*a))
jax.clear_caches()
text = io.StringIO()
with redirect_stdout(text):
    rc = harness.main(["--workload", CELL, "--seed", "23", "--seconds",
                       "0.2", "--rehearse"])
out["planted_shard_dropped"] = {{"rc": rc, "line": json.loads(
    text.getvalue().strip().splitlines()[-1])}}
print(json.dumps(out, default=harness.printable))
"""


@pytest.fixture(scope="module")
def child():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    r = subprocess.run(
        [sys.executable, "-c", _CHILD.format(root=ROOT, cell=CELL)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def _passes(checks):
    return all(c["value"] <= c["limit"] for c in checks)


def test_rehearsal_runs_the_cell_on_four_devices(child):
    assert child["devices"] == 4
    got = child["rehearsal"]
    assert got["rc"] == 0 and got["line"]["metrics"] == {}
    assert got["line"]["correct"] is True and got["line"]["attempted"] >= 1
    assert got["line"]["device"]["count"] == 4
    assert set(got["line"]["checks"]) == {"split_regret", "gain_gap",
                                          "leaf_gap"}


def test_every_chip_makes_its_own_rows_where_they_stay(child):
    data = child["data"]
    assert data["devices_of_binsT"] == 4
    assert data["spec"] == "PartitionSpec(None, 'data')"
    assert data["shard_shapes"] == [[28, 5000]] * 4
    assert all(data[k] for k in (
        "y_lies_as_the_rows", "chips_differ", "same_seed_same_rows",
        "other_seed_other_rows", "bins_in_range")), data


def test_control_in_bfloat16_is_not_correct(child):
    found = child["control"]
    assert _passes(found["checks"]), found["checks"]
    assert not _passes(found["control_checks"]), found["control_checks"]


@pytest.mark.parametrize("fault", FAULTS)
def test_every_fault_is_not_correct(child, fault):
    assert set(child["faults"]) == set(FAULTS)
    assert not _passes(child["faults"][fault]), child["faults"][fault]


def test_a_run_whose_all_reduce_misses_a_chip_reports_not_correct(child):
    got = child["planted_shard_dropped"]
    assert got["rc"] == 0 and got["line"]["correct"] is False, \
        got["line"]["checks"]
