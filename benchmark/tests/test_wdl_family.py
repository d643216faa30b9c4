"""Tests of what the `wdl` family brings to the yardstick: its dataset, its
work function by hand, its readers on a hand-made trace, its rehearsal,
and its comparison failing the control and every fault.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_wdl_family.py -q
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

CELL = "wdl-criteo.train"
MANIFEST = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))


def _config():
    return harness.find_cell(MANIFEST, CELL)[1]


# --- the configuration ------------------------------------------------------

def test_the_quarter_is_the_published_table_cut_four_ways():
    config = _config()
    published = config["published_vocab_sizes"]
    assert len(published) == 26 and sum(published) == 33_762_577
    assert config["vocab_sizes"] == [math.ceil(v / 4) + 1 for v in published]
    assert config["embed_size"] == 32 and config["dense_dim"] == 13
    assert config["hidden_dims"] == [1024, 512, 256]
    assert set(config["reduced"]) == {"vocab_sizes", "train_rows",
                                      "valid_rows"}
    assert config["train_rows"] % config["batch_rows"] == 0


# --- the dataset ------------------------------------------------------------

def test_rank_probabilities_are_a_distribution_that_falls():
    from benchmark.datasets import criteo_synth
    for n in (1, 7, 2_532_807):
        p = criteo_synth.rank_probabilities(n, 1.1)
        assert p.shape == (n,) and abs(p.sum() - 1.0) < 1e-12
        assert np.all(np.diff(p) < 0)
    p = criteo_synth.rank_probabilities(3, 1.1)
    k = np.arange(1, 5) ** -0.1
    assert np.allclose(p, (k[:-1] - k[1:]) / (1 - k[-1]))


def test_spread_is_a_bijection_of_every_column():
    from benchmark.datasets import criteo_synth
    config = {**_config(), **_config()["rehearsal"]}
    n = criteo_synth.real_ids(config)
    consts = criteo_synth.spread_constants(n)
    for c, size in enumerate(n):
        ranks = np.arange(size, dtype=np.uint32)
        ids = criteo_synth.spread(ranks, np.uint32(size), consts[:, c])
        assert sorted(ids.tolist()) == list(range(size)), c
    big = criteo_synth.real_ids(_config())
    consts = criteo_synth.spread_constants(big)
    assert np.all(big % consts[0] != 0) and np.all(big % consts[1] != 0)
    # every product of the two-step multiplication fits 32 bits
    assert (int(big.max()) - 1) * int(consts[:2].max()) + int(big.max()) \
        < 2 ** 32


def test_rows_follow_the_stated_distribution():
    import jax
    from benchmark.datasets import criteo_synth
    config = {**_config(), **_config()["rehearsal"]}
    effects = criteo_synth.id_effects(config, 2 ** 31 + 3)
    n_rows = 60_000
    dense, ids, y = jax.jit(lambda k: criteo_synth.fill(
        k, n_rows, config, effects))(criteo_synth.seed_key(2 ** 31 + 3, 0))
    dense, ids, y = map(np.asarray, (dense, ids, y))
    assert dense.shape == (n_rows, 13) and np.abs(dense).max() <= 4.0
    n = criteo_synth.real_ids(config)
    assert np.all(ids >= 0) and np.all(ids <= n)
    missing = (ids == n).mean(axis=0)
    assert np.all(np.abs(missing - config["missing_rate"]) < 0.006)
    # the hottest id of a column is rank 1's image under the spread
    c = 2
    consts = criteo_synth.spread_constants(n)
    hot = int(criteo_synth.spread(np.uint32(0), np.uint32(n[c]),
                                  consts[:, c]))
    p1 = criteo_synth.rank_probabilities(int(n[c]), 1.1)[0] \
        * (1 - config["missing_rate"])
    assert abs((ids[:, c] == hot).mean() - p1) < 0.01
    assert 0.15 < y.mean() < 0.4
    # another seed, other rows; the same seed, the same rows
    again = np.asarray(jax.jit(lambda k: criteo_synth.fill(
        k, n_rows, config, effects))(criteo_synth.seed_key(2 ** 31 + 3, 0))[1])
    assert np.array_equal(again, ids)


# --- the work function, by hand ----------------------------------------------

def test_wdl_work_by_hand():
    work = harness.load("work", "wdl")
    config = {"dataset": "criteo_synth", "dense_dim": 2, "embed_size": 3,
              "hidden_dims": [4], "output_dim": 1, "vocab_sizes": [3, 2],
              "train_rows": 8, "batch_rows": 4, "dtype": "float32",
              "zipf_exponent": 1.1, "missing_rate": 0.25}
    # deep input 2 + 2*3 = 8; 8*4 + 4*1 = 36 products
    assert work.deep_products(config) == 36
    # column 0: 2 real ids, ranks with p = (1 - 2^-.1, 2^-.1 - 3^-.1) /
    # (1 - 3^-.1); column 1: one real id; each and the missing slot seen
    # by a batch of 4 with 1 - (1 - p)^4
    k = np.arange(1, 4) ** -0.1
    p = 0.75 * (k[:-1] - k[1:]) / (1 - k[-1])
    seen = lambda q: 1 - (1 - q) ** 4  # noqa: E731
    distinct = seen(p[0]) + seen(p[1]) + seen(0.25) + seen(0.75) + seen(0.25)
    assert work.distinct_rows_per_batch(config) == pytest.approx(distinct)
    # 4 passes over (3 + 1) floats a distinct row, 2 batches a step; a row
    # is 2 floats + label + weight + 2 ids
    table = 2 * 4 * 4 * 4 * distinct
    assert work.table_step_work(config) == {"flops": 0.0,
                                            "bytes": pytest.approx(table)}
    got = work.step_work(config)
    assert got["flops"] == 3 * 2 * 36 * 8
    assert got["bytes"] == pytest.approx(8 * (4 * 4 + 2 * 4) + table)


def test_the_cell_work_is_a_lower_bound_on_a_dense_pass():
    work = harness.load("work", "wdl")
    config = _config()
    distinct = work.distinct_rows_per_batch(config)
    lookups = config["batch_rows"] * len(config["vocab_sizes"])
    assert 26 <= distinct < 0.2 * lookups          # hot ids repeat
    dense_pass = 4 * 4 * 33 * sum(config["vocab_sizes"])
    assert work.table_bytes_per_batch(config) < 0.02 * dense_pass


# --- the readers on a hand-made trace ----------------------------------------

def _context(ops, host=()):
    config = {"vocab_sizes": [30, 50], "batch_rows": 7, "embed_size": 32,
              "dataset": "criteo_synth", "train_rows": 14,
              "dtype": "float32", "zipf_exponent": 1.1, "missing_rate": 0.1}
    dev = tr.Device(0)
    dev.ops = tr.set_self_times(
        [tr.Event(name, s, e, detail=text) for name, s, e, text in ops])
    dev.busy = tr.merge((e.start, e.end) for e in dev.ops)
    spans = [tr.Event(n, s, e) for n, s, e in host if n.startswith("bench:")]
    trace = tr.Reduced(10.0, [dev], spans,
                       [tr.Event(n, s, e) for n, s, e in host])
    return {"trace": trace, "fullest_device": 0, "config": config,
            "steps": 2, "work": harness.load("work", "wdl"),
            "peak": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e3},
            "chips": 1}


OPS = [
    # carries the packed table (80 rows, four to a 128-lane row: 20)
    ("while.1", 0.0, 8.0, "%while.1 = (f32[1,20,128]{2,1,0}, s32[]) while(...)"),
    ("fusion.2", 1.0, 3.0, "%fusion.2 = f32[1,20,128]{2,0,1:T(1,128)} fusion(%p), kind=kLoop"),
    # the wide table (80 rows) and a batch's 14 lookups
    ("fusion.3", 3.0, 3.5, "%fusion.3 = f32[14]{0} fusion(f32[1,80]{1,0:T(1,128)} %w, s32[14]{0} %i)"),
    # a batch's looked-up block
    ("fusion.6", 3.5, 4.0, "%fusion.6 = bf16[1,7,2,32]{3,2,1,0} fusion(f32[1,7,2,128]{3,2,1,0} %g)"),
    # the deep tower and an unrelated copy: not the table path
    ("fusion.4", 4.0, 6.0, "%fusion.4 = f32[7,1024]{1,0:T(8,128)} fusion(f32[7,77]{1,0} %h)"),
    ("copy.5", 9.0, 9.5, "%copy.5 = f32[77,9]{1,0} copy(%q)"),
]


def test_embed_ops_share_reads_the_table_events_self_time():
    share = harness.load("layer_metrics", "embed_ops_share")
    ctx = _context(OPS)
    names = [e.name for e in share.table_events(ctx)]
    # the while carries the table and counts for its self time only
    assert names == ["while.1", "fusion.2", "fusion.3", "fusion.6"]
    busy = 8.5
    assert share.read(ctx) == pytest.approx(100 * (3.0 + 2.0 + 1.0) / busy)
    assert share.read(_context(OPS[4:])) is None
    other = _context(OPS)
    del other["config"]["vocab_sizes"]
    assert share.read(other) is None


def test_embed_roofline_is_least_time_over_measured():
    roof = harness.load("layer_metrics", "embed_roofline")
    ctx = _context(OPS)
    least = ctx["work"].table_step_work(ctx["config"])["bytes"] / 1e3
    assert roof.read(ctx) == pytest.approx(100 * 2 * least / 6.0)
    assert roof.read(_context(OPS[4:])) is None


def test_train_shuffle_ms_reads_the_span_or_nothing():
    shuffle = harness.load("layer_metrics", "train_shuffle_ms")
    host = [("bench:window", 0, 10), ("bench:call", 0, 4),
            ("shifu:train.job", 0.1, 3.9), ("shifu:train.shuffle", 0.2, 0.5),
            ("shifu:train.place", 0.5, 0.6), ("bench:call", 4, 8),
            ("shifu:train.job", 4.1, 7.9), ("shifu:train.shuffle", 4.2, 4.3)]
    assert shuffle.read(_context(OPS, host)) == pytest.approx(1e3 * 0.4 / 2)
    no_job = [h for h in host if not h[0].startswith("shifu:")]
    assert shuffle.read(_context(OPS, no_job)) is None
    full_batch = [h for h in host if h[0] != "shifu:train.shuffle"]
    assert shuffle.read(_context(OPS, full_batch)) == 0.0


def test_the_new_metrics_are_in_the_manifest_for_the_cell_alone():
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in ("embed_ops_share", "embed_roofline", "train_shuffle_ms"):
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "train_rows_per_s"
    reported = {m["name"] for m in harness.metrics_of(MANIFEST, "per_layer",
                                                      CELL)}
    assert {"step_mfu", "device_idle_share", "call_host_ms",
            "train_prepare_ms", "train_place_ms", "train_program_ms",
            "train_fetch_ms", "host_unnamed_ms"} <= reported


# --- rehearsal, control and faults --------------------------------------------

def _passes(checks):
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks)


def test_rehearsal_runs_the_cell_and_prints_no_device_metric(capsys):
    rc = harness.main(["--workload", CELL, "--seed", str(2 ** 31 + 7),
                       "--seconds", "0.5", "--rehearse"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["metrics"] == {}
    assert line["correct"] is True and line["attempted"] >= 1
    assert set(line["checks"]) == {
        "epoch0_loss_gap", "loss_gap", "val_gap", "change_gap",
        "best_epoch_regret", "untouched_changed"}
    assert line["checks"]["untouched_changed"] == {"value": 0.0, "limit": 0}


@pytest.fixture(scope="module")
def calibrated():
    _, config, traffic = harness.find_cell(MANIFEST, CELL)
    config = {**config, **config["rehearsal"]}
    family = harness.load("families", config["family"])
    seed = 2 ** 31 + 9
    job_seed = seed % (2 ** 31 - 1)
    data = family.make_data(config, seed, 1)
    got = family.outputs(family.make_call(config, traffic, data, job_seed)())
    return family, config, traffic, data, job_seed, got


def test_control_in_bfloat16_is_not_correct(calibrated):
    family, config, traffic, data, job_seed, got = calibrated
    found = family.check(config, traffic, data, job_seed, got, control=True)
    assert _passes(found["checks"]), found["checks"]
    assert not _passes(found["control_checks"]), found["control_checks"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "column_dropped", "ids_shifted",
                                   "duplicates_last_wins"])
def test_every_fault_is_not_correct(calibrated, fault):
    family, config, traffic, data, job_seed, got = calibrated
    broken = family.faults(config, traffic, data, job_seed, got)
    assert set(broken) == {"state_unchanged", "half_batch", "column_dropped",
                           "ids_shifted", "duplicates_last_wins"}
    checks = family.check(config, traffic, data, job_seed,
                          broken[fault]())["checks"]
    assert not _passes(checks), checks


def _plant(monkeypatch, fault):
    """Break the timed path underneath a run: the program's own step, as
    `test_benchmark.py::_plant` does for the families it knows."""
    from shifu_tpu.models import wdl
    from shifu_tpu.processor import train_wdl as entry
    if fault == "state_unchanged":
        import optax
        monkeypatch.setattr(entry, "optimizer_from_params",
                            lambda params: optax.set_to_zero())
    elif fault == "half_batch":
        real = entry.train_wdl
        monkeypatch.setattr(
            entry, "train_wdl",
            lambda conf, dense, idx, y, w, sizes, **kw: real(
                conf, dense[:len(y) // 2], idx[:len(y) // 2],
                y[:len(y) // 2], w[:len(y) // 2], sizes, **kw))
    elif fault == "column_dropped":
        # every id of one column goes to its missing slot
        real_index = wdl.table_index

        def dropped(vocab_sizes, idx):
            idx = idx.at[..., 0].set(vocab_sizes[0] - 1)
            return real_index(vocab_sizes, idx)
        monkeypatch.setattr(wdl, "table_index", dropped)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "column_dropped"])
def test_a_run_on_a_broken_program_reports_not_correct(
        fault, monkeypatch, capsys):
    import jax
    jax.clear_caches()
    _plant(monkeypatch, fault)
    rc = harness.main(["--workload", CELL, "--seed", "23", "--seconds",
                       "0.2", "--rehearse"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    jax.clear_caches()
    assert rc == 0 and line["correct"] is False, line["checks"]
