"""Ragged WDL tables, the `train_wdl` entry and device-side mini-batching
(tier-1, CPU, small sizes).

(a) `train_wdl` with MiniBatchRows against the benchmark's plain reference
    (`benchmark/families/wdl_reference.py`, which imports nothing of the
    program): losses, validation errors, every leaf, untouched rows exact;
(b) ragged tables with equal column sizes ARE the old stacked model: scores
    and gradients bit for bit, and an old `vocab_size` model file loads and
    scores the same;
(c) the four quarters of a deployment's tables add up to the uncut layer;
(d) device inputs are batched on the device into the host path's batches;
(e) the spans and scopes the WDL job adds;
(g) on a mesh with a model axis both tables are divided by row, whatever
    their lengths, and train to the single-device scores.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import wdl as wdl_family
from benchmark.families import wdl_reference
from shifu_tpu.config.model_config import ModelTrainConf
from shifu_tpu.models import wdl
from shifu_tpu.obs import trace as obs_trace
from shifu_tpu.processor import train_wdl as train_wdl_proc
from shifu_tpu.train import trainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 3000000019          # past 2^31, as the driver's are
JOB_SEED = SEED % (2 ** 31 - 1)


@pytest.fixture(scope="module")
def cell():
    """(configuration at rehearsal size, traffic, data, what a job call
    returned, the reference's run) of the cell wdl-criteo.train."""
    with open(os.path.join(REPO, "benchmark/configs/wdl-criteo.json")) as f:
        config = json.load(f)
    config = {**config, **config["rehearsal"]}
    with open(os.path.join(REPO, "benchmark/traffic/jobs-2-epochs.json")) as f:
        traffic = json.load(f)
    data = wdl_family.make_data(config, SEED, 1)
    got = wdl_family.outputs(
        wdl_family.make_call(config, traffic, data, JOB_SEED)())
    ref = wdl_reference.simulate(config, traffic, data, JOB_SEED)
    return config, traffic, data, got, ref


def test_rehearsal_pads_the_last_batch(cell):
    config, _, data, _, _ = cell
    assert data["y"].shape[0] % config["batch_rows"], \
        "the rehearsal's rows must not tile its batches: the pad is tested"
    assert len(set(config["vocab_sizes"])) > 10, "ragged, not equal columns"


@pytest.mark.parametrize("what", ["train_errors", "val_errors"])
def test_train_wdl_meets_the_reference_errors(cell, what):
    _, _, _, got, ref = cell
    assert got[what].shape == ref[what].shape == (2,)
    np.testing.assert_allclose(got[what], ref[what], rtol=2e-6)


def _leaves(params):
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    return {jax.tree_util.keystr(k): np.asarray(v) for k, v in flat}


def test_train_wdl_meets_the_reference_on_every_leaf(cell):
    _, _, _, got, ref = cell
    want = _leaves(ref["params_by_epoch"][got["best_epoch"]])
    init = _leaves(ref["init"])
    have = _leaves(got["params"])
    assert have.keys() == want.keys()
    for name, leaf in have.items():
        moved = np.linalg.norm(want[name] - init[name])
        assert moved > 0, name
        assert np.linalg.norm(leaf - want[name]) <= 1e-4 * moved, name


def test_untouched_table_rows_keep_their_initial_bits(cell):
    config, _, data, got, ref = cell
    rows = np.asarray(wdl_reference.table_rows(config, data["ids"], None))
    untouched = np.ones(sum(config["vocab_sizes"]), bool)
    untouched[rows.reshape(-1)] = False
    assert untouched.sum() > 100
    init = _leaves(ref["init"])
    for name in ("['embed']", "['wide_cat']"):
        have = _leaves(got["params"])[name]
        assert np.array_equal(have[untouched], init[name][untouched]), name
        assert not np.array_equal(have[~untouched], init[name][~untouched])


def test_compare_passes_the_program_and_fails_every_fault(cell):
    config, traffic, data, got, ref = cell
    ok = lambda checks: all(c["value"] <= c["limit"] for c in checks)  # noqa: E731
    assert ok(wdl_reference.compare(config, data, got, ref))
    broken = wdl_family.faults(config, traffic, data, JOB_SEED, got)
    assert set(broken) == {"state_unchanged", "half_batch",
                           *wdl_reference.TABLE_FAULTS}
    for name, make in broken.items():
        assert not ok(wdl_reference.compare(config, data, make(), ref)), name


# -- (b) equal columns are the old stacked model ---------------------------

def _stacked_forward(spec, params, dense, idx, vocab):
    """`models/wdl.forward` as it was before the ragged tables: stacked
    (Cc, V, E) / (Cc, V) tables, one padded `vocab_size`."""
    from shifu_tpu.models import nn as nn_mod
    cols = jnp.arange(spec.n_cat)[None, :]
    safe = jnp.clip(idx, 0, vocab - 1)
    logit = jnp.zeros(dense.shape[0])
    logit = logit + params["wide_cat"][cols, safe].sum(axis=1)
    emb = params["embed"][cols, safe]
    logit = logit + dense @ params["wide_dense"]
    logit = logit + params["wide_bias"]
    deep = nn_mod.forward(spec.deep_spec, params["deep"],
                          jnp.concatenate([dense, emb.reshape(
                              dense.shape[0], -1)], axis=1))
    return jax.nn.sigmoid(logit + deep)


def _equal_columns(rng, n=64, n_cat=3, vocab=7):
    spec = wdl.WDLSpec(dense_dim=5, n_cat=n_cat, vocab_sizes=(vocab,) * n_cat,
                       embed_size=4, hidden_dims=(8,), activations=("relu",))
    params = wdl.init_params(spec, jax.random.PRNGKey(2))
    params["wide_cat"] = jnp.asarray(
        rng.normal(0, 1, params["wide_cat"].shape).astype(np.float32))
    flat = wdl.file_params(spec, jax.tree.map(np.asarray, params))
    stacked = {**params,
               "embed": jnp.asarray(flat["embed"].reshape(n_cat, vocab, -1)),
               "wide_cat": params["wide_cat"].reshape(n_cat, vocab)}
    dense = jnp.asarray(rng.normal(0, 1, (n, 5)).astype(np.float32))
    # ids past the vocabulary too: both clip them to the missing slot
    idx = jnp.asarray(rng.integers(0, vocab + 2, (n, n_cat)).astype(np.int32))
    return spec, params, stacked, dense, idx, vocab


def test_equal_columns_score_as_the_stacked_model_bit_for_bit(rng):
    spec, params, stacked, dense, idx, vocab = _equal_columns(rng)
    new = wdl.forward(spec, params, dense, idx)
    old = _stacked_forward(spec, stacked, dense, idx, vocab)
    assert np.array_equal(np.asarray(new), np.asarray(old))


def test_equal_columns_give_the_stacked_gradients_bit_for_bit(rng):
    spec, params, stacked, dense, idx, vocab = _equal_columns(rng)
    y = jnp.asarray((rng.random(dense.shape[0]) < 0.4).astype(np.float32))
    loss = lambda p, fwd: -jnp.mean(  # noqa: E731
        y * jnp.log(fwd(p) + 1e-7) + (1 - y) * jnp.log(1 - fwd(p) + 1e-7))
    g_new = jax.grad(lambda p: loss(p, lambda q: wdl.forward(
        spec, q, dense, idx)))(params)
    g_old = jax.grad(lambda p: loss(p, lambda q: _stacked_forward(
        spec, q, dense, idx, vocab)))(stacked)
    g_new = wdl.file_params(spec, jax.tree.map(np.asarray, g_new))
    g_old = {**g_old, "embed": g_old["embed"].reshape(-1, 4),
             "wide_cat": g_old["wide_cat"].reshape(-1)}
    for (ka, a), (kb, b) in zip(sorted(_leaves(g_new).items()),
                                sorted(_leaves(g_old).items())):
        assert ka == kb and np.array_equal(a, b), ka


def test_old_vocab_size_model_file_loads_and_scores_the_same(tmp_path, rng):
    from shifu_tpu.models.spec import load_model, save_model
    from shifu_tpu.portable import score_model
    spec, params, stacked, dense, idx, vocab = _equal_columns(rng)
    old_meta = {"spec": {"dense_dim": 5, "n_cat": 3, "vocab_size": vocab,
                         "embed_size": 4, "hidden_dims": [8],
                         "activations": ["relu"], "l2": 0.0,
                         "wide_enable": True, "deep_enable": True}}
    new_meta = {"spec": {**{k: v for k, v in old_meta["spec"].items()
                            if k != "vocab_size"},
                         "vocab_sizes": [vocab] * 3}}
    scores = {}
    for tag, meta, p in (("old", old_meta, stacked),
                         ("new", new_meta, wdl.file_params(
                             spec, jax.tree.map(np.asarray, params)))):
        path = str(tmp_path / f"{tag}.wdl")
        save_model(path, "wdl", meta, jax.tree.map(np.asarray, p))
        kind, meta2, p2 = load_model(path)
        assert kind == "wdl"
        scores[tag] = (wdl.predict(meta2, p2, np.asarray(dense),
                                   np.asarray(idx)),
                       score_model("wdl", meta2, p2, dense=np.asarray(dense),
                                   index=np.asarray(idx)))
    want = np.asarray(wdl.forward(spec, params, dense, idx))
    for tag, (native, portable) in scores.items():
        assert np.array_equal(native, want), tag
        np.testing.assert_allclose(portable, want, rtol=1e-5, atol=1e-6)


# -- (c) the quarters of a deployment add up -------------------------------

def test_four_quarters_of_the_tables_add_up_to_the_whole_layer(rng):
    """A deployment deals every column's ids over four chips (id mod 4);
    each chip's tables hold ceil(V/4) ids + a missing slot a column. A
    chip contributes the rows of the ids it holds and nothing else; the
    missing slot, the dense wide term and the bias count once (chip 0)."""
    full = (9, 4, 30, 2, 17)                     # real ids + missing slot
    n, n_cat, e = 200, len(full), 32
    spec = wdl.WDLSpec(dense_dim=3, n_cat=n_cat, vocab_sizes=full,
                       embed_size=e, hidden_dims=(4,), activations=("relu",))
    params = wdl.init_params(spec, jax.random.PRNGKey(5))
    params["wide_cat"] = jnp.asarray(
        rng.normal(0, 1, (spec.table_rows,)).astype(np.float32))
    params["wide_dense"] = jnp.asarray(rng.normal(0, 1, 3).astype(np.float32))
    params["wide_bias"] = jnp.asarray(0.3, jnp.float32)
    dense = rng.normal(0, 1, (n, 3)).astype(np.float32)
    idx = np.stack([rng.integers(0, v, n) for v in full], 1).astype(np.int32)
    missing = idx == np.asarray(full) - 1

    rows = np.asarray(wdl.table_index(full, idx))
    table = wdl.file_params(spec, jax.tree.map(np.asarray, params))["embed"]
    whole_emb = np.asarray(wdl.lookup(params["embed"], rows, e))
    assert np.array_equal(whole_emb, table[rows])
    whole_wide = np.asarray(params["wide_cat"])[rows].sum(1) \
        + dense @ np.asarray(params["wide_dense"]) + 0.3

    emb_sum = np.zeros_like(whole_emb)
    wide_sum = np.zeros(n, np.float32)
    for q in range(4):
        real = [len(range(q, v - 1, 4)) for v in full]
        local = tuple(r + 1 for r in real)
        held = [list(range(q, v - 1, 4)) + [v - 1] for v in full]
        pick = np.concatenate([np.asarray(wdl.table_offsets(full)[c]) + np.asarray(h)
                               for c, h in enumerate(held)])
        embed_q = wdl.pack_table(table[pick], 128 // 32)
        wide_q = np.asarray(params["wide_cat"])[pick]
        assert pick.shape[0] == sum(local)
        mine = np.where(missing, q == 0, idx % 4 == q)
        local_idx = np.where(missing, np.asarray(real), idx // 4)
        r_q = np.asarray(wdl.table_index(local, local_idx))
        emb_sum += np.asarray(wdl.lookup(jnp.asarray(embed_q), r_q, e)) \
            * mine[:, :, None]
        wide_sum += (wide_q[r_q] * mine).sum(1)
        if q == 0:
            wide_sum += dense @ np.asarray(params["wide_dense"]) + 0.3
    assert np.array_equal(emb_sum, whole_emb)
    np.testing.assert_allclose(wide_sum, whole_wide, rtol=1e-5, atol=1e-6)


# -- (d) device-side mini-batching ------------------------------------------

@pytest.mark.parametrize("axis_rows,shape", [(0, (1000, 3)), (0, (1000,)),
                                             (1, (2, 1000))])
def test_device_batches_equal_the_host_batches(rng, axis_rows, shape):
    a = rng.normal(0, 1, shape).astype(np.float32)
    perm = trainer.minibatch_row_order(1000, 77)
    host = trainer._host_batches(a, axis_rows, perm, 4, 256)
    dev = trainer._device_batches(jnp.asarray(a), axis_rows,
                                  jnp.asarray(perm), 4, 256)
    assert isinstance(dev, jax.Array) and dev.shape == host.shape
    assert np.array_equal(np.asarray(dev), host)


def _small_job(device: bool, batch_rows=128, sizes=(5, 40, 3)):
    rng = np.random.default_rng(8)
    n = 700
    dense = rng.normal(0, 1, (n, 4)).astype(np.float32)
    idx = np.stack([rng.integers(0, v, n) for v in sizes], 1).astype(np.int32)
    y = (dense[:, 0] + (idx[:, 0] > 2) > 0.5).astype(np.float32)
    w = np.ones(n, np.float32)
    put = jnp.asarray if device else (lambda a: a)
    conf = ModelTrainConf.from_dict({
        "numTrainEpochs": 2, "validSetRate": 0.2,
        "params": {"NumHiddenNodes": [8], "ActivationFunc": ["relu"],
                   "EmbedSize": 4, "Propagation": "ADAGRAD",
                   "LearningRate": 0.05, "MiniBatchRows": batch_rows}})
    val = tuple(put(a[:100]) for a in (dense, idx, y, w))
    return train_wdl_proc.train_wdl(
        conf, put(dense[100:]), put(idx[100:]), put(y[100:]), put(w[100:]),
        sizes, seed=5, val_data=val)


def test_train_wdl_trains_the_same_from_device_and_host_inputs(caplog):
    host = _small_job(device=False)
    with caplog.at_level("WARNING", logger="shifu_tpu"):
        dev = _small_job(device=True)
    assert "readback" not in caplog.text
    assert np.array_equal(host.train_errors, dev.train_errors)
    assert np.array_equal(host.val_errors, dev.val_errors)
    assert host.spec.vocab_sizes == (5, 40, 3)
    assert host.params_per_bag[0]["embed"].shape == (48, 4)   # as filed


def test_minibatch_row_order_is_the_written_rule():
    want = np.random.default_rng(np.uint64(0xB47C4) ^ np.uint64(12306)) \
        .permutation(50)
    assert np.array_equal(trainer.minibatch_row_order(50, 12306), want)


# -- (e) spans and scopes ------------------------------------------------------

def test_shuffle_span_and_wdl_scopes_are_registered():
    assert obs_trace.span_registered("train.shuffle")
    assert {"embed", "wide", "deep", "table_update"} <= set(
        obs_trace.DEVICE_SCOPES)


def test_wdl_job_span_carries_batches_and_lookups(tmp_path):
    from tests.test_train_spans import _profiled_spans
    by_line = _profiled_spans(tmp_path, lambda: _small_job(device=True))
    evs = [e for line in by_line.values() for e in line]
    job = [e for e in evs if e[0] == "shifu:train.job"]
    assert len(job) == 1
    stats = job[0][3]
    assert stats["family"] == "wdl" and int(stats["rows"]) == 600
    assert int(stats["batches"]) == 5
    assert int(stats["lookups"]) == 600 * 3 * 2
    shuffle = [e for e in evs if e[0] == "shifu:train.shuffle"]
    assert len(shuffle) == 1 and int(shuffle[0][3]["batches"]) == 5
    assert job[0][1] <= shuffle[0][1] and shuffle[0][2] <= job[0][2]
    place = [e for e in evs if e[0] == "shifu:train.place"]
    assert shuffle[0][2] <= place[0][1], "side by side, shuffle first"


def test_wdl_program_carries_the_table_scopes():
    from tests.test_train_spans import _op_names, _scopes_of
    import optax
    spec = wdl.WDLSpec(dense_dim=4, n_cat=2, vocab_sizes=(5, 9),
                       embed_size=4, hidden_dims=(6,), activations=("relu",))
    optimizer = train_wdl_proc._tables_scoped(optax.adagrad(0.05))
    keys = jax.random.split(jax.random.PRNGKey(0), 1)
    stacked = jax.vmap(lambda k: wdl.init_params(spec, k))(keys)
    carry = trainer.init_train_carry(optimizer, stacked, keys)
    d = jnp.zeros((2, 32, 4))
    i = jnp.zeros((2, 32, 2), jnp.int32)
    y = jnp.zeros((2, 32))
    mask = jax.tree.map(lambda l: jnp.ones_like(l[0]), stacked)
    text = trainer.train_bags_carry.lower(
        lambda p, inp, w_, k: wdl.loss_fn(spec, p, inp[0], inp[1], inp[2],
                                          w_),
        lambda p, inp, w_: wdl.mse(spec, p, inp[0], inp[1], inp[2], w_),
        optimizer, 1, 0, 0.0, carry, (d, i, y), jnp.ones((1, 2, 32)),
        (d[0], i[0], y[0]), jnp.ones(32), mask,
        n_batches=2).compile().as_text()
    names = _op_names(text)
    assert {"embed", "wide", "deep", "table_update", "forward_loss",
            "update", "validate"} <= _scopes_of(names)
    assert any("forward_loss" in n and "transpose(" in n and "embed" in n
               for n in names), "the gradient's way into the table"
    assert any("/update/" in n and "table_update" in n for n in names)


# -- (f) the two cost models ---------------------------------------------------

def test_wdl_row_costs_counts_the_benchmarks_mlp_operations():
    """`profiling.wdl_row_costs` and `benchmark/work/wdl.py` count the same
    deep-tower operations a training row; the profiling model adds the wide
    adds and charges every lookup its bytes, the benchmark's is a lower
    bound (distinct rows only)."""
    from benchmark.work import wdl as work
    from shifu_tpu import profiling
    config = {"dense_dim": 13, "embed_size": 32, "hidden_dims": [1024, 512,
                                                                 256],
              "output_dim": 1, "vocab_sizes": [10] * 26}
    flops, bytes_ = profiling.wdl_row_costs(13, 26, 32, (1024, 512, 256))
    assert flops == 3 * 2 * work.deep_products(config) + 2 * (13 + 26)
    assert bytes_ > 3 * 4 * 26 * 32          # three passes a lookup, at least


@pytest.mark.parametrize("n_rows", [100, 40_000])
def test_blocked_validation_error_is_the_plain_one(rng, n_rows, monkeypatch):
    spec = wdl.WDLSpec(dense_dim=3, n_cat=2, vocab_sizes=(6, 11),
                       embed_size=4, hidden_dims=(5,), activations=("relu",))
    params = wdl.init_params(spec, jax.random.PRNGKey(9))
    dense = jnp.asarray(rng.normal(0, 1, (n_rows, 3)).astype(np.float32))
    idx = jnp.asarray(np.stack([rng.integers(0, v, n_rows)
                                for v in (6, 11)], 1).astype(np.int32))
    y = jnp.asarray((rng.random(n_rows) < 0.3).astype(np.float32))
    w = jnp.asarray(rng.random(n_rows).astype(np.float32))
    blocked = float(wdl.mse(spec, params, dense, idx, y, w))
    monkeypatch.setattr(wdl, "SCORE_BLOCK_ROWS", 10 ** 9)
    plain = float(wdl.mse(spec, params, dense, idx, y, w))
    assert blocked == pytest.approx(plain, rel=1e-5)


# -- (g) the tables divided by row over a model axis ---------------------------

@pytest.mark.parametrize("sizes", [
    # table rows, and 32 of them to a packed row at EmbedSize 4
    (5, 40, 3),      # 48 rows, 2 packed: both divide a model axis of 2
    (6, 40, 3),      # 49 and 2: only the packed table does
    (5, 22, 3),      # 30 and 1: only the wide table does
    (5, 23, 3),      # 31 and 1: neither does
], ids=["both_even", "wide_odd", "packed_odd", "both_odd"])
def test_model_axis_divides_both_tables_by_row(monkeypatch, sizes):
    """`embed` is lane-packed, so the two tables differ in length and either
    may fail to divide the model axis: both are padded to it, placed divided
    by row, filed without the pad, and score as on one device."""
    from shifu_tpu.parallel import mesh as mesh_mod
    assert len(jax.devices()) == 8
    monkeypatch.setenv("SHIFU_TPU_MESH_DEVICES", "1")
    one = _small_job(device=False, sizes=sizes)

    placed = {}
    real = mesh_mod.place_stacked

    def spy(tree, shardings):
        out = real(tree, shardings)
        # the arrays themselves are handed over to the program
        placed.update({k: (out[k].shape, out[k].sharding)
                       for k in ("embed", "wide_cat")})
        return out

    monkeypatch.setattr(mesh_mod, "place_stacked", spy)
    monkeypatch.setenv("SHIFU_TPU_MESH_DEVICES", "4")
    monkeypatch.setenv("SHIFU_TPU_MESH_MODEL", "2")
    two = _small_job(device=False, sizes=sizes)

    mesh = mesh_mod.default_mesh()
    assert dict(mesh.shape) == {"data": 2, "model": 2}
    assert set(placed) == {"embed", "wide_cat"}
    for name, (shape, sharding) in placed.items():
        assert sharding.spec[1] == "model", (name, sharding)
        assert sharding.shard_shape(shape)[1] == shape[1] // 2, name
    rows = sum(sizes)
    assert two.params_per_bag[0]["embed"].shape == (rows, 4)
    assert two.params_per_bag[0]["wide_cat"].shape == (rows,)
    np.testing.assert_allclose(two.train_errors, one.train_errors,
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(two.val_errors, one.val_errors,
                               rtol=1e-5, atol=1e-7)
    for a, b in zip(jax.tree.leaves(one.params_per_bag[0]),
                    jax.tree.leaves(two.params_per_bag[0])):
        np.testing.assert_allclose(b, a, rtol=1e-4, atol=1e-6)
    rng = np.random.default_rng(3)
    dense = rng.normal(0, 1, (64, 4)).astype(np.float32)
    idx = np.stack([rng.integers(0, v + 1, 64) for v in sizes],
                   1).astype(np.int32)

    def score(res):
        params = wdl.device_params(res.spec, res.params_per_bag[0])
        return np.asarray(wdl.forward(
            res.spec, jax.tree.map(jnp.asarray, params), jnp.asarray(dense),
            jnp.asarray(idx)))

    np.testing.assert_allclose(score(two), score(one), rtol=1e-4, atol=1e-6)
