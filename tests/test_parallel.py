"""SPMD-by-default tests: the REAL pipeline over the 8-device mesh.

The reference certifies its distributed loop with GuaguaMRUnitDriver
(whole master–worker app in one JVM, SURVEY.md §4.3); here the analog
is the real processors running over the 8-virtual-device CPU mesh and
matching their 1-device results — plus an HLO check that the GBDT
histogram reduction is an all-reduce (psum), not an all-gather of the
row-sharded bin matrix (dt/DTMaster.java:276 aggregation semantics).
"""

import json
import os

import numpy as np
import pytest


def _train_and_collect(root):
    from shifu_tpu.processor import (init as init_proc, norm as norm_proc,
                                     stats as stats_proc,
                                     train as train_proc)
    from shifu_tpu.processor.base import ProcessorContext
    for proc in (init_proc, stats_proc, norm_proc, train_proc):
        ctx = ProcessorContext.load(root)
        assert proc.run(ctx) == 0
    from shifu_tpu.models.spec import load_model
    _, meta, params = load_model(ctx.path_finder.model_path(0, "nn"))
    with open(ctx.path_finder.val_error_path()) as f:
        val = json.load(f)
    return params, val, ctx


def test_train_mesh_parity_8dev_vs_1dev(tmp_path, rng):
    """`shifu train` over the 8-device mesh produces the same model as
    1-device within fp tolerance (VERDICT #1 done-when)."""
    import jax
    from tests.synth import make_model_set
    assert len(jax.devices()) == 8, "conftest must force 8 CPU devices"

    # identical data for both runs: fresh identically-seeded rngs (the
    # session `rng` fixture has been advanced by earlier tests)
    params8, val8, ctx8 = _train_and_collect(
        make_model_set(tmp_path / "m8", np.random.default_rng(777),
                       n_rows=1500))
    try:
        os.environ["SHIFU_TPU_MESH_DEVICES"] = "1"
        params1, val1, ctx1 = _train_and_collect(
            make_model_set(tmp_path / "m1",
                           np.random.default_rng(777), n_rows=1500))
    finally:
        os.environ.pop("SHIFU_TPU_MESH_DEVICES", None)

    # same data (same rng seed), same seeds → same model up to collective
    # reduction order
    for l8, l1 in zip(params8, params1):
        for k in l8:
            np.testing.assert_allclose(np.asarray(l8[k]), np.asarray(l1[k]),
                                       rtol=2e-3, atol=2e-4)
    assert abs(val8["bestValError"][0] - val1["bestValError"][0]) < 1e-3


def test_stats_mesh_pad_correction(tmp_path, rng):
    """Stats over the 8-device mesh with a row count NOT divisible by 8:
    missing counts and bin counts must not absorb the padding rows."""
    from tests.synth import make_model_set
    from shifu_tpu.processor import init as init_proc, stats as stats_proc
    from shifu_tpu.processor.base import ProcessorContext

    root = make_model_set(tmp_path, rng, n_rows=1003)  # 1003 % 8 != 0
    for proc in (init_proc, stats_proc):
        ctx = ProcessorContext.load(root)
        assert proc.run(ctx) == 0
    ctx = ProcessorContext.load(root)
    total_rows = None
    for cc in ctx.column_configs:
        if not cc.is_candidate or cc.columnBinning.binCountPos is None:
            continue
        st = cc.columnStats
        bn = cc.columnBinning
        n = int(np.sum(bn.binCountPos) + np.sum(bn.binCountNeg))
        # every row lands in exactly one bin (incl. missing): counts sum
        # to the real row count, not the padded one
        assert n == st.totalCount, (cc.columnName, n, st.totalCount)
        assert st.missingCount >= 0
        total_rows = st.totalCount
    assert total_rows is not None and total_rows <= 1003


def test_gbdt_sharded_histogram_matches_single_device():
    """A tree built on the 8-device mesh with row-sharded bins picks
    the same splits as single-device (VERDICT #5) — up to near-tie
    flips: an 8-way psum and a serial sum round differently in f32, so
    a gain tie at that precision can legitimately resolve either way
    on BOTH histogram paths (sibling subtraction widens the window via
    parent − left cancellation). The contract asserted here: at most a
    couple of flipped decisions, agreeing predictions, identical
    histograms where splits agree."""
    import jax
    import jax.numpy as jnp
    from shifu_tpu.models import gbdt

    # dedicated generator: the session rng's position varies with test
    # order, and this test's tolerance accounting needs fixed data
    rng = np.random.default_rng(424242)
    r, c, b = 1000, 6, 16
    bins = rng.integers(0, b - 1, (r, c)).astype(np.int32)
    y = (rng.random(r) < 0.4).astype(np.float32)
    w = np.ones(r, np.float32)
    cfg = gbdt.TreeConfig(max_depth=4, n_bins=b, loss="log")

    def compare(subtract_env, max_flips):
        try:
            os.environ["SHIFU_TPU_HIST_SUBTRACT"] = subtract_env
            trees8, _ = gbdt.build_gbt(cfg, bins, y, w, n_trees=5)
            os.environ["SHIFU_TPU_MESH_DEVICES"] = "1"
            trees1, _ = gbdt.build_gbt(cfg, bins, y, w, n_trees=5)
        finally:
            os.environ.pop("SHIFU_TPU_MESH_DEVICES", None)
            os.environ.pop("SHIFU_TPU_HIST_SUBTRACT", None)
        flips = int(
            (np.asarray(trees8["bin"]) != np.asarray(trees1["bin"])).sum()
            + (np.asarray(trees8["feature"]) !=
               np.asarray(trees1["feature"])).sum())
        assert flips <= max_flips,             f"{flips} split decisions flipped (subtract={subtract_env})"
        binsT = jnp.asarray(bins.T)
        p8 = np.asarray(gbdt.predict_trees(
            jax.tree.map(jnp.asarray, trees8), binsT, cfg.max_depth,
            cfg.n_bins)).sum(axis=0)
        p1 = np.asarray(gbdt.predict_trees(
            jax.tree.map(jnp.asarray, trees1), binsT, cfg.max_depth,
            cfg.n_bins)).sum(axis=0)
        np.testing.assert_allclose(p8, p1, rtol=0.05, atol=0.02)
        return flips

    compare("0", max_flips=2)   # direct path: ulp-level ties only
    compare("1", max_flips=5)   # subtraction widens the tie window


def test_rf_sharded_matches_single_device(rng):
    """build_rf over the 8-device mesh grows the SAME forest (splits,
    leaves) as 1-device — VERDICT r2 #3: RF correctness under SPMD."""
    from shifu_tpu.models import gbdt

    r, c, b = 1000, 6, 16
    bins = rng.integers(0, b - 1, (r, c)).astype(np.int32)
    y = (rng.random(r) < 0.4).astype(np.float32)
    w = np.ones(r, np.float32)
    cfg = gbdt.TreeConfig(max_depth=4, n_bins=b)

    trees8 = gbdt.build_rf(cfg, bins, y, w, n_trees=4,
                           subset_strategy="ALL", bagging_rate=1.0, seed=42)
    try:
        os.environ["SHIFU_TPU_MESH_DEVICES"] = "1"
        trees1 = gbdt.build_rf(cfg, bins, y, w, n_trees=4,
                               subset_strategy="ALL", bagging_rate=1.0,
                               seed=42)
    finally:
        os.environ.pop("SHIFU_TPU_MESH_DEVICES", None)

    np.testing.assert_array_equal(trees8["feature"], trees1["feature"])
    np.testing.assert_array_equal(trees8["bin"], trees1["bin"])
    np.testing.assert_allclose(trees8["leaf_value"], trees1["leaf_value"],
                               rtol=1e-4, atol=1e-5)


def test_forest_histogram_reduction_is_psum_not_gather(rng):
    """HLO check for the lockstep forest histogram: all-reduce (psum),
    never an all-gather of the row-sharded bins — the RF analog of the
    GBT assertion below."""
    import jax
    from shifu_tpu.models.gbdt import _forest_level_histograms
    from shifu_tpu.parallel import mesh as mesh_mod

    mesh = mesh_mod.default_mesh()
    assert mesh.shape["data"] == 8

    r, c, b, s, t = 1024, 4, 8, 4, 3
    binsT = mesh_mod.shard_axis(
        mesh, np.ascontiguousarray(
            rng.integers(0, b, (r, c)).astype(np.int32).T), 1)
    node = mesh_mod.shard_axis(
        mesh, rng.integers(0, s, (t, r)).astype(np.int32), 1)
    grad = mesh_mod.shard_axis(
        mesh, rng.normal(0, 1, (t, r)).astype(np.float32), 1)
    hess = mesh_mod.shard_axis(mesh, np.ones((t, r), np.float32), 1)

    def hist(binsT, node, grad, hess):
        return _forest_level_histograms(binsT, node, grad, hess, 0, s, b,
                                        mesh=mesh)

    hlo = jax.jit(hist).lower(binsT, node, grad, hess).compile().as_text()
    assert "all-reduce" in hlo, "forest histogram should reduce via psum"
    assert "all-gather" not in hlo, \
        "row-sharded operands must not be all-gathered"

    # numerics: matches a per-tree host loop
    g, _ = jax.jit(hist)(binsT, node, grad, hess)
    bins_h = np.asarray(binsT).T
    node_h = np.asarray(node)
    grad_h = np.asarray(grad)
    g_ref = np.zeros((t, s, c, b), np.float32)
    for ti in range(t):
        for i in range(r):
            if node_h[ti, i] < s:
                for j in range(c):
                    g_ref[ti, node_h[ti, i], j, bins_h[i, j]] += grad_h[ti, i]
    np.testing.assert_allclose(np.asarray(g), g_ref, rtol=1e-5, atol=1e-4)


def test_gbdt_histogram_reduction_is_psum_not_gather(rng):
    """HLO check: the sharded level-histogram reduces with all-reduce
    (psum) and never all-gathers the row-sharded (R, C) bin matrix —
    the silent-gather failure mode VERDICT #5 warns about."""
    import jax
    import jax.numpy as jnp
    from shifu_tpu.models.gbdt import _level_histograms
    from shifu_tpu.parallel import mesh as mesh_mod

    mesh = mesh_mod.default_mesh()
    assert mesh.shape["data"] == 8

    r, c, b, s = 1024, 4, 8, 4
    binsT = mesh_mod.shard_axis(
        mesh, np.ascontiguousarray(rng.integers(0, b, (r, c)).astype(np.int32).T), 1)
    node = mesh_mod.shard_axis(mesh, rng.integers(0, s, r).astype(np.int32), 0)
    grad = mesh_mod.shard_axis(mesh, rng.normal(0, 1, r).astype(np.float32), 0)
    hess = mesh_mod.shard_axis(mesh, np.ones(r, np.float32), 0)

    def hist(binsT, node, grad, hess):
        return _level_histograms(binsT, node, grad, hess, 0, s, b, mesh=mesh)

    lowered = jax.jit(hist).lower(binsT, node, grad, hess)
    hlo = lowered.compile().as_text()
    assert "all-reduce" in hlo, "histogram reduction should be a psum"
    assert "all-gather" not in hlo, \
        "row-sharded operands must not be all-gathered"

    # and the result matches the unsharded computation
    g, h = jax.jit(hist)(binsT, node, grad, hess)
    bins_h = np.asarray(binsT).T
    node_h = np.asarray(node)
    grad_h = np.asarray(grad)
    g_ref = np.zeros((s, c, b), np.float32)
    for i in range(r):
        if node_h[i] < s:
            for j in range(c):
                g_ref[node_h[i], j, bins_h[i, j]] += grad_h[i]
    np.testing.assert_allclose(np.asarray(g), g_ref, rtol=1e-5, atol=1e-4)


def _run_family_pipeline(root, algorithm):
    from shifu_tpu.processor import init as init_proc
    from shifu_tpu.processor import norm as norm_proc
    from shifu_tpu.processor import stats as stats_proc
    from shifu_tpu.processor import train as train_proc
    from shifu_tpu.processor.base import ProcessorContext
    for proc in (init_proc, stats_proc, norm_proc, train_proc):
        ctx = ProcessorContext.load(root)
        assert proc.run(ctx) == 0
    return ctx


@pytest.mark.parametrize("algorithm,kind,norm_type,params,epochs,tol", [
    ("WDL", "wdl", "ZSCALE_INDEX",
     {"NumHiddenNodes": [8], "ActivationFunc": ["relu"], "EmbedSize": 4,
      "LearningRate": 0.05}, None, (2e-3, 2e-4)),
    # MTL runs a PINNED short horizon with a TIGHT tolerance. At the
    # synth default of 40 epochs the two meshes diverge chaotically
    # (measured leaf deltas: 1e-10 @ 1 epoch, 0 @ 2, 6e-8 @ 8,
    # ~1e-4 @ 32, ~0.15 @ 40 — pure float-order amplification through
    # the epoch scan plus a best-val-epoch selection flip, NOT a
    # model-axis semantics bug: the 'model'-sharded head psum sums
    # partial products in a different order than the replicated
    # matmul). 8 epochs is past several optimizer steps on every
    # shard yet before chaos outruns float32, so a REAL regression in
    # the head-sharding math (wrong psum, dropped shard, stale
    # replicated trunk) fails loudly while benign reduction-order
    # noise stays ~4 orders of magnitude under the gate.
    ("MTL", "mtl", "ZSCALE",
     {"NumHiddenNodes": [8], "ActivationFunc": ["relu"],
      "LearningRate": 0.05}, 8, (1e-4, 1e-5)),
])
def test_model_axis_parity(tmp_path, monkeypatch, algorithm, kind,
                           norm_type, params, epochs, tol):
    """SHIFU_TPU_MESH_MODEL=2 (data=4 × model=2 mesh; WDL embedding /
    MTL head rows sharded over 'model') trains the same model as the
    pure data mesh — the product model-parallel path (VERDICT r3 next
    #10), not a toy dryrun step."""
    import json as json_mod

    import jax
    from tests.synth import make_model_set
    from shifu_tpu.models.spec import load_model
    assert len(jax.devices()) == 8

    def build(sub):
        root = make_model_set(tmp_path / sub, np.random.default_rng(4242),
                              n_rows=1200, algorithm=algorithm,
                              norm_type=norm_type,
                              train_params=dict(params))
        mcp = os.path.join(root, "ModelConfig.json")
        mc = json_mod.load(open(mcp))
        if algorithm == "MTL":
            mc["dataSet"]["targetColumnName"] = "diagnosis|diagnosis"
        if epochs is not None:
            mc["train"]["numTrainEpochs"] = epochs
        json_mod.dump(mc, open(mcp, "w"))
        return root

    monkeypatch.delenv("SHIFU_TPU_MESH_MODEL", raising=False)
    ctx_d = _run_family_pipeline(build("data_only"), algorithm)
    monkeypatch.setenv("SHIFU_TPU_MESH_MODEL", "2")
    ctx_m = _run_family_pipeline(build("model_axis"), algorithm)

    _, _, p_d = load_model(ctx_d.path_finder.model_path(0, kind))
    _, _, p_m = load_model(ctx_m.path_finder.model_path(0, kind))
    flat_d = jax.tree.leaves(p_d)
    flat_m = jax.tree.leaves(p_m)
    assert len(flat_d) == len(flat_m)
    rtol, atol = tol
    for a, b in zip(flat_d, flat_m):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=rtol, atol=atol)


# ---------------------------------------------------------------------------
# elastic mesh: logical axis rules, 2-D DCN×ICI builder, spec re-resolution
# ---------------------------------------------------------------------------

def test_mesh_rules_defaults_and_env_overrides(monkeypatch):
    from shifu_tpu.parallel import mesh as mesh_mod
    rules = mesh_mod.default_rules()
    assert rules("rows", "hidden") == ("data", "model")
    assert rules("unknown") == (None,)
    # override: replicate 'hidden' (empty RHS), re-point 'vocab' to data
    monkeypatch.setenv("SHIFU_TPU_MESH_RULES", "hidden=,vocab=data")
    rules = mesh_mod.default_rules()
    assert rules("hidden") == (None,)
    assert rules("vocab") == ("data",)
    assert rules("task") == ("model",)   # untouched default
    monkeypatch.setenv("SHIFU_TPU_MESH_RULES", "garbage")
    with pytest.raises(ValueError, match="SHIFU_TPU_MESH_RULES"):
        mesh_mod.default_rules()


def test_mesh_rules_never_duplicate_a_physical_axis():
    """jax rejects P('model','model'); when two logical dims map to the
    same physical axis the FIRST claim wins and later ones replicate
    (MTL heads: task and hidden both default to 'model')."""
    from jax.sharding import PartitionSpec as P

    from shifu_tpu.parallel import mesh as mesh_mod
    rules = mesh_mod.default_rules()
    assert rules.spec("task", "hidden") == P("model", None)
    assert rules.spec("hidden", "task") == P("model", None)


def test_make_mesh_multihost_host_major_and_ici_validation():
    """Multi-host device ordering is host-major so each model group
    stays within one host (ICI); an n_model that cannot divide a
    host's local device count must fail loudly, naming the knob."""
    from types import SimpleNamespace

    from shifu_tpu.parallel import mesh as mesh_mod

    def fake(host, i):
        return SimpleNamespace(process_index=host, id=host * 10 + i)

    # 2 hosts × 4 local: n_model=2 keeps each model pair on one host
    devs = [fake(h, i) for h in (1, 0) for i in range(4)]   # shuffled
    try:
        mesh_mod.make_mesh(4, 2, devices=devs)
    except TypeError:
        # Mesh() itself rejects the fakes on some jax versions — the
        # ordering/validation code above it is what this test covers
        pass
    # n_model=8 spans hosts → ValueError naming the knob
    with pytest.raises(ValueError, match="SHIFU_TPU_MESH_MODEL"):
        mesh_mod.make_mesh(1, 8, devices=devs)
    # uneven per-host counts are rejected too
    devs_uneven = [fake(0, i) for i in range(6)] + [fake(1, i)
                                                    for i in range(2)]
    with pytest.raises(ValueError, match="local device count"):
        mesh_mod.make_mesh(2, 4, devices=devs_uneven)


def test_resolve_spec_against_foreign_meshes():
    import jax

    from shifu_tpu.parallel import mesh as mesh_mod
    mesh = mesh_mod.make_mesh(4, 2)
    # recorded on a matching mesh: names survive
    spec = mesh_mod.resolve_spec(mesh, ["data", "model"], (8, 6))
    assert tuple(spec) == ("data", "model")
    # dim not divisible by the axis → that dim replicates
    spec = mesh_mod.resolve_spec(mesh, [None, "model"], (8, 5))
    assert tuple(spec) == ()
    # axis name this mesh does not have → replicates
    spec = mesh_mod.resolve_spec(mesh, ["expert"], (8,))
    assert tuple(spec) == ()
    # 1-device mesh: everything replicates trivially but specs survive
    one = mesh_mod.make_mesh(1, 1, devices=jax.devices()[:1])
    spec = mesh_mod.resolve_spec(one, ["data", "model"], (8, 6))
    assert tuple(spec) == ("data", "model")


def test_mesh_topology_record():
    from shifu_tpu.parallel import mesh as mesh_mod
    top = mesh_mod.mesh_topology(mesh_mod.make_mesh(4, 2))
    assert top == {"axes": ["data", "model"], "shape": [4, 2],
                   "devices": 8, "hosts": 1}


def test_leased_devices_follow_slice_env(monkeypatch):
    """The device-slice lease seam: SHIFU_TPU_DEVICE_SLICE filters the
    devices default_mesh builds over; a partial id match refuses loudly
    (never a silent shrink onto chips another node leased); a fully
    renumbered visible set no larger than the lease passes through
    (TPU_VISIBLE_DEVICES already did the narrowing)."""
    import jax

    from shifu_tpu.parallel import mesh as mesh_mod
    monkeypatch.delenv("SHIFU_TPU_MESH_DEVICES", raising=False)
    monkeypatch.setenv("SHIFU_TPU_DEVICE_SLICE", "2,5")
    devs = mesh_mod.leased_devices()
    assert sorted(d.id for d in devs) == [2, 5]
    m = mesh_mod.default_mesh()
    assert m.devices.size == 2
    assert sorted(d.id for d in m.devices.flat) == [2, 5]
    assert len(mesh_mod.leased_local_devices()) == 2
    # partial match: id 2 resolves, 99 does not → refuse
    monkeypatch.setenv("SHIFU_TPU_DEVICE_SLICE", "2,99")
    with pytest.raises(RuntimeError, match="refusing"):
        mesh_mod.leased_devices()
    # renumbered visibility: nothing matches but the visible set is no
    # larger than the lease — visibility narrowing already happened
    monkeypatch.setenv("SHIFU_TPU_DEVICE_SLICE", "98,99")
    got = mesh_mod.leased_devices(jax.devices()[:2])
    assert [d.id for d in got] == [0, 1]
    # malformed slice env names the knob
    monkeypatch.setenv("SHIFU_TPU_DEVICE_SLICE", "2,x")
    with pytest.raises(ValueError, match="SHIFU_TPU_DEVICE_SLICE"):
        mesh_mod.leased_devices()
    # no slice env → the whole set, untouched
    monkeypatch.delenv("SHIFU_TPU_DEVICE_SLICE")
    assert len(mesh_mod.leased_devices()) == len(jax.devices())
