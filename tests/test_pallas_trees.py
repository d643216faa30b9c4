"""Fused Pallas tree-ensemble inference (ops/pallas_trees.py) parity.

The interpretive `bin_dataset + predict_trees` walk (`gbdt.predict`'s
"xla" route) is the pinned reference; the fused kernel must reproduce
it — in-register binning, missing-value `default_left` routing,
categorical cat_map routing, and the `gbdt.predict` convert (RF mean;
GBT lr·sum with the ±30-clip sigmoid) — through interpret mode on CPU.
Per-row ROUTING is integer-exact, so structure decisions bit-match;
final scores may differ at f32-ulp scale only (the kernel accumulates
the leaf sum tree-by-tree where numpy pairwise-reassociates, and
jnp.exp vs np.exp in the sigmoid).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from shifu_tpu.models import gbdt
from shifu_tpu.models.gbdt import TreeConfig
from shifu_tpu.ops import pallas_trees


def _dataset(rng, n=600, cn=5, cc=2, vocab=6, n_bins=16, miss=0.08):
    """Raw cleaned blocks (NaN-missing numeric + coded categoricals)
    with their binning tables — the layout `gbdt.predict` serves."""
    dense = rng.normal(0, 1, (n, cn)).astype(np.float32)
    dense[rng.random((n, cn)) < miss] = np.nan
    codes = rng.integers(0, vocab, (n, cc)).astype(np.int32)
    codes[rng.random((n, cc)) < miss] = -1  # missing category
    qs = np.linspace(0, 1, n_bins)[1:-1]
    num_cuts = np.nanquantile(dense, qs, axis=0).astype(np.float32)
    tables = gbdt.make_bin_tables(
        num_cuts, [rng.permutation(vocab).astype(np.int32)
                   for _ in range(cc)], n_bins)
    y = ((np.nan_to_num(dense[:, 0]) + 0.4 * codes[:, 0]) > 0.5) \
        .astype(np.float32)
    return dense, codes, tables, y


def _spec(kind, cfg, trees, tables):
    meta = {"kind": kind,
            "treeConfig": {"max_depth": cfg.max_depth,
                           "n_bins": cfg.n_bins,
                           "learning_rate": cfg.learning_rate,
                           "loss": cfg.loss}}
    import jax
    params = {"trees": jax.tree.map(np.asarray, trees),
              "tables": tables}
    return meta, params


def _both_routes(meta, params, dense, codes):
    ref = gbdt.predict(meta, params, dense, codes, route="xla")
    fused = gbdt.predict(meta, params, dense, codes, route="pallas")
    return ref, fused


@pytest.mark.parametrize("loss", ["squared", "log"])
def test_fused_matches_walk_gbt(rng, loss):
    """Trained GBT, mixed numeric/categorical with missing on both:
    fused route ≡ the interpretive walk at ulp tolerance."""
    n_bins = 16
    dense, codes, tables, y = _dataset(rng, n_bins=n_bins)
    bins = gbdt.bin_dataset(tables, dense, codes, n_bins)
    cfg = TreeConfig(max_depth=4, n_bins=n_bins, learning_rate=0.2,
                     loss=loss)
    trees, _ = gbdt.build_gbt(cfg, bins, y, np.ones_like(y), 5)
    meta, params = _spec("gbt", cfg, trees, tables)
    ref, fused = _both_routes(meta, params, dense, codes)
    np.testing.assert_allclose(fused, ref, rtol=1e-6, atol=1e-7)


def test_fused_matches_walk_rf(rng):
    """RF (in-kernel mean convert) over its Poisson-bagged forest."""
    n_bins = 16
    dense, codes, tables, y = _dataset(rng, n_bins=n_bins)
    bins = gbdt.bin_dataset(tables, dense, codes, n_bins)
    cfg = TreeConfig(max_depth=3, n_bins=n_bins)
    trees = gbdt.build_rf(cfg, bins, y, np.ones_like(y), 4, "SQRT",
                          1.0, 7)
    meta, params = _spec("rf", cfg, trees, tables)
    ref, fused = _both_routes(meta, params, dense, codes)
    np.testing.assert_allclose(fused, ref, rtol=1e-6, atol=1e-7)


def _hand_tree(n_nodes, feature, bin_, default_left, leaves):
    """One depth-1 tree: root split on `feature` at `bin_`, children
    leaves. Arrays in the (T, n_nodes) stacked-tree layout."""
    t = {"feature": np.full((1, n_nodes), -1, np.int32),
         "bin": np.zeros((1, n_nodes), np.int32),
         "default_left": np.zeros((1, n_nodes), np.int32),
         "is_leaf": np.ones((1, n_nodes), bool),
         "gain": np.zeros((1, n_nodes), np.float32),
         "leaf_value": np.zeros((1, n_nodes), np.float32)}
    t["feature"][0, 0] = feature
    t["bin"][0, 0] = bin_
    t["default_left"][0, 0] = default_left
    t["is_leaf"][0, 0] = False
    t["leaf_value"][0, 1] = leaves[0]
    t["leaf_value"][0, 2] = leaves[1]
    return t


@pytest.mark.parametrize("default_left", [0, 1])
def test_missing_routes_by_default_left(default_left):
    """NaN rows must take the split's default direction — both ways —
    and land on the same leaf as the reference walk."""
    n_bins = 8
    cfg = TreeConfig(max_depth=1, n_bins=n_bins, learning_rate=1.0,
                     loss="squared")
    trees = _hand_tree(cfg.n_nodes, feature=0, bin_=2,
                       default_left=default_left, leaves=(-1.0, 2.0))
    num_cuts = np.arange(1, n_bins - 1, dtype=np.float32)[:, None]
    tables = gbdt.make_bin_tables(num_cuts, [], n_bins)
    dense = np.array([[0.5], [2.5], [np.nan], [5.5]], np.float32)
    meta, params = _spec("gbt", cfg, trees, tables)
    ref, fused = _both_routes(meta, params, dense, None)
    np.testing.assert_array_equal(fused, ref)
    # the NaN row went where default_left says, not where a bin would
    assert fused[2] == (-1.0 if default_left else 2.0)


def test_categorical_cat_map_routing(rng):
    """Categorical columns route through the posRate-ordered cat_map
    (identity cuts host-mapped by make_fused_inputs) — including -1
    and out-of-vocab missing codes."""
    n_bins, vocab = 8, 4
    cfg = TreeConfig(max_depth=1, n_bins=n_bins, learning_rate=1.0,
                     loss="squared")
    trees = _hand_tree(cfg.n_nodes, feature=0, bin_=1,
                       default_left=0, leaves=(3.0, -4.0))
    order = np.array([2, 0, 3, 1], np.int32)  # raw code → ordered bin
    tables = gbdt.make_bin_tables(np.zeros((n_bins - 2, 0), np.float32),
                                  [order], n_bins)
    codes = np.array([[0], [1], [2], [3], [-1], [vocab]], np.int32)
    dense = np.zeros((len(codes), 0), np.float32)
    meta, params = _spec("gbt", cfg, trees, tables)
    ref, fused = _both_routes(meta, params, dense, codes)
    np.testing.assert_array_equal(fused, ref)
    expect = np.where(order <= 1, 3.0, -4.0).astype(np.float32)
    np.testing.assert_array_equal(fused[:vocab], expect)
    # missing codes (-1 and vocab-length) take default_left=0 → right
    np.testing.assert_array_equal(fused[vocab:], [-4.0, -4.0])


def test_logloss_clip_boundary():
    """Raw scores past ±30 clip BEFORE the sigmoid on both routes —
    the exact `gbdt.predict` convert, saturating to {σ(-30), σ(30)}."""
    n_bins = 8
    cfg = TreeConfig(max_depth=1, n_bins=n_bins, learning_rate=1.0,
                     loss="log")
    trees = _hand_tree(cfg.n_nodes, feature=0, bin_=2, default_left=0,
                       leaves=(-100.0, 100.0))
    num_cuts = np.arange(1, n_bins - 1, dtype=np.float32)[:, None]
    tables = gbdt.make_bin_tables(num_cuts, [], n_bins)
    dense = np.array([[0.5], [5.5]], np.float32)
    meta, params = _spec("gbt", cfg, trees, tables)
    ref, fused = _both_routes(meta, params, dense, None)
    np.testing.assert_allclose(fused, ref, rtol=1e-6, atol=0)
    np.testing.assert_allclose(
        fused, [1.0 / (1.0 + np.exp(30.0)),
                1.0 / (1.0 + np.exp(-30.0))], rtol=1e-6)


def test_stub_tree_all_leaf():
    """A root-leaf-only ensemble (max_depth 0 fold: every node a leaf)
    must score the constant on both routes — the walk never moves."""
    n_bins = 8
    cfg = TreeConfig(max_depth=2, n_bins=n_bins, learning_rate=0.5,
                     loss="squared")
    t = {"feature": np.full((2, cfg.n_nodes), -1, np.int32),
         "bin": np.zeros((2, cfg.n_nodes), np.int32),
         "default_left": np.zeros((2, cfg.n_nodes), np.int32),
         "is_leaf": np.ones((2, cfg.n_nodes), bool),
         "gain": np.zeros((2, cfg.n_nodes), np.float32),
         "leaf_value": np.zeros((2, cfg.n_nodes), np.float32)}
    t["leaf_value"][0, 0] = 1.5
    t["leaf_value"][1, 0] = -0.5
    num_cuts = np.arange(1, n_bins - 1, dtype=np.float32)[:, None]
    tables = gbdt.make_bin_tables(num_cuts, [], n_bins)
    dense = np.array([[0.1], [np.nan], [9.0]], np.float32)
    meta, params = _spec("gbt", cfg, t, tables)
    ref, fused = _both_routes(meta, params, dense, None)
    np.testing.assert_array_equal(fused, ref)
    np.testing.assert_allclose(fused, np.full(3, 0.5, np.float32),
                               rtol=1e-6)


def test_route_knob_and_explicit_override(rng, monkeypatch):
    """SHIFU_TPU_TREE_FUSED resolves the default route (auto → xla off
    TPU); an explicit route= argument overrides the knob either way."""
    import jax
    monkeypatch.setenv("SHIFU_TPU_TREE_FUSED", "auto")
    expect_auto = "pallas" if jax.default_backend() == "tpu" else "xla"
    assert pallas_trees.tree_fused_mode() == expect_auto
    monkeypatch.setenv("SHIFU_TPU_TREE_FUSED", "pallas")
    assert pallas_trees.tree_fused_mode() == "pallas"
    monkeypatch.setenv("SHIFU_TPU_TREE_FUSED", "xla")
    assert pallas_trees.tree_fused_mode() == "xla"

    n_bins = 16
    dense, codes, tables, y = _dataset(rng, n=200, n_bins=n_bins)
    bins = gbdt.bin_dataset(tables, dense, codes, n_bins)
    cfg = TreeConfig(max_depth=3, n_bins=n_bins)
    trees, _ = gbdt.build_gbt(cfg, bins, y, np.ones_like(y), 3)
    meta, params = _spec("gbt", cfg, trees, tables)
    # env pins xla; the explicit pallas route must still run fused
    fused = gbdt.predict(meta, params, dense, codes, route="pallas")
    default = gbdt.predict(meta, params, dense, codes)
    np.testing.assert_allclose(fused, default, rtol=1e-6, atol=1e-7)


def test_padding_and_row_tile_invariance(rng):
    """Scores are invariant to bucket padding (serving repeats the
    last row up to the bucket) and to the kernel row tile — each row
    only ever sees its own lane."""
    n_bins = 16
    dense, codes, tables, y = _dataset(rng, n=150, n_bins=n_bins)
    bins = gbdt.bin_dataset(tables, dense, codes, n_bins)
    cfg = TreeConfig(max_depth=3, n_bins=n_bins)
    trees, _ = gbdt.build_gbt(cfg, bins, y, np.ones_like(y), 3)
    meta, params = _spec("gbt", cfg, trees, tables)
    base = gbdt.predict(meta, params, dense, codes, route="pallas")
    pad = 256 - len(dense)
    padded = gbdt.predict(
        meta, params,
        np.concatenate([dense, np.repeat(dense[-1:], pad, 0)]),
        np.concatenate([codes, np.repeat(codes[-1:], pad, 0)]),
        route="pallas")
    np.testing.assert_array_equal(padded[:len(dense)], base)

    fb = gbdt.make_fused_inputs(tables, dense, codes, n_bins)
    import jax
    trees_np = jax.tree.map(np.asarray, params["trees"])
    packed, _ = pallas_trees.pack_ensemble(trees_np)
    kw = dict(n_trees=3, kind="gbt", loss=cfg.loss,
              learning_rate=cfg.learning_rate, max_depth=cfg.max_depth,
              n_bins=n_bins, interpret=jax.default_backend() != "tpu")
    t128 = pallas_trees.predict_ensemble(
        jnp.asarray(packed), jnp.asarray(fb.valuesT),
        jnp.asarray(fb.cuts), row_tile=128, **kw)
    t512 = pallas_trees.predict_ensemble(
        jnp.asarray(packed), jnp.asarray(fb.valuesT),
        jnp.asarray(fb.cuts), row_tile=512, **kw)
    np.testing.assert_array_equal(np.asarray(t128), np.asarray(t512))


@pytest.mark.parametrize("tree_tile", [1, 2])
def test_tree_tiles_accumulate_to_the_one_tile_score(rng, tree_tile):
    """An ensemble too large for one block (T=500 at depth 8) is tiled
    over TREES: the inner grid axis accumulates each tile's leaf sums
    before the convert. Forcing several tiles on a small forest — 3
    trees in tiles of 2 leaves a padded, empty tree slot — must give
    the one-tile score up to the f32 order of that sum."""
    n_bins = 16
    dense, codes, tables, y = _dataset(rng, n=150, n_bins=n_bins)
    bins = gbdt.bin_dataset(tables, dense, codes, n_bins)
    cfg = TreeConfig(max_depth=3, n_bins=n_bins)
    trees, _ = gbdt.build_gbt(cfg, bins, y, np.ones_like(y), 3)
    meta, params = _spec("gbt", cfg, trees, tables)
    fb = gbdt.make_fused_inputs(tables, dense, codes, n_bins)
    import jax
    packed, _ = pallas_trees.pack_ensemble(
        jax.tree.map(np.asarray, params["trees"]))
    args = (jnp.asarray(packed), jnp.asarray(fb.valuesT),
            jnp.asarray(fb.cuts))
    kw = dict(n_trees=3, kind="gbt", loss=cfg.loss,
              learning_rate=cfg.learning_rate, max_depth=cfg.max_depth,
              n_bins=n_bins, interpret=jax.default_backend() != "tpu")
    one_tile = pallas_trees.predict_ensemble(*args, **kw)
    tiled = pallas_trees.predict_ensemble(*args, tree_tile=tree_tile, **kw)
    np.testing.assert_allclose(np.asarray(tiled), np.asarray(one_tile),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(
        np.asarray(tiled)[:len(dense)],
        gbdt.predict(meta, params, dense, codes, route="xla"),
        rtol=1e-5, atol=1e-6)
