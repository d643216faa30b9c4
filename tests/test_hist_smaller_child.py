"""Sibling subtraction builds each parent's SMALLER child and derives the
larger (`gbdt._smaller_child`, `_route_level`, `_subtract_siblings`):
what a float32 histogram over very many rows is off by lands on the
sibling that can bear it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shifu_tpu.models import gbdt

B, C = 8, 3
CFG = gbdt.TreeConfig(max_depth=3, n_bins=B)


def _tree(feature, split_bin, default_left):
    tree = dict(gbdt._empty_tree(CFG))
    ids = jnp.arange(1, 3)                       # the two nodes of level 1
    tree["feature"] = tree["feature"].at[ids].set(jnp.asarray(feature))
    tree["bin"] = tree["bin"].at[ids].set(jnp.asarray(split_bin))
    tree["default_left"] = tree["default_left"].at[ids].set(
        jnp.asarray(default_left))
    return tree


def test_smaller_child_is_the_side_of_less_hessian_by_hand():
    h = np.zeros((2, C, B), np.float32)
    # node 0 splits column 1 after bin 2: bins 0..2 hold 1+1+1, bins 3..6
    # hold 4x2, the missing bin (7) holds 6 and goes left: 9 against 8
    h[0, 1] = [1, 1, 1, 2, 2, 2, 2, 6]
    # node 1 splits column 2 after bin 0, missing goes right: 5 against 9
    h[1, 2] = [5, 1, 1, 1, 1, 1, 1, 3]
    h[:, 0] = 100.0                              # another column: not read
    tree = _tree([1, 2], [2, 0], [True, False])
    side = gbdt._smaller_child(CFG, tree, jnp.asarray(h), depth=1)
    assert side.tolist() == [1, 0]               # right is smaller, then left
    tree = _tree([1, 2], [2, 0], [False, False])
    assert gbdt._smaller_child(CFG, tree, jnp.asarray(h), 1).tolist() == [0, 0]


@pytest.mark.parametrize("side", [(0, 0, 0, 0), (1, 1, 1, 1), (1, 0, 0, 1),
                                  (0, 1, 1, 0)])
def test_built_child_and_its_sibling_make_the_whole_level(side):
    """Whichever child of a parent goes through the kernel, the half
    pass over the rows routing picked for it and the subtraction
    together give the level's direct histograms; a parent that did not
    split has no children."""
    rng = np.random.default_rng(5)
    r, offset, n_level = 3000, 3, 4              # level 2: nodes 3..6
    binsT = jnp.asarray(rng.integers(0, B, (C, r)).astype(np.int32))
    node = jnp.asarray(rng.integers(offset - 4, offset + n_level, r)
                       .astype(np.int32))     # some rows parked above, or -1
    grad = jnp.asarray(rng.normal(size=r).astype(np.float32))
    hess = jnp.asarray(rng.random(r).astype(np.float32))
    tree = dict(gbdt._empty_tree(CFG))
    ids = offset + jnp.arange(n_level)
    tree["feature"] = tree["feature"].at[ids].set(jnp.asarray([2, 0, -1, 1]))
    tree["bin"] = tree["bin"].at[ids].set(jnp.asarray([3, 1, 0, 5]))
    tree["default_left"] = tree["default_left"].at[ids].set(
        jnp.asarray([True, False, False, True]))
    side = jnp.asarray(side, jnp.int32)
    child, half = gbdt._route_level(CFG, tree, binsT, node, 2, side)
    prev_g, prev_h = gbdt._level_histograms(binsT, node, grad, hess, offset,
                                            n_level, B)
    direct = gbdt._level_histograms(binsT, child, grad, hess, 7, 8, B)
    gb, hb = gbdt._level_histograms(binsT, half, grad, hess, 7, 4, B)
    g, h = gbdt._subtract_siblings(prev_g, prev_h, gb, hb,
                                   tree["feature"][3:7] >= 0, side)
    np.testing.assert_allclose(g, direct[0], rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(h, direct[1], rtol=1e-5, atol=1e-4)
    assert not np.asarray(g[4:6]).any()          # node 5 is a leaf
    assert np.asarray(h[:4]).any(axis=(1, 2)).all()   # the others' hold rows


def test_an_offset_on_the_parent_lands_on_the_larger_child():
    """The point of it: whatever the parent's histogram is off by is
    handed on whole to the child that is derived, so that has to be the
    larger one; the smaller is built from its own rows and is exact."""
    g_small = np.full((1, C, B), 2.0, np.float32)         # a child of few rows
    g_large = np.full((1, C, B), 5000.0, np.float32)
    parent = g_small + g_large + 7.0                      # off by 7 a bin
    split = jnp.ones(1, bool)
    left_is_small = jnp.zeros(1, jnp.int32)
    g, _ = gbdt._subtract_siblings(
        jnp.asarray(parent), jnp.asarray(parent), jnp.asarray(g_small),
        jnp.asarray(g_small), split, left_is_small)
    np.testing.assert_array_equal(g[0], g_small[0])       # built: exact
    np.testing.assert_allclose(g[1], g_large[0] + 7.0)    # 0.14% off


@pytest.mark.parametrize("tier", ["host", "device"])
def test_the_streaming_tiers_build_the_smaller_child_too(tier):
    """One subtraction rule for every builder: on gradients whose sums
    round (so that parent − built depends on WHICH child was built) the
    level-by-level tiers of `build_gbt_streaming` grow `build_tree`'s
    tree bit for bit, from one chunk that adds its rows in the same
    order."""
    from shifu_tpu.parallel import mesh as mesh_mod
    rng = np.random.default_rng(11)
    r, cfg = 5000, gbdt.TreeConfig(max_depth=4, n_bins=B)
    bins = rng.integers(0, B, (r, C)).astype(np.int32)
    grad = (rng.normal(size=r) + (bins[:, 1] > 4)).astype(np.float32)
    hess = rng.random(r).astype(np.float32) + 0.1
    fm = np.ones(C, np.float32)
    want = gbdt.build_tree(cfg, jnp.asarray(bins.T), jnp.asarray(grad),
                           jnp.asarray(hess), jnp.asarray(fm), subtract=True)
    if tier == "host":
        got = gbdt._build_tree_streaming(
            cfg, bins, lambda a, b: (grad[a:b], hess[a:b]),
            np.zeros(r, np.int32), r, fm,
            mesh_mod.make_mesh(n_data=1, devices=jax.devices()[:1]), None)
    else:
        got = gbdt._build_tree_streaming_device(
            cfg, lambda ci: jnp.asarray(bins.T), 1,
            [jnp.zeros(r, jnp.int32)], [jnp.asarray(grad)],
            [jnp.asarray(hess)], fm, None)
    assert (np.asarray(want["feature"]) >= 0).sum() >= 8
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]),
                                      err_msg=k)
