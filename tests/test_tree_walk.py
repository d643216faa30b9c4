"""The walk of a finished tree (`gbdt._walk_tree`, under `predict_trees`,
`leaf_indices`, `tree_validation.shifu_validate` and the streaming tier's
`_predict_chunk`) against the per-row gather walk it replaced, kept here
as the oracle in numpy: the same landing node for every row and the same
leaf-value bits, whatever form each level takes (`gbdt.walk_levels`).
Callers that walk one tree at a time (`gbdt._predict_tree`) give the
bits of `predict_trees` over a stack of that one tree."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shifu_tpu.models import gbdt, tree_validation
from shifu_tpu.models.gbdt import FusedBins, TreeConfig

N_BINS = 16


def _oracle_nodes(trees, binsT, max_depth, n_bins):
    """(T, R) landing nodes by per-row gathers, level by level: a row
    stays at a leaf or at a node of feature < 0; a missing bin follows
    the node's default direction, any other goes left when <= the
    split's bin."""
    binsT = np.asarray(binsT)
    rows = np.arange(binsT.shape[1])
    out = []
    for t in range(np.asarray(trees["feature"]).shape[0]):
        tree = {k: np.asarray(v[t]) for k, v in trees.items()}
        node = np.zeros(binsT.shape[1], np.int64)
        for _ in range(max_depth):
            feat = tree["feature"][node]
            row_bin = binsT[np.maximum(feat, 0), rows]
            go_left = np.where(row_bin == n_bins - 1,
                               tree["default_left"][node],
                               row_bin <= tree["bin"][node])
            node = np.where(tree["is_leaf"][node] | (feat < 0), node,
                            2 * node + np.where(go_left, 1, 2))
        out.append(node)
    return np.stack(out).astype(np.int32)


def _oracle_scores(trees, nodes):
    values = np.asarray(trees["leaf_value"])
    return np.stack([values[t][nodes[t]] for t in range(len(nodes))])


def _random_trees(rng, n_trees, depth, n_cols, leaf_share=0.15):
    """Trees as a builder lays them out, with what an imported or
    resumed tree may also hold: leaves at every depth, leaves whose
    feature is >= 0, interior nodes of feature -1, the last level all
    leaves; -0.0 among the leaf values."""
    n = 2 ** (depth + 1) - 1
    feature = rng.integers(0, n_cols, (n_trees, n)).astype(np.int32)
    feature[rng.random((n_trees, n)) < 0.05] = -1
    is_leaf = rng.random((n_trees, n)) < leaf_share
    is_leaf[:, 2 ** depth - 1:] = True
    # the left spine splits, and the spine's right child at every depth
    # is a leaf: rows stop at each depth
    spine = 2 ** np.arange(depth) - 1
    feature[:, spine] = rng.integers(0, n_cols, (n_trees, depth))
    is_leaf[:, spine] = False
    is_leaf[:, spine[1:] + 1] = True
    values = rng.standard_normal((n_trees, n)).astype(np.float32)
    values[rng.random((n_trees, n)) < 0.05] = -0.0
    return {"feature": jnp.asarray(feature),
            "bin": jnp.asarray(rng.integers(0, N_BINS - 1, (n_trees, n)),
                               jnp.int32),
            "default_left": jnp.asarray(rng.random((n_trees, n)) < 0.5),
            "is_leaf": jnp.asarray(is_leaf),
            "leaf_value": jnp.asarray(values)}


def _random_bins(rng, n_rows, n_cols, miss=0.1):
    binsT = rng.integers(0, N_BINS - 1, (n_cols, n_rows)).astype(np.int32)
    binsT[rng.random((n_cols, n_rows)) < miss] = N_BINS - 1
    return binsT


def _rng(name):
    """A generator of the test's own: the session's `rng` hands each test
    whatever state the tests before it on its worker left."""
    return np.random.default_rng(sum(map(ord, name)))


def _bits(x):
    return np.asarray(x, np.float32).view(np.int32)


# the depth at which a walk's last level is wider than the select form
# takes, and its leaf-value table too: both forms in one walk
MIXED_DEPTH = int(math.log2(gbdt._WALK_SELECT_SLOTS)) + 2

WALK_CASES = {
    # name: (trees, depth, rows, columns)
    "one_tree": (1, 4, 1000, 5),
    "five_trees_vmap": (5, 5, 777, 6),
    "shallow_ragged": (5, 2, 129, 3),
    "depth8_deep_leaves": (1, 8, 1500, 7),
    "fused_bins": (5, 4, 901, 4),
    "mixed_forms": (1, MIXED_DEPTH, 300, 4),
}


def _case_trees_and_bins(case):
    rng = _rng(case)
    n_trees, depth, n_rows, n_cols = WALK_CASES[case]
    leaf_share = 0.02 if case == "mixed_forms" else 0.15
    trees = _random_trees(rng, n_trees, depth, n_cols, leaf_share)
    return trees, _random_bins(rng, n_rows, n_cols), depth


def _fused(binsT):
    """Raw values whose cuts give back `binsT`: value = bin, cut k at
    k + 0.5, NaN for the missing bin."""
    values = np.where(binsT == N_BINS - 1, np.nan, binsT.astype(np.float32))
    cuts = np.broadcast_to(np.arange(N_BINS - 2, dtype=np.float32) + 0.5,
                           (binsT.shape[0], N_BINS - 2))
    return FusedBins(jnp.asarray(values), jnp.asarray(cuts))


@pytest.mark.parametrize("case", sorted(WALK_CASES))
def test_walk_lands_where_the_gather_walk_lands(case):
    trees, binsT, depth = _case_trees_and_bins(case)
    walked = _fused(binsT) if case == "fused_bins" else jnp.asarray(binsT)
    want = _oracle_nodes(trees, binsT, depth, N_BINS)
    got = np.asarray(gbdt.leaf_indices(trees, walked, depth, N_BINS))
    np.testing.assert_array_equal(got, want)
    scores = gbdt.predict_trees(trees, walked, depth, N_BINS)
    np.testing.assert_array_equal(_bits(scores),
                                  _bits(_oracle_scores(trees, want)))
    # rows stop at several depths
    depths = np.floor(np.log2(want + 1)).astype(int)
    assert len(np.unique(depths)) >= min(depth, 3)
    levels = gbdt.walk_levels(depth)
    if case == "mixed_forms":
        assert levels == {"walk_select_levels": depth - 1,
                          "walk_gather_levels": 1}
        assert (depths == depth).any()
    else:
        assert levels == {"walk_select_levels": depth,
                          "walk_gather_levels": 0}


@pytest.mark.parametrize("case", sorted(WALK_CASES))
def test_one_tree_walk_gives_the_stack_of_ones_bits(case):
    """`gbdt._predict_tree`, one tree walked on the rows' own (R,)
    vectors, as `shifu_validate`'s `lax.map` and the streaming tier's
    `_predict_chunk` call it: bit for bit what `predict_trees` gives for
    a stack of that one tree, in every walk case (fused inputs binned
    by `predict_trees` itself)."""
    trees, binsT, depth = _case_trees_and_bins(case)
    walked = _fused(binsT) if case == "fused_bins" else jnp.asarray(binsT)
    cfg = TreeConfig(max_depth=depth, n_bins=N_BINS)
    one = jax.jit(gbdt._predict_tree, static_argnums=(2, 3))
    for t in range(np.asarray(trees["feature"]).shape[0]):
        tree = jax.tree.map(lambda a: a[t], trees)
        stacked = gbdt.predict_trees(jax.tree.map(lambda a: a[None], tree),
                                     walked, depth, N_BINS)[0]
        np.testing.assert_array_equal(
            _bits(one(tree, jnp.asarray(binsT), depth, N_BINS)),
            _bits(stacked))
        # the streaming tier's chunk takes int bins of any width
        chunk = gbdt._predict_chunk(cfg, tree,
                                    jnp.asarray(binsT, jnp.int16))
        np.testing.assert_array_equal(_bits(chunk), _bits(stacked))


@pytest.mark.parametrize("group", [5, 4, 2, 1])
def test_shifu_validate_gives_the_gather_walks_scores_and_errors(group):
    """A round's validation program: each tree's leaf values added at the
    learning rate into its ensemble's raw scores and each ensemble's
    error, bit for bit what the same arithmetic gives on the oracle's
    scores and on `predict_trees` over a stack of each one tree, for
    groups of 1, 2, 4 and 5 trees."""
    rng = _rng(f"validate{group}")
    depth, n_rows, n_cols = 5, 1000, 6
    cfg = TreeConfig(max_depth=depth, n_bins=N_BINS, learning_rate=0.1,
                     loss="log")
    trees = _random_trees(rng, group, depth, n_cols)
    binsT = _random_bins(rng, n_rows, n_cols)
    vraw = jnp.asarray(rng.standard_normal((group, n_rows)), jnp.float32)
    vy = jnp.asarray(rng.random(n_rows) < 0.5, jnp.float32)
    vw = jnp.ones(n_rows, jnp.float32)
    got_raw, got_err = tree_validation.shifu_validate(
        cfg, trees, jnp.asarray(binsT), vraw, vy, vw)
    score = jnp.asarray(_oracle_scores(
        trees, _oracle_nodes(trees, binsT, depth, N_BINS)))
    want_raw = jax.jit(lambda v, s: v + cfg.learning_rate * s)(vraw, score)
    want_err = gbdt._val_error(want_raw, vy, vw, cfg.loss)
    np.testing.assert_array_equal(_bits(got_raw), _bits(want_raw))
    np.testing.assert_array_equal(_bits(got_err), _bits(want_err))
    stacks = jnp.concatenate([gbdt.predict_trees(
        jax.tree.map(lambda a: a[t:t + 1], trees), jnp.asarray(binsT), depth,
        N_BINS) for t in range(group)])
    np.testing.assert_array_equal(_bits(stacks), _bits(score))


@pytest.mark.parametrize("builder", ["build_gbt", "build_gbt_bagged"])
def test_the_validate_span_says_how_the_walk_goes(tmp_path, monkeypatch,
                                                  builder):
    """`train.validate` carries the levels a walk of the job's trees takes
    in each form, in both builders that validate."""
    from shifu_tpu.obs import trace as obs_trace
    rng = _rng(builder)
    monkeypatch.setenv("SHIFU_TPU_TRACE", "1")
    monkeypatch.delenv("SHIFU_TPU_TRACE_DIR", raising=False)
    cfg = TreeConfig(max_depth=3, n_bins=N_BINS, learning_rate=0.1,
                     loss="log")
    bins = _random_bins(rng, 600, 4).T.copy()
    y = (bins[:, 0] >= N_BINS // 2).astype(np.float32)
    val = (_random_bins(rng, 200, 4).T.copy(), y[:200])
    w = np.ones_like(y)
    with obs_trace.trace_run(str(tmp_path / "set"), "train") as run:
        if builder == "build_gbt":
            gbdt.build_gbt(cfg, bins, y, w, 2, val_data=val)
        else:
            gbdt.build_gbt_bagged(cfg, bins, y, w, 2, 3, 1.0, True, 7,
                                  val_data=val)
        spans = [s for s in run.tracer.spans()
                 if s["name"] == "train.validate"]
    assert len(spans) == 2
    for s in spans:
        assert s["args"]["walk_select_levels"] == 3
        assert s["args"]["walk_gather_levels"] == 0
