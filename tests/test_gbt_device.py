"""GBT on-device state tiers and lockstep bagging.

Pins the PR-12 contracts: (1) the resident row-state tier of
`build_gbt_streaming` grows the SAME ensemble as the host-numpy tier —
and does it with ZERO device→host syncs inside a level and at most one
per boosting round, asserted via the pipeline `host_syncs` counter,
not eyeballed; (2) lockstep bagged boosting (`build_gbt_bagged`)
matches per-bag sequential `build_gbt` including per-bag early stop;
(3) the early-stop val metric is the shared `_val_error` on every
builder, so decisions can't diverge on metric arithmetic.

Parity notes: tree STRUCTURE (feature/bin/is_leaf/default_left) is
exact. Leaf values/gains are allclose at f32-ulp tolerances — the
resident tier computes the log-loss sigmoid with jax.nn.sigmoid where
the host tier uses numpy exp, and the lockstep build stacks per-bag
scatters that XLA may reassociate differently from the single-tree
build. Squared-loss gradients are the same f32 expression on both
tiers, so streaming parity there is bitwise.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shifu_tpu.data.pipeline import drain_stage_timers
from shifu_tpu.models import gbdt
from shifu_tpu.models.gbdt import TreeConfig


def _case(rng, n=900, c=7, n_bins=16, miss=0.05):
    bins = rng.integers(0, n_bins - 1, size=(n, c)).astype(np.int32)
    bins[rng.random((n, c)) < miss] = n_bins - 1
    y = (bins[:, 0] >= (n_bins - 1) // 2).astype(np.float32)
    flip = rng.random(n) < 0.1
    return bins, np.where(flip, 1 - y, y).astype(np.float32)


def _cfg(loss="squared", depth=3):
    return TreeConfig(max_depth=depth, n_bins=16,
                      min_instances_per_node=2, min_info_gain=0.0,
                      reg_lambda=1.0, learning_rate=0.1, loss=loss)


def _assert_tree_parity(a, b, leaf_rtol=1e-5, leaf_atol=1e-6):
    for k in ("feature", "bin", "is_leaf", "default_left"):
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                      err_msg=k)
    for k in ("leaf_value", "gain"):
        np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]),
                                   rtol=leaf_rtol, atol=leaf_atol,
                                   err_msg=k)


@pytest.mark.parametrize("loss", ["squared", "log"])
def test_resident_streaming_matches_host_tier(rng, monkeypatch, loss):
    bins, y = _case(rng)
    w = np.ones_like(y)
    cfg = _cfg(loss)
    monkeypatch.setenv("SHIFU_TPU_GBT_RESIDENT_STATE", "0")
    host_t, host_e = gbdt.build_gbt_streaming(
        cfg, bins, y, w, 4, valid_rate=0.2, chunk_rows=256,
        early_stop_window=3)
    monkeypatch.setenv("SHIFU_TPU_GBT_RESIDENT_STATE", "1")
    res_t, res_e = gbdt.build_gbt_streaming(
        cfg, bins, y, w, 4, valid_rate=0.2, chunk_rows=256,
        early_stop_window=3)
    # log loss: sigmoid ulp noise in the gradients amplifies through
    # the gain's sum-of-squares — wider (still f32-ulp-scale) band
    tol = dict(leaf_rtol=1e-4, leaf_atol=5e-5) if loss == "log" else {}
    _assert_tree_parity(host_t, res_t, **tol)
    assert len(host_e) == len(res_e)
    np.testing.assert_allclose(host_e, res_e, rtol=1e-6, atol=1e-7)


def test_resident_sync_budget(rng, monkeypatch):
    """THE acceptance gate: a resident-tier level performs zero
    device→host syncs and a round at most one — counted by the
    pipeline host_syncs counter that host_fetch bumps. A no-val build
    must show ZERO syncs total; with validation, exactly one per
    round (the early-stop decision fetch)."""
    bins, y = _case(rng, n=700)
    w = np.ones_like(y)
    cfg = _cfg()
    monkeypatch.setenv("SHIFU_TPU_GBT_RESIDENT_STATE", "1")

    drain_stage_timers()
    gbdt.build_gbt_streaming(cfg, bins, y, w, 3, chunk_rows=256)
    t = drain_stage_timers()
    assert t.get("host_syncs", 0) == 0, t

    n_rounds = 4
    gbdt.build_gbt_streaming(cfg, bins, y, w, n_rounds, valid_rate=0.2,
                             chunk_rows=256)
    t = drain_stage_timers()
    assert t.get("host_syncs", 0) == n_rounds, t

    # the host tier, same workload, syncs per chunk per level — the
    # counter is what makes the resident win falsifiable
    monkeypatch.setenv("SHIFU_TPU_GBT_RESIDENT_STATE", "0")
    gbdt.build_gbt_streaming(cfg, bins, y, w, n_rounds, valid_rate=0.2,
                             chunk_rows=256)
    t = drain_stage_timers()
    assert t.get("host_syncs", 0) > n_rounds * (cfg.max_depth + 1), t


def test_resident_state_mode_gating(monkeypatch):
    monkeypatch.setenv("SHIFU_TPU_GBT_RESIDENT_STATE", "1")
    assert gbdt.gbt_resident_state_mode(10 ** 12)
    monkeypatch.setenv("SHIFU_TPU_GBT_RESIDENT_STATE", "0")
    assert not gbdt.gbt_resident_state_mode(10)
    monkeypatch.setenv("SHIFU_TPU_GBT_RESIDENT_STATE", "auto")
    monkeypatch.setenv("SHIFU_TPU_GBT_STATE_BUDGET_MB", "1")
    # 24 B/train row + 12 B/val row vs a 1 MiB budget
    assert gbdt.gbt_resident_state_mode(40_000)
    assert not gbdt.gbt_resident_state_mode(40_000, 20_000)
    assert not gbdt.gbt_resident_state_mode(50_000)


def test_resident_resume_matches_host_tier(rng, monkeypatch):
    """init_trees (continuous training) warms predictions device-side
    on the resident tier — the appended trees must match the host
    tier's."""
    bins, y = _case(rng, n=600)
    w = np.ones_like(y)
    cfg = _cfg()
    monkeypatch.setenv("SHIFU_TPU_GBT_RESIDENT_STATE", "0")
    first, _ = gbdt.build_gbt_streaming(cfg, bins, y, w, 2,
                                        chunk_rows=256)
    host_t, _ = gbdt.build_gbt_streaming(cfg, bins, y, w, 2,
                                         chunk_rows=256,
                                         init_trees=first)
    monkeypatch.setenv("SHIFU_TPU_GBT_RESIDENT_STATE", "1")
    res_t, _ = gbdt.build_gbt_streaming(cfg, bins, y, w, 2,
                                        chunk_rows=256,
                                        init_trees=first)
    _assert_tree_parity(host_t, res_t)


def test_lockstep_bagged_matches_sequential(rng):
    """Each bag of the lockstep build must equal a sequential
    build_gbt run with the same bag weights — including per-bag early
    stop (different bags may stop at different rounds; each keeps
    exactly what its sequential loop would have kept)."""
    bins, y = _case(rng)
    vb, vy = _case(rng, n=300)
    cfg = _cfg()
    w_T = rng.poisson(1.0, size=(3, len(y))).astype(np.float32)
    w_T[w_T.sum(axis=1) == 0] = 1.0
    bag_out = gbdt.build_gbt_bagged(cfg, bins, y, w_T, 5,
                                    val_data=(vb, vy),
                                    early_stop_window=2)
    for b in range(3):
        seq_t, seq_e = gbdt.build_gbt(cfg, bins, y, w_T[b], 5,
                                      val_data=(vb, vy),
                                      early_stop_window=2)
        lk_t, lk_e = bag_out[b]
        assert seq_t["feature"].shape == lk_t["feature"].shape
        _assert_tree_parity(seq_t, lk_t)
        assert len(seq_e) == len(lk_e)
        np.testing.assert_allclose(seq_e, lk_e, rtol=1e-6, atol=1e-7)


def test_lockstep_bagged_noval_scan_matches_sequential(rng, monkeypatch):
    """The no-val lockstep path scans rounds device-side (grouped by
    SHIFU_TPU_GBT_SCAN_GROUP like build_gbt) — same ensembles."""
    monkeypatch.setenv("SHIFU_TPU_GBT_SCAN_GROUP", "2")
    bins, y = _case(rng, n=600)
    cfg = _cfg()
    w_T = rng.poisson(1.0, size=(2, len(y))).astype(np.float32)
    w_T[w_T.sum(axis=1) == 0] = 1.0
    bag_out = gbdt.build_gbt_bagged(cfg, bins, y, w_T, 3)
    for b in range(2):
        seq_t, _ = gbdt.build_gbt(cfg, bins, y, w_T[b], 3)
        _assert_tree_parity(seq_t, bag_out[b][0])


def test_forest_return_nodes_land_on_leaves(rng):
    """build_forest(return_nodes=True): per-tree landing nodes gather
    the same leaf values as the predict_trees re-walk — the lockstep
    boosting update's one-gather shortcut."""
    bins, y = _case(rng, n=800, c=5)
    cfg = _cfg(depth=4)
    binsT = jnp.asarray(bins.T)
    grad_T = jnp.asarray(np.stack([-y, -y * 0.5]).astype(np.float32))
    hess_T = jnp.ones_like(grad_T)
    masks = jnp.ones((2, 5), jnp.float32)
    trees, node_T = gbdt.build_forest(cfg, binsT, grad_T, hess_T, masks,
                                      return_nodes=True)
    via_nodes = np.asarray(jax.vmap(
        lambda tr, n: tr["leaf_value"][n])(trees, node_T))
    via_walk = np.asarray(gbdt.predict_trees(trees, binsT,
                                             cfg.max_depth, cfg.n_bins))
    np.testing.assert_array_equal(via_nodes, via_walk)


def test_val_metric_aligned_across_builders(rng, monkeypatch):
    """Satellite gate: build_gbt and both streaming tiers report the
    same per-round val errors (one shared _val_error definition) —
    early-stop decisions cannot diverge between builders."""
    bins, y = _case(rng, n=800)
    w = np.ones_like(y)
    cfg = _cfg(loss="log")
    n_val = 160
    n_train = len(y) - n_val
    # build_gbt takes an explicit (val_bins, val_y) split; streaming
    # takes the trailing fraction of the same layout
    _, res_e = gbdt.build_gbt(
        cfg, bins[:n_train], y[:n_train], w[:n_train], 3,
        val_data=(bins[n_train:], y[n_train:]))
    monkeypatch.setenv("SHIFU_TPU_GBT_RESIDENT_STATE", "0")
    _, host_e = gbdt.build_gbt_streaming(cfg, bins, y, w, 3,
                                         chunk_rows=256, n_val=n_val)
    monkeypatch.setenv("SHIFU_TPU_GBT_RESIDENT_STATE", "1")
    _, dev_e = gbdt.build_gbt_streaming(cfg, bins, y, w, 3,
                                        chunk_rows=256, n_val=n_val)
    np.testing.assert_allclose(res_e, host_e, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(host_e, dev_e, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# one growth loop (gbdt._grow_tree): every level's histogram pass is
# sized to what the level reads, whoever calls it; the whole tree in
# one jit is BITWISE what the level-by-level dispatches build; the
# resident single-chunk tier still pays one dispatch a tree
# ---------------------------------------------------------------------------

def _tree_bitwise(a, b, ctx=""):
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                      err_msg=f"{ctx}:{k}")


def _tree_case(rng, n=700, c=6):
    bins, y = _case(rng, n=n, c=c)
    binsT = jnp.asarray(np.ascontiguousarray(bins.T))
    grad = jnp.asarray(-(y - 0.5))
    return binsT, grad, jnp.ones_like(grad), jnp.ones(c, jnp.float32)


def _forest_case(rng, n=600, c=5):
    bins, y = _case(rng, n=n, c=c)
    binsT = jnp.asarray(np.ascontiguousarray(bins.T))
    grad_T = jnp.asarray(np.stack([-y, -y * 0.5, y - 0.3])
                         .astype(np.float32))
    masks = jnp.asarray((rng.random((3, c)) > 0.3).astype(np.float32))
    return binsT, grad_T, jnp.ones_like(grad_T), masks


def _kernel_calls(monkeypatch):
    """Spy on the one place every builder's histogram pass goes
    through: (slots, columns) of each call, in trace order."""
    calls = []
    real = gbdt._local_level_histograms

    def spy(binsT, slot, grad, hess, n_level_nodes, n_bins):
        calls.append((n_level_nodes, binsT.shape[0]))
        return real(binsT, slot, grad, hess, n_level_nodes, n_bins)

    monkeypatch.setattr(gbdt, "_local_level_histograms", spy)
    return calls


def _slots_a_level(depth, subtract):
    """What each level reads: with sibling subtraction the root and
    then one child of every parent, 1, 1, 2, ..., 2^(D-1); without, the whole
    level, 1, 2, ..., 2^D."""
    if subtract:
        return [1] + [2 ** (d - 1) for d in range(1, depth + 1)]
    return [2 ** d for d in range(depth + 1)]


@pytest.mark.parametrize("depth", [1, 2, 3, 5])
@pytest.mark.parametrize("subtract", [False, True])
@pytest.mark.parametrize("builder", ["tree", "forest"])
def test_levels_ask_the_kernel_for_their_own_slots(rng, monkeypatch, builder,
                                                   depth, subtract):
    """No level's pass is wider than what it reads: the kernel is asked
    for 1, 1, 2, ... slots, never for the 2^max_depth of the deepest
    level, and the leaf level for the one column its totals read; the
    lockstep forest (a vmap of the same pass over trees) asks for the
    same as the single tree."""
    case, build = {"tree": (_tree_case, gbdt.build_tree),
                   "forest": (_forest_case, gbdt.build_forest)}[builder]
    args = case(rng)
    calls = _kernel_calls(monkeypatch)
    jax.eval_shape(lambda *a: build.__wrapped__(
        _cfg(depth=depth), *a, subtract=subtract), *args)
    assert [s for s, _ in calls] == _slots_a_level(depth, subtract)
    assert [c for _, c in calls] == \
        [args[0].shape[0]] * depth + [gbdt._LEAF_COLUMNS]


@pytest.mark.parametrize("depth", [1, 2, 3, 5])
@pytest.mark.parametrize("subtract", [False, True])
def test_one_jit_tree_bitwise_matches_level_dispatches(
        rng, monkeypatch, depth, subtract):
    """build_tree (every level unrolled into one jit) against the
    resident streaming tier's level-by-level dispatches on one chunk
    (_stream_level_chunk a level, the leaf level on every column): the
    same histograms scatter in the same row order, so the whole tree
    and the landing nodes are bit-equal — not allclose, equal."""
    binsT, grad, hess, fm = _tree_case(rng)
    cfg = _cfg(depth=depth)
    monkeypatch.setenv("SHIFU_TPU_HIST_SUBTRACT", "1" if subtract else "0")
    t_jit, n_jit = gbdt.build_tree(cfg, binsT, grad, hess, fm,
                                   subtract=subtract, return_nodes=True)
    node_state = [jnp.zeros(binsT.shape[1], jnp.int32)]
    t_lvl = gbdt._build_tree_streaming_device(
        cfg, lambda ci: binsT, 1, node_state, [grad], [hess], fm, None)
    _tree_bitwise(t_jit, t_lvl, f"d{depth}/sub{subtract}")
    # the level dispatches route lazily: the last level's routing is
    # the caller's, as build_gbt_streaming does it
    n_lvl, _ = gbdt._route_level(cfg, t_lvl, binsT, node_state[0], depth - 1,
                                 jnp.zeros(2 ** (depth - 1), jnp.int32))
    np.testing.assert_array_equal(np.asarray(n_jit), np.asarray(n_lvl))


@pytest.mark.parametrize("subtract", [False, True])
def test_forest_bitwise_matches_single_trees(rng, subtract):
    """build_forest's lockstep trees against build_tree a tree, each
    with its own gradients and feature mask: on the XLA scatter path
    the vmapped pass adds the same cells in the same order."""
    binsT, grad_T, hess_T, masks = _forest_case(rng)
    cfg = _cfg(depth=3)
    trees, node_T = gbdt.build_forest(cfg, binsT, grad_T, hess_T, masks,
                                      subtract=subtract, return_nodes=True)
    for t in range(3):
        one, node = gbdt.build_tree(cfg, binsT, grad_T[t], hess_T[t],
                                    masks[t], subtract=subtract,
                                    return_nodes=True)
        _tree_bitwise(jax.tree.map(lambda a, t=t: a[t], trees), one,
                      f"tree{t}/sub{subtract}")
        np.testing.assert_array_equal(np.asarray(node_T[t]),
                                      np.asarray(node))


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("subtract", [False, True])
def test_leaf_level_on_its_columns_gives_the_same_leaves(
        rng, monkeypatch, backend, subtract):
    """_final_leaves reads column 0's histogram alone, so the leaf
    level's pass runs on _LEAF_COLUMNS columns: the leaf values are
    bitwise those of a pass over every column, through the scatter and
    through the kernel (interpret mode)."""
    binsT, grad, hess, fm = _tree_case(rng, n=500)
    cfg = _cfg(depth=3)
    monkeypatch.setenv("SHIFU_TPU_HIST", backend)

    def build(columns):
        monkeypatch.setattr(gbdt, "_LEAF_COLUMNS", columns)
        jax.clear_caches()      # both knobs are read when traced
        return gbdt.build_tree(cfg, binsT, grad, hess, fm,
                               subtract=subtract)

    cut, whole = build(1), build(binsT.shape[0])
    jax.clear_caches()
    assert np.asarray(cut["is_leaf"])[-2 ** 3:].all()
    _tree_bitwise(cut, whole, f"{backend}/sub{subtract}")


def test_resident_single_chunk_one_dispatch_per_tree(rng, monkeypatch):
    """THE dispatch gate, with no knob set: a single-chunk resident
    build launches ONE device computation per tree (counted by the
    pipeline tree_build_dispatches counter) and keeps the resident
    zero-host-sync contract; the same rows in two chunks pay one
    dispatch a chunk a level, for the same splits in the first tree."""
    bins, y = _case(rng, n=800)
    w = np.ones_like(y)
    cfg = _cfg(loss="log")
    n_trees = 3
    monkeypatch.setenv("SHIFU_TPU_GBT_RESIDENT_STATE", "1")

    def run(chunk_rows):
        drain_stage_timers()
        trees, _ = gbdt.build_gbt_streaming(cfg, bins, y, w, n_trees,
                                            chunk_rows=chunk_rows)
        return trees, drain_stage_timers()

    t_one, timers_one = run(1 << 20)
    t_two, timers_two = run(400)
    assert timers_one.get("tree_build_dispatches") == n_trees, timers_one
    assert timers_two.get("tree_build_dispatches") == \
        n_trees * 2 * (cfg.max_depth + 1), timers_two
    assert timers_one.get("host_syncs", 0) == 0, timers_one
    assert timers_two.get("host_syncs", 0) == 0, timers_two
    for k in ("feature", "bin", "is_leaf", "default_left"):
        np.testing.assert_array_equal(t_one[k][0], t_two[k][0], err_msg=k)
