"""Fused GBT split-search kernel parity (ops/pallas_split).

`_best_splits`' XLA chain (cumsum → gain → masks → flat argmax) is the
reference; the Pallas kernel fuses the whole chain and must match it
EXACTLY on CPU (interpret mode) — including jnp.argmax's
first-occurrence tie-breaking across column tiles, the min-instances
and feature masks, the last-main-bin exclusion, and the all-masked
node resolving to flat index 0. The suite runs under the default and
`SHIFU_TPU_HIST_PRECISION=highest` knob settings (split math is pure
f32 elementwise either way; the knob gates the histogram kernel that
produces this kernel's inputs — parity must hold in both regimes).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shifu_tpu.models import gbdt
from shifu_tpu.models.gbdt import TreeConfig
from shifu_tpu.ops import pallas_split

CFG = TreeConfig(max_depth=4, n_bins=16, min_instances_per_node=2,
                 min_info_gain=0.0, reg_lambda=1.0, learning_rate=0.1,
                 loss="squared")


def _hists(rng, n, c, n_bins=16):
    g = rng.normal(size=(n, c, n_bins)).astype(np.float32)
    h = (np.abs(rng.normal(size=(n, c, n_bins))) * 3).astype(np.float32)
    return jnp.asarray(g), jnp.asarray(h)


def _xla_ref(g, h, fm, cfg=CFG):
    """The XLA chain, pinned regardless of the routing knob."""
    import os
    old = os.environ.get("SHIFU_TPU_SPLIT_FUSED")
    os.environ["SHIFU_TPU_SPLIT_FUSED"] = "xla"
    try:
        return gbdt._best_splits((g, h), cfg, fm)
    finally:
        if old is None:
            os.environ.pop("SHIFU_TPU_SPLIT_FUSED", None)
        else:
            os.environ["SHIFU_TPU_SPLIT_FUSED"] = old


def _assert_split_parity(ref, got):
    for k in ("feature", "bin", "default_left"):
        np.testing.assert_array_equal(np.asarray(ref[k]),
                                      np.asarray(got[k]), err_msg=k)
    # gains come from the identical f32 expression tree — bitwise
    np.testing.assert_array_equal(np.asarray(ref["gain"]),
                                  np.asarray(got["gain"]))
    g_ref = np.asarray(ref["g_tot"])
    h_ref = np.asarray(ref["h_tot"])
    if g_ref.ndim == 2:  # XLA path carries per-feature copies
        g_ref, h_ref = g_ref[:, 0], h_ref[:, 0]
    np.testing.assert_array_equal(g_ref, np.asarray(got["g_tot"]))
    np.testing.assert_array_equal(h_ref, np.asarray(got["h_tot"]))


@pytest.mark.parametrize("highest", [False, True])
@pytest.mark.parametrize("n,c", [(1, 1), (8, 5), (16, 33), (64, 12)])
def test_fused_matches_xla(rng, monkeypatch, n, c, highest):
    if highest:
        monkeypatch.setenv("SHIFU_TPU_HIST_PRECISION", "highest")
    g, h = _hists(rng, n, c)
    fm = jnp.asarray((rng.random(c) > 0.25).astype(np.float32))
    ref = _xla_ref(g, h, fm)
    got = pallas_split.best_splits_pallas(
        g, h, jnp.broadcast_to(fm[None, :], (n, c)),
        float(CFG.reg_lambda), float(CFG.min_instances_per_node),
        interpret=True)
    _assert_split_parity(ref, got)


def test_fused_per_node_masks(rng):
    """(N, C) per-node masks — the lockstep forest's flattened layout —
    must match running the XLA chain with the same 2-D mask."""
    n, c = 12, 9
    g, h = _hists(rng, n, c)
    mask2 = jnp.asarray((rng.random((n, c)) > 0.4).astype(np.float32))
    ref = _xla_ref(g, h, mask2)
    got = pallas_split.best_splits_pallas(
        g, h, mask2, float(CFG.reg_lambda),
        float(CFG.min_instances_per_node), interpret=True)
    _assert_split_parity(ref, got)


def test_tie_break_is_first_flat_index(rng):
    """Duplicated feature columns force exact gain ties; the kernel
    must pick the LOWEST flat feature·(B-1)+bin index — jnp.argmax's
    first-occurrence rule — even when the tie spans column tiles
    (col_tile=2 puts the duplicates in different tiles)."""
    one = rng.normal(size=(4, 1, 16)).astype(np.float32)
    oneh = (np.abs(rng.normal(size=(4, 1, 16))) * 2).astype(np.float32)
    g = jnp.asarray(np.tile(one, (1, 6, 1)))
    h = jnp.asarray(np.tile(oneh, (1, 6, 1)))
    fm = jnp.ones(6, jnp.float32)
    ref = _xla_ref(g, h, fm)
    got = pallas_split.best_splits_pallas(
        g, h, jnp.broadcast_to(fm[None, :], (4, 6)), 1.0, 2.0,
        col_tile=2, interpret=True)
    _assert_split_parity(ref, got)
    assert np.asarray(got["feature"]).max() == 0  # earliest duplicate

def test_all_masked_resolves_to_index_zero(rng):
    """Every gain -inf (all features masked) must yield flat index 0 —
    what jnp.argmax returns on an all-equal row — so downstream
    can_split (isfinite check) sees a well-defined, in-range split."""
    g, h = _hists(rng, 4, 6)
    ref = _xla_ref(g, h, jnp.zeros(6, jnp.float32))
    got = pallas_split.best_splits_pallas(
        g, h, jnp.zeros((4, 6), jnp.float32), 1.0, 2.0, col_tile=2,
        interpret=True)
    _assert_split_parity(ref, got)
    assert np.array_equal(np.asarray(got["feature"]), np.zeros(4))
    assert np.array_equal(np.asarray(got["bin"]), np.zeros(4))
    assert np.all(np.isneginf(np.asarray(got["gain"])))


def test_masked_feature_never_wins(rng):
    """Put an overwhelming gain on a masked feature: the winner must
    come from the unmasked set on both routes."""
    g, h = _hists(rng, 6, 4)
    g = g.at[:, 2, :8].add(100.0)  # feature 2 would dominate
    fm = jnp.asarray([1.0, 1.0, 0.0, 1.0], jnp.float32)
    ref = _xla_ref(g, h, fm)
    got = pallas_split.best_splits_pallas(
        g, h, jnp.broadcast_to(fm[None, :], (6, 4)), 1.0, 2.0,
        interpret=True)
    _assert_split_parity(ref, got)
    assert not np.any(np.asarray(got["feature"]) == 2)


def test_min_instances_masking(rng):
    """A high min-instances floor kills thin splits identically on
    both routes (hessian≈count when hess=1)."""
    cfg = TreeConfig(max_depth=4, n_bins=16, min_instances_per_node=40,
                     min_info_gain=0.0, reg_lambda=1.0,
                     learning_rate=0.1, loss="squared")
    g = jnp.asarray(rng.normal(size=(5, 3, 16)).astype(np.float32))
    h = jnp.asarray(np.abs(rng.normal(size=(5, 3, 16))
                           ).astype(np.float32))  # sums ≪ 40 per side
    fm = jnp.ones(3, jnp.float32)
    ref = _xla_ref(g, h, fm, cfg)
    got = pallas_split.best_splits_pallas(
        g, h, jnp.broadcast_to(fm[None, :], (5, 3)),
        float(cfg.reg_lambda), float(cfg.min_instances_per_node),
        interpret=True)
    _assert_split_parity(ref, got)


def test_split_fused_mode_routing(monkeypatch):
    monkeypatch.setenv("SHIFU_TPU_SPLIT_FUSED", "pallas")
    assert pallas_split.split_fused_mode() == "pallas"
    monkeypatch.setenv("SHIFU_TPU_SPLIT_FUSED", "xla")
    assert pallas_split.split_fused_mode() == "xla"
    monkeypatch.setenv("SHIFU_TPU_SPLIT_FUSED", "auto")
    expect = "pallas" if jax.default_backend() == "tpu" else "xla"
    assert pallas_split.split_fused_mode() == expect


def test_build_tree_via_fused_route_matches_xla(rng, monkeypatch):
    """End-to-end: a whole build_tree through SHIFU_TPU_SPLIT_FUSED=
    pallas (interpret on CPU) grows the identical tree. Caches are
    cleared between routes — the knob is read at trace time, so a
    stale jit entry would silently reuse the other route."""
    bins = rng.integers(0, 15, size=(1500, 6)).astype(np.int32)
    y = (bins[:, 0] >= 7).astype(np.float32)
    cfg = TreeConfig(max_depth=3, n_bins=16)
    args = (jnp.asarray(bins.T), jnp.asarray(-y),
            jnp.asarray(np.ones_like(y)), jnp.ones(6, jnp.float32))
    monkeypatch.setenv("SHIFU_TPU_SPLIT_FUSED", "xla")
    jax.clear_caches()
    ref = gbdt.build_tree(cfg, *args)
    monkeypatch.setenv("SHIFU_TPU_SPLIT_FUSED", "pallas")
    jax.clear_caches()
    got = gbdt.build_tree(cfg, *args)
    jax.clear_caches()  # don't leak pallas-route traces to other tests
    for k in ref:
        np.testing.assert_array_equal(np.asarray(ref[k]),
                                      np.asarray(got[k]), err_msg=k)


@pytest.mark.parametrize("node_tile", [1, 2, 3])
def test_node_tiles_search_each_node_on_its_own(rng, node_tile):
    """A flattened lockstep-forest level (T·N nodes) is more than one
    block holds, so the grid tiles the NODE axis too, outside the
    column axis the running argmax walks. Several node tiles — with a
    node count that does not divide into them, so the last tile is
    padding — give each node the one-tile answer exactly."""
    g, h = _hists(rng, 5, 6)
    fm = jnp.asarray([1, 1, 0, 1, 1, 1], jnp.float32)
    ref = _xla_ref(g, h, fm)
    got = pallas_split.best_splits_pallas(
        g, h, jnp.broadcast_to(fm[None, :], (5, 6)), 1.0, 2.0,
        col_tile=2, node_tile=node_tile, interpret=True)
    _assert_split_parity(ref, got)
