"""Fused bin-lookup + histogram kernel parity (ops/pallas_hist).

The fused kernel (`level_histograms_fused`) re-derives bin indices
in-register from raw feature values + cut boundaries instead of reading
a pre-binned int32 matrix. These tests pin the whole contract on CPU:

- the in-kernel binning rule (`bins_from_values`, also the XLA-fallback
  binning stage) matches `gbdt.bin_dataset` bit-for-bit, including NaN
  missing values and host-mapped categorical codes;
- the fused kernel's histograms (interpret mode) match the XLA
  scatter-add reference, in both default and
  SHIFU_TPU_HIST_PRECISION=highest modes;
- a full GBT build through FusedBins grows the same ensemble as the
  pre-binned path on the SAME histogram backend (cross-backend runs may
  legitimately flip `default_left` on equal-gain ties — float summation
  order — so parity is only asserted same-backend).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shifu_tpu.models import gbdt
from shifu_tpu.models.gbdt import TreeConfig
from shifu_tpu.ops.pallas_hist import (bins_from_values,
                                       level_histograms_fused)

N_BINS = 10


def _dataset(rng, n=500, cn=3, vocabs=(5, 3)):
    """Mixed numeric + categorical data with missing values, plus the
    packed bin tables: numeric cuts are per-column quantiles (+inf
    padded to n_bins-2 slots), categorical maps are posRate-style
    permutations of the low bin ids."""
    dense = rng.normal(0.0, 1.0, (n, cn)).astype(np.float32)
    dense[rng.random((n, cn)) < 0.1] = np.nan
    k = N_BINS - 2
    qs = np.linspace(0.1, 0.9, k - 2)
    cuts = np.full((k, cn), np.inf, np.float32)
    cuts[:k - 2] = np.nanquantile(dense, qs, axis=0)
    cat_orders = [rng.permutation(v).astype(np.int32) for v in vocabs]
    codes = np.stack([rng.integers(-1, v + 1, n) for v in vocabs],
                     axis=1).astype(np.int32)  # -1 and v are missing
    tables = gbdt.make_bin_tables(cuts, cat_orders, N_BINS)
    return dense, codes, tables


def test_bins_from_values_matches_bin_dataset(rng):
    """The lax reference for the kernel's in-register binning agrees
    with the host bin_dataset on every cell: numeric quantile lookups,
    NaN -> missing bin, categorical identity-cut trick (host-mapped id
    carried as a float against cuts 0.5, 1.5, ...)."""
    dense, codes, tables = _dataset(rng)
    ref = gbdt.bin_dataset(tables, dense, codes, N_BINS)        # (R, C)
    fused = gbdt.make_fused_inputs(tables, dense, codes, N_BINS)
    got = np.asarray(bins_from_values(jnp.asarray(fused.valuesT),
                                      jnp.asarray(fused.cuts), N_BINS))
    np.testing.assert_array_equal(got.T, ref)


def _scatter_ref(binsT, slot, grad, hess, n_slots, n_bins):
    """Numpy mirror of the XLA scatter in _local_level_histograms."""
    c, r = binsT.shape
    g = np.zeros((n_slots, c, n_bins), np.float32)
    h = np.zeros((n_slots, c, n_bins), np.float32)
    ok = (slot >= 0) & (slot < n_slots)
    for col in range(c):
        np.add.at(g[:, col, :], (slot[ok], binsT[col, ok]), grad[ok])
        np.add.at(h[:, col, :], (slot[ok], binsT[col, ok]), hess[ok])
    return g, h


def _fused_case(rng, n=600):
    dense, codes, tables = _dataset(rng, n=n)
    fused = gbdt.make_fused_inputs(tables, dense, codes, N_BINS)
    bins = gbdt.bin_dataset(tables, dense, codes, N_BINS)
    n_slots = 4
    slot = rng.integers(-1, n_slots + 2, n).astype(np.int32)
    grad = rng.normal(0, 1, n).astype(np.float32)
    hess = rng.uniform(0.5, 1.5, n).astype(np.float32)
    return fused, bins, slot, grad, hess, n_slots


def test_fused_kernel_matches_scatter_reference(rng):
    """level_histograms_fused (interpret mode) == scatter-add on the
    equivalent pre-binned matrix, for rows scattered across level
    slots including out-of-level (-1, >=S dump) rows."""
    fused, bins, slot, grad, hess, n_slots = _fused_case(rng)
    g0, h0 = _scatter_ref(bins.T, slot, grad, hess, n_slots, N_BINS)
    g1, h1 = level_histograms_fused(
        jnp.asarray(fused.valuesT), jnp.asarray(fused.cuts),
        jnp.asarray(slot), jnp.asarray(grad), jnp.asarray(hess),
        n_slots, N_BINS, row_tile=128, col_tile=5, interpret=True)
    np.testing.assert_allclose(np.asarray(g1), g0, rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(np.asarray(h1), h0, rtol=1e-5, atol=1e-3)


def test_fused_kernel_highest_precision(rng, monkeypatch):
    """SHIFU_TPU_HIST_PRECISION=highest switches the fused kernel to
    the f32-exact contraction (small row tile); parity with the
    scatter reference tightens to summation-order noise."""
    monkeypatch.setenv("SHIFU_TPU_HIST_PRECISION", "highest")
    fused, bins, slot, grad, hess, n_slots = _fused_case(rng)
    g0, h0 = _scatter_ref(bins.T, slot, grad, hess, n_slots, N_BINS)
    g1, h1 = level_histograms_fused(
        jnp.asarray(fused.valuesT), jnp.asarray(fused.cuts),
        jnp.asarray(slot), jnp.asarray(grad), jnp.asarray(hess),
        n_slots, N_BINS, interpret=True)
    np.testing.assert_allclose(np.asarray(g1), g0, rtol=1e-6, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h1), h0, rtol=1e-6, atol=1e-4)


def _tree_arrays(trees):
    return {k: np.asarray(v) for k, v in trees.items()}


def _assert_same_ensemble(a, b):
    for key in ("feature", "bin", "is_leaf", "default_left"):
        np.testing.assert_array_equal(a[key], b[key], err_msg=key)
    np.testing.assert_allclose(a["leaf_value"], b["leaf_value"],
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_fused_gbt_matches_prebinned_same_backend(rng, monkeypatch,
                                                  backend):
    """build_gbt fed FusedBins grows the same trees as build_gbt fed
    the pre-binned int32 matrix, holding the histogram backend fixed
    (xla scatter, or the pallas kernels in interpret mode on CPU).
    SHIFU_TPU_HIST is read at trace time, so caches are cleared around
    the env flip."""
    n, cn = 800, 5
    dense = rng.normal(0.0, 1.0, (n, cn)).astype(np.float32)
    k = N_BINS - 2
    cuts = np.quantile(dense, np.linspace(0.08, 0.92, k),
                       axis=0).astype(np.float32)
    beta = rng.normal(0, 1, cn)
    y = ((dense @ beta) > np.median(dense @ beta)).astype(np.float32)
    w = np.ones(n, np.float32)
    tables = gbdt.make_bin_tables(cuts, [], N_BINS)
    bins = gbdt.bin_dataset(tables, dense, None, N_BINS)
    fused = gbdt.make_fused_inputs(tables, dense, None, N_BINS)

    cfg = TreeConfig(max_depth=3, n_bins=N_BINS, learning_rate=0.3,
                     loss="log")
    monkeypatch.setenv("SHIFU_TPU_HIST", backend)
    jax.clear_caches()
    try:
        t_int, _ = gbdt.build_gbt(cfg, bins, y, w, n_trees=4)
        t_fused, _ = gbdt.build_gbt(cfg, fused, y, w, n_trees=4)
    finally:
        jax.clear_caches()  # don't leak the pinned backend's traces

    _assert_same_ensemble(_tree_arrays(t_int), _tree_arrays(t_fused))


# ---------------------------------------------------------------------------
# every slot count from 1 (gbdt._grow_tree calls the kernel once a level
# at what that level reads), and G and H in one stacked contraction
# ---------------------------------------------------------------------------

def _slot_case(rng, n_slots, n=700, c=5, n_bins=16):
    binsT = rng.integers(0, n_bins, (c, n)).astype(np.int32)
    slot = rng.integers(-1, n_slots + 2, n).astype(np.int32)
    grad = rng.normal(0, 1, n).astype(np.float32)
    hess = rng.uniform(0.5, 1.5, n).astype(np.float32)
    return binsT, slot, grad, hess, n_bins


@pytest.mark.parametrize("n_slots", [1, 2, 8, 64, 128])
def test_kernel_matches_scatter_at_every_level_width(rng, n_slots):
    """level_histograms_pallas (interpret mode) == the scatter-add at
    the slot counts a depth-8 build asks for, the one-slot root and
    slot counts under a sublane tile among them; rows outside the level
    (-1, the dump slot and past it) add to no cell."""
    from shifu_tpu.ops.pallas_hist import level_histograms_pallas
    binsT, slot, grad, hess, n_bins = _slot_case(rng, n_slots)
    g0, h0 = _scatter_ref(binsT, slot, grad, hess, n_slots, n_bins)
    g1, h1 = level_histograms_pallas(
        jnp.asarray(binsT), jnp.asarray(slot), jnp.asarray(grad),
        jnp.asarray(hess), n_slots, n_bins, row_tile=128, interpret=True)
    assert g1.shape == h1.shape == (n_slots, 5, n_bins)
    np.testing.assert_allclose(np.asarray(g1), g0, rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(np.asarray(h1), h0, rtol=1e-5, atol=1e-3)


def test_both_routes_agree_through_level_histograms(rng, monkeypatch):
    """`gbdt._level_histograms` as a tree build calls it, once on each
    route `SHIFU_TPU_HIST` selects — the scatter-add and the kernel at
    the tiles it derives for itself (interpret mode here) — on one
    input with rows outside the level: the same histograms."""
    binsT, slot, grad, hess, n_bins = _slot_case(rng, 8, n=5_000, c=8,
                                                 n_bins=8)
    args = [jnp.asarray(a) for a in (binsT, slot, grad, hess)]
    got = {}
    for mode in ("xla", "pallas"):
        monkeypatch.setenv("SHIFU_TPU_HIST", mode)
        got[mode] = gbdt._level_histograms(*args, 0, 8, n_bins)
    for a, b in zip(got["xla"], got["pallas"]):
        assert a.shape == (8, 8, n_bins)
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-5, atol=1e-3)
    assert float(jnp.abs(got["xla"][1]).sum()) > 0


def _two_contractions(binsT, slot, grad, hess, n_slots, n_bins, row_tile):
    """What the kernel computed before G and H shared an operand: a row
    tile at a time, the gradient-weighted and the hessian-weighted node
    one-hot each contracted against the bin one-hot, summed over tiles
    in order."""
    c, r = binsT.shape
    g = jnp.zeros((n_slots, c, n_bins), jnp.float32)
    h = jnp.zeros((n_slots, c, n_bins), jnp.float32)
    for a in range(0, r, row_tile):
        b = binsT[:, a:a + row_tile]
        onehot = (b[:, None, :] == jnp.arange(n_bins)[None, :, None]
                  ).astype(jnp.float32).reshape(c * n_bins, -1)
        node = (slot[None, a:a + row_tile]
                == jnp.arange(n_slots)[:, None]).astype(jnp.float32)
        for acc, w in ((0, grad), (1, hess)):
            part = jax.lax.dot_general(
                node * w[None, a:a + row_tile], onehot,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32
            ).reshape(n_slots, c, n_bins)
            g, h = (g + part, h) if acc == 0 else (g, h + part)
    return g, h


@pytest.mark.parametrize("n_slots", [2, 4, 8, 64])
def test_stacked_contraction_is_bitwise_two_contractions(rng, n_slots):
    """G and H ride one (2·S8, rows) operand through one contraction:
    every cell is the sum of the same products in the same order as
    with a contraction each, so the histograms are bit-equal (same row
    tile; rows a multiple of it, as the pad rows add exact zeros). The
    one-slot case is read on the chip (PERF.md, PR 27): a one-row
    operand takes the CPU backend's matrix-vector routine, which sums
    in another order than its matrix product."""
    from shifu_tpu.ops.pallas_hist import level_histograms_pallas
    binsT, slot, grad, hess, n_bins = _slot_case(rng, n_slots, n=768)
    args = [jnp.asarray(a) for a in (binsT, slot, grad, hess)]
    g0, h0 = _two_contractions(*args, n_slots, n_bins, 128)
    g1, h1 = level_histograms_pallas(*args, n_slots, n_bins, row_tile=128,
                                     interpret=True)
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g0))
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h0))


def test_tile_budget_counts_the_stacked_operand():
    """derive_tiles reckons the (2·S8, TR) stacked operand and the
    (2·S8, TC·B) output block: at the cell's shape the row tile stays
    512 at every slot count a depth-8 build asks for, and a budget the
    stacked buffers overrun halves it."""
    from shifu_tpu.ops.pallas_hist import derive_tiles
    for s in (1, 8, 64, 128, 256):
        assert derive_tiles(28, s, 64) == (512, 28)
    assert derive_tiles(28, 4096, 64)[0] < 512
