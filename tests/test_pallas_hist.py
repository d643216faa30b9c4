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


def _two_contractions(binsT, slot, grad, hess, n_slots, n_bins, row_tile,
                      precision=None):
    """What the kernel computed before G and H shared an operand: a row
    tile at a time, the gradient-weighted and the hessian-weighted node
    one-hot each contracted against the bin one-hot, summed over tiles
    in ascending row order. The node one-hot has the kernel's sublane
    padding (slots up to a multiple of 8), so a one-slot level takes
    the CPU backend's matrix product as the kernel does and not its
    matrix-vector routine, which sums in another order. For the same
    reason the rows are padded as the kernel pads them, to whole row
    tiles of the tile it takes, with slot -1 and no weight: a last tile
    of fewer rows is a narrower product, which the CPU backend also sums
    in another order."""
    c, r = binsT.shape
    row_tile = min(row_tile, max(8, r))
    pad = -r % row_tile
    if pad:
        binsT = jnp.pad(binsT, ((0, 0), (0, pad)))
        slot = jnp.pad(slot, (0, pad), constant_values=-1)
        grad, hess = (jnp.pad(v, (0, pad)) for v in (grad, hess))
        r += pad
    s8 = -(-n_slots // 8) * 8
    g = jnp.zeros((s8, c, n_bins), jnp.float32)
    h = jnp.zeros((s8, c, n_bins), jnp.float32)
    for a in range(0, r, row_tile):
        b = binsT[:, a:a + row_tile]
        onehot = (b[:, None, :] == jnp.arange(n_bins)[None, :, None]
                  ).astype(jnp.float32).reshape(c * n_bins, -1)
        node = (slot[None, a:a + row_tile]
                == jnp.arange(s8)[:, None]).astype(jnp.float32)
        for acc, w in ((0, grad), (1, hess)):
            part = jax.lax.dot_general(
                node * w[None, a:a + row_tile], onehot,
                (((1,), (1,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32
            ).reshape(s8, c, n_bins)
            g, h = (g + part, h) if acc == 0 else (g, h + part)
    return g[:n_slots], h[:n_slots]


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
    """derive_tiles reckons the (2·S8, TR) stacked operand, the
    (2·S8, TC·B) output block, and a grid step's eight bins tiles and
    three (8, TR) blocks of slot, gradient and hessian, double-buffered
    (0.9 MB more than a tile and a packed block a step): at the cell's
    shape the row tile stays
    512 at every slot count a depth-8 build asks for, and a budget the
    stacked buffers overrun halves it."""
    from shifu_tpu.ops.pallas_hist import derive_tiles
    for s in (1, 8, 64, 128, 256):
        assert derive_tiles(28, s, 64) == (512, 28)
    assert derive_tiles(28, 4096, 64)[0] < 512


# ---------------------------------------------------------------------------
# slot, gradient and hessian reach the kernel as three dense (tiles, TR)
# views and a grid step covers eight row tiles, contracted one after the
# other: same products, same order, whatever the rows
# ---------------------------------------------------------------------------

_TILE = 128
# a whole number of eight-tile steps; whole tiles but no whole step;
# no whole tile; fewer tiles than one step; fewer rows than one tile
_ROWS = {"blocks": 16 * _TILE, "tiles": 11 * _TILE, "ragged": 9 * _TILE + 77,
         "short": 3 * _TILE, "tiny": 100}
_DENSE_CASES = (
    [("plain", s, r) for s in (1, 8, 64, 128) for r in _ROWS]
    + [("highest", s, r) for s in (1, 64) for r in ("blocks", "ragged")]
    + [("fused", s, r) for s in (1, 8, 128)
       for r in ("blocks", "tiles", "ragged", "tiny")]
    + [("vmap", s, r) for s in (1, 8, 64)
       for r in ("blocks", "tiles", "ragged", "short")])


@pytest.mark.parametrize("form,n_slots,rows", _DENSE_CASES)
def test_dense_row_operands_keep_the_tile_ordered_sums(rng, monkeypatch,
                                                       form, n_slots, rows):
    """G and H of the dense-operand kernel (interpret mode) are bit-equal
    to a row tile at a time contracted and added in ascending row order:
    at slot counts under, at and over a sublane tile and the array's
    width, for rows that fill whole eight-tile grid steps, whole tiles
    only (the last step's bins block hangs over the matrix's end), no
    whole tile, fewer tiles than a step and fewer rows than a tile (pad
    rows carry slot -1 and add exact zeros); with the
    f32-exact contraction (`highest`: the row tile it starts from); for
    the fused values-and-cuts kernel; and under `vmap` over three trees'
    row state with the bin matrix shared."""
    from shifu_tpu.ops.pallas_hist import level_histograms_pallas
    n, c, n_bins = _ROWS[rows], 5, 16
    binsT = rng.integers(0, n_bins, (c, n)).astype(np.int32)
    slot = rng.integers(-1, n_slots + 2, (3, n)).astype(np.int32)
    grad = rng.normal(0, 1, (3, n)).astype(np.float32)
    hess = rng.uniform(0.5, 1.5, (3, n)).astype(np.float32)
    binsT, slot, grad, hess = (jnp.asarray(a)
                               for a in (binsT, slot, grad, hess))
    precision, tiles = None, {"row_tile": _TILE}
    if form == "highest":
        monkeypatch.setenv("SHIFU_TPU_HIST_PRECISION", "highest")
        precision, tiles = jax.lax.Precision.HIGHEST, {}  # derives 128

    def kernel(s_, g_, h_):
        if form == "fused":
            # value b + 0.5 against cuts 1, 2, ...: bin b, no NaN
            cuts = jnp.broadcast_to(
                jnp.arange(1, n_bins - 1, dtype=jnp.float32),
                (c, n_bins - 2))
            return level_histograms_fused(
                binsT.astype(jnp.float32) + 0.5, cuts, s_, g_, h_, n_slots,
                n_bins, interpret=True, **tiles)
        return level_histograms_pallas(binsT, s_, g_, h_, n_slots, n_bins,
                                       interpret=True, **tiles)

    if form == "fused":
        # the in-kernel lookup clamps a value's bin to n_bins - 2
        binsT = jnp.minimum(binsT, n_bins - 2)
    trees = range(3) if form == "vmap" else range(1)
    if form == "vmap":
        got_g, got_h = jax.vmap(kernel)(slot, grad, hess)
    else:
        got_g, got_h = (a[None] for a in kernel(slot[0], grad[0], hess[0]))
    for t in trees:
        want_g, want_h = _two_contractions(binsT, slot[t], grad[t], hess[t],
                                           n_slots, n_bins, _TILE, precision)
        np.testing.assert_array_equal(np.asarray(got_g[t]),
                                      np.asarray(want_g))
        np.testing.assert_array_equal(np.asarray(got_h[t]),
                                      np.asarray(want_h))
    assert float(jnp.abs(got_h).sum()) > 0


def test_dense_row_operands_through_level_histograms_on_the_mesh(
        rng, monkeypatch):
    """`gbdt._level_histograms` on the rig's eight-device data mesh, the
    kernel forced on (interpret mode): each device views its own 1,250
    rows as tiles inside the mapped function (no whole tile's worth: the
    three vectors are padded shard by shard), and the
    one `psum` adds the eight partial histograms. Gradients and hessians
    are small whole numbers, so every sum is exact in any order and the
    result is the scatter-add's bit for bit."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from shifu_tpu.parallel import mesh as mesh_mod
    monkeypatch.setenv("SHIFU_TPU_HIST", "pallas")
    mesh = mesh_mod.make_mesh(n_data=8, devices=jax.devices()[:8])
    n, c, n_bins, n_slots = 10_000, 6, 16, 8
    binsT = rng.integers(0, n_bins, (c, n)).astype(np.int32)
    node = rng.integers(-1, 2 * n_slots + 8, n).astype(np.int32)
    grad = rng.integers(-8, 9, n).astype(np.float32)
    hess = rng.integers(1, 5, n).astype(np.float32)
    by_row = NamedSharding(mesh, P("data"))
    args = (jax.device_put(binsT, NamedSharding(mesh, P(None, "data"))),
            jax.device_put(node, by_row), jax.device_put(grad, by_row),
            jax.device_put(hess, by_row))
    offset = n_slots - 1                       # the level of 8 nodes
    g1, h1 = jax.jit(lambda b, nd, g, h: gbdt._level_histograms(
        b, nd, g, h, offset, n_slots, n_bins, mesh=mesh))(*args)
    g0, h0 = _scatter_ref(binsT, node - offset, grad, hess, n_slots, n_bins)
    np.testing.assert_array_equal(np.asarray(g1), g0)
    np.testing.assert_array_equal(np.asarray(h1), h0)
    assert np.abs(g0).sum() > 0


# ---------------------------------------------------------------------------
# a lockstep group's (T, R) row state handed to the kernel whole: the trees
# of a pass share every row tile's bin one-hot, and each tree's histograms
# are its own call's bit for bit
# ---------------------------------------------------------------------------

def _group_case(rng, n_trees, n_slots, n, c, n_bins):
    binsT = rng.integers(0, n_bins, (c, n)).astype(np.int32)
    # -1, the dump slot S and past it: rows of no slot of this level
    slot = rng.integers(-1, n_slots + 2, (n_trees, n)).astype(np.int32)
    grad = rng.normal(0, 1, (n_trees, n)).astype(np.float32)
    hess = rng.uniform(0.5, 1.5, (n_trees, n)).astype(np.float32)
    return [jnp.asarray(a) for a in (binsT, slot, grad, hess)]


def _kernel_of(form, n_bins):
    """The bins kernel, or the fused one on values b + 0.5 against cuts
    1, 2, ... (bin b, clamped to n_bins - 2 as the fused lookup does)."""
    from shifu_tpu.ops.pallas_hist import level_histograms_pallas

    def kernel(binsT, slot, grad, hess, n_slots, **kw):
        if form == "fused":
            c = binsT.shape[0]
            cuts = jnp.broadcast_to(
                jnp.arange(1, n_bins - 1, dtype=jnp.float32),
                (c, n_bins - 2))
            return level_histograms_fused(
                jnp.minimum(binsT, n_bins - 2).astype(jnp.float32) + 0.5,
                cuts, slot, grad, hess, n_slots, n_bins, interpret=True, **kw)
        return level_histograms_pallas(binsT, slot, grad, hess, n_slots,
                                       n_bins, interpret=True, **kw)

    return kernel


def _assert_each_tree_its_own_call(kernel, binsT, slot, grad, hess, n_slots,
                                   **kw):
    got_g, got_h = kernel(binsT, slot, grad, hess, n_slots, **kw)
    assert got_g.shape == got_h.shape == (
        slot.shape[0], n_slots, binsT.shape[0], got_g.shape[-1])
    for t in range(slot.shape[0]):
        want_g, want_h = kernel(binsT, slot[t], grad[t], hess[t], n_slots,
                                **kw)
        np.testing.assert_array_equal(np.asarray(got_g[t]),
                                      np.asarray(want_g), err_msg=f"G {t}")
        np.testing.assert_array_equal(np.asarray(got_h[t]),
                                      np.asarray(want_h), err_msg=f"H {t}")
    assert float(jnp.abs(got_h).sum()) > 0


@pytest.mark.parametrize("form", ["bins", "fused"])
@pytest.mark.parametrize("n_slots", [1, 2, 8, 16, 32, 64, 128])
@pytest.mark.parametrize("n_trees", [1, 2, 4, 5])
def test_grouped_pass_is_each_trees_own_call(form, n_trees, n_slots):
    """The grouped entry (interpret mode) against per-tree calls stacked
    by hand, bit for bit: one tree, a pass of all the trees, the trees
    over more passes than one (five at 16 and 32 slots: a last pass of
    padded trees), one tree a pass from 64 slots up; rows that fill no
    whole row tile, rows at -1, at the dump slot and past it."""
    binsT, slot, grad, hess = _group_case(
        np.random.default_rng((n_trees, n_slots)), n_trees, n_slots,
        9 * _TILE + 77, 5, 16)
    _assert_each_tree_its_own_call(_kernel_of(form, 16), binsT, slot, grad,
                                   hess, n_slots, row_tile=_TILE)


def test_trees_a_pass_fill_one_pass_of_the_array_at_one_tile():
    """Trees a pass: as many as the 128 rows of one MXU pass hold, G and
    H of each (2·Tp·S8 = 128 at the edge: 8 trees at 8 slots, 4 at 16,
    2 at 32), one from 64 slots up, never more than the group; and none
    that would change the row tile: a budget under which the stacked
    operand of more trees would shrink it keeps one tree a pass."""
    from shifu_tpu.ops.pallas_hist import derive_tiles, trees_per_pass
    c, b = 28, 64
    cases = {(8, 8): 8, (9, 8): 8, (4, 1): 4, (4, 8): 4, (4, 16): 4,
             (5, 16): 4, (8, 16): 4, (4, 32): 2, (3, 32): 2, (4, 64): 1,
             (4, 128): 1, (4, 256): 1, (1, 8): 1, (2, 2): 2}
    for (t, s), tp in cases.items():
        assert trees_per_pass(t, s, c, b) == tp, (t, s)
        s8 = -(-s // 8) * 8
        assert 2 * tp * s8 <= 128 or tp == 1
        assert derive_tiles(c, tp * s8, b) == derive_tiles(c, s8, b)
    assert trees_per_pass(4, 8, 1, b) == 4        # the leaf level's column
    assert trees_per_pass(4, 8, c, b, highest=True) == 4


def test_a_budget_that_would_shrink_the_tile_keeps_one_tree_a_pass(
        monkeypatch):
    """At 16 MiB of VMEM the one-tree tile at 28 columns x 64 bins stays
    (512, 28), and a stacked operand of two trees would halve the row
    tile: the grouped entry then takes a tree a pass, and every tree is
    its own call's bit for bit at the tile both derive."""
    from shifu_tpu.ops.pallas_hist import derive_tiles, trees_per_pass
    monkeypatch.setenv("SHIFU_TPU_HIST_VMEM_MB", "16")
    assert derive_tiles(28, 8, 64) == (512, 28)
    assert derive_tiles(28, 16, 64)[0] < 512
    assert trees_per_pass(4, 8, 28, 64) == 1
    binsT, slot, grad, hess = _group_case(np.random.default_rng(41), 3, 8,
                                          2 * 512 + 77, 28, 64)
    _assert_each_tree_its_own_call(_kernel_of("bins", 64), binsT, slot, grad,
                                   hess, 8)
    monkeypatch.setenv("SHIFU_TPU_HIST_VMEM_MB", "64")
    assert trees_per_pass(4, 8, 28, 64) == 4


def test_nine_trees_take_two_passes_of_eight():
    """Eight trees fill a pass at 8 slots; a ninth takes a second pass,
    padded with trees of no rows, inside the same call."""
    binsT, slot, grad, hess = _group_case(np.random.default_rng(41), 9, 8,
                                          3 * _TILE + 5, 4, 16)
    _assert_each_tree_its_own_call(_kernel_of("bins", 16), binsT, slot, grad,
                                   hess, 8, row_tile=_TILE)


def test_grouped_pass_keeps_the_f32_exact_tile(monkeypatch):
    """SHIFU_TPU_HIST_PRECISION=highest: one tree derives the 128-lane
    row tile, and so does a pass of three trees; each tree's histograms
    are its own call's bit for bit."""
    from shifu_tpu.ops.pallas_hist import trees_per_pass
    monkeypatch.setenv("SHIFU_TPU_HIST_PRECISION", "highest")
    assert trees_per_pass(3, 8, 5, 16) == 3
    binsT, slot, grad, hess = _group_case(np.random.default_rng(41), 3, 8,
                                          9 * _TILE + 77, 5, 16)
    _assert_each_tree_its_own_call(_kernel_of("bins", 16), binsT, slot, grad,
                                   hess, 8)
