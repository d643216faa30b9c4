"""Pallas TPU kernel: per-(node, feature, bin) gradient histograms.

The tree-growth hot loop (reference: `dt/DTWorker.java:914-944` — every
worker walks each instance to its node and bumps per-(node,feature,bin)
stat arrays on CPU; here `models/gbdt._level_histograms`) is, on TPU,
bound by how the scatter-add is expressed. XLA lowers
`zeros.at[node, col, bin].add(g)` to a serialized scatter (measured
~10 s for 2M×128 at depth 6 on v5e); this kernel reformulates the
histogram as an MXU contraction instead:

    hist[n, c, b] = Σ_r onehot_node[n, r] · g[r] · onehot_bin[c, b, r]

Layout is everything on TPU: arrays pad their minor dim to the 128
lane width and the second-minor to 8 sublanes, so a row-major
(R, C) bin matrix with few features (HIGGS: C=28) or an (R, 1) column
vector wastes 4–128× HBM. Every per-row operand therefore arrives
TRANSPOSED — rows on the LANE axis:

- `binsT`: (C, R) int — negligible padding for any feature count;
- `packed`: (8, R) f32 carrying [slot, grad, hess] in its first three
  sublane rows (slot as exact-integer float).

Per grid step the kernel expands a (TC, TR) bins tile to its bin
one-hot in a bin-major sublane layout (sublane l = b·TC + c, built
with the dedicated `tpu.repeat` op — no 128-alignment constraint on
TC, verified on v5e at TC=28), builds the node one-hot by comparing
the slot lane-vector against a sublane iota, and contracts the two on
the MXU with an NT matmul. G and H share ONE contraction: the node
one-hot weighted by grad in sublanes [0, S8) and by hess in
[S8, 2·S8) (S8 = S slots padded to the sublane tile) is a single
(2·S8, TR) operand against the (L, TR) bin one-hot, so the bin
one-hot — the side whose streaming through the 128-wide array sets
the kernel's time — passes once for both, not once each. The
(2·S8, L) output block accumulates across row tiles (TPU grids iterate
sequentially, so `+=` into the same output block is the standard
reduction pattern); the two (S, C, B) histograms are sliced and
reassembled by cheap XLA reshape/transpose outside the kernel.

The kernel meets every slot count from 1: `models/gbdt._grow_tree`
calls it once a level at what that level reads (under sibling
subtraction 1, 1, 2, ..., 2^(max_depth-1)), and the leaf level on
one column only (`gbdt._leaf_columns`). Up to S = 64 a call is one
pass of the array's width; the row tile is 512 whatever S is, so a
slot's sum adds the same products in the same order at every S.

`interpret=True` runs the same kernel on CPU for tests (conftest's
8-device CPU mesh), keeping kernel parity checkable without a chip.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from shifu_tpu.config.environment import knob_int, knob_str

__all__ = ["level_histograms_pallas", "level_histograms_fused",
           "bins_from_values"]


def _slots8(n_slots: int) -> int:
    """Slots padded to the sublane tile: where H starts in the stacked
    operand and in the kernel's output block."""
    return -(-n_slots // 8) * 8


def _hist_body(binsT, pk, out_ref, i, *, n_slots: int, n_bins: int,
               precision):
    """Shared contraction body: a (TC, TR) int32 bins tile + the (8, TR)
    packed [slot, grad, hess] block → accumulate the (2·S8, B·TC)
    output block, G in sublanes [0, S), H in [S8, S8 + S). `i` is the
    row-tile (reduction) grid index."""
    slot = pk[0:1, :].astype(jnp.int32)         # (1, TR)
    grad = pk[1:2, :]
    hess = pk[2:3, :]

    tc, tr = binsT.shape
    # bin one-hot, transposed + bin-major (sublane l = b·TC + c):
    # tpu.repeat stacks B copies of the (TC, TR) tile along sublanes
    # (rows l % TC; off the chip its implementation is jnp.tile)
    rep = pltpu.repeat(binsT, n_bins, axis=0)
    lane_bin = jax.lax.broadcasted_iota(
        jnp.int32, (tc * n_bins, tr), 0) // tc
    onehot_bins = (rep == lane_bin).astype(jnp.float32)   # (B·TC, TR)

    # node one-hot weighted by grad in the first S8 sublanes and by
    # hess in the next S8: ONE (2·S8, TR) operand, so the bin one-hot
    # streams through the MXU once for both (at S ≤ 64 the two together
    # fill no more of the 128-wide array than either did). A row's
    # dump slot (n_slots; rows not in this level) matches at most a pad
    # sublane, which the caller drops.
    s8 = _slots8(n_slots)
    sub = jax.lax.broadcasted_iota(jnp.int32, (2 * s8, tr), 0)
    is_h = sub >= s8
    node_onehot = (slot == jnp.where(is_h, sub - s8, sub)
                   ).astype(jnp.float32)
    ghw = node_onehot * jnp.where(is_h, hess, grad)       # (2·S8, TR)

    # MXU NT contraction over rows: (2·S8, TR) · (B·TC, TR)ᵀ
    part = jax.lax.dot_general(
        ghw, onehot_bins, (((1,), (1,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32)

    @pl.when(i == 0)
    def _init():
        out_ref[:, :] = part

    @pl.when(i > 0)
    def _accum():
        out_ref[:, :] += part


def _hist_kernel(binsT_ref, pk_ref, out_ref, *,
                 n_slots: int, n_bins: int, precision):
    # grid = (col_tiles, row_tiles): the ROW (reduction) dimension is
    # innermost, so each output block's revisits are consecutive grid
    # steps — required for the += accumulation pattern on TPU (the
    # output VMEM buffer is flushed between non-consecutive revisits)
    i = pl.program_id(1)
    _hist_body(binsT_ref[:, :], pk_ref[:, :], out_ref, i,
               n_slots=n_slots, n_bins=n_bins, precision=precision)


def _fused_hist_kernel(valT_ref, cuts_ref, pk_ref, out_ref, *,
                       n_slots: int, n_bins: int, n_cuts: int, precision):
    """Fused bin-lookup + histogram: the (TC, TR) tile arrives as RAW
    feature values (NaN = missing) plus each column's ascending cut
    boundaries, and the bin index is derived in-register — GBT level
    building never materializes the (C, R) bin-index matrix in HBM.

    Bin semantics match gbdt.bin_dataset / ops.stats.bin_index_numeric:
    bin = #(v >= cut) clamped to n_bins-2 (cuts are +inf padded, so
    pad entries never count for finite v), NaN → the shared missing
    bin n_bins-1. The per-cut compare loop is statically unrolled
    (n_cuts ≤ n_bins-1 iterations of one VPU compare+add each)."""
    i = pl.program_id(1)
    valT = valT_ref[:, :]                       # (TC, TR) f32
    cuts = cuts_ref[:, :]                       # (TC, K) f32
    bins = jnp.zeros(valT.shape, jnp.int32)
    for k in range(n_cuts):
        bins += (valT >= cuts[:, k:k + 1]).astype(jnp.int32)
    bins = jnp.minimum(bins, n_bins - 2)
    bins = jnp.where(jnp.isnan(valT), n_bins - 1, bins)
    _hist_body(bins, pk_ref[:, :], out_ref, i,
               n_slots=n_slots, n_bins=n_bins, precision=precision)


def bins_from_values(valuesT: jax.Array, cutsT: jax.Array,
                     n_bins: int) -> jax.Array:
    """Lax reference for the fused kernel's in-register binning: (C, R)
    raw values + (C, K) ascending per-column cuts → (C, R) int32 bins,
    NaN → n_bins-1. Also the binning stage of the XLA fallback."""
    def one(v, c):
        # side="right" counts boundaries <= v — identical to #(v >= c)
        return jnp.searchsorted(c, v, side="right").astype(jnp.int32)
    b = jnp.minimum(jax.vmap(one)(valuesT, cutsT), n_bins - 2)
    return jnp.where(jnp.isnan(valuesT), n_bins - 1, b)


def _vmem_budget() -> int:
    """SHIFU_TPU_HIST_VMEM_MB in bytes: handed to the compiler as the
    kernel's VMEM limit AND what `derive_tiles` sizes the tiles from."""
    return max(1, knob_int("SHIFU_TPU_HIST_VMEM_MB")) << 20


def derive_tiles(n_cols: int, n_slots: int, n_bins: int,
                 highest: bool = False):
    """(row_tile, col_tile) sized to the VMEM budget instead of fixed
    constants, so the kernel holds across n_bins ∈ {16, 64, 256+} and
    wide tables without running out of VMEM (the reference's analogous
    memory-sized batching is DTMaster.java:369-506 todo-node batches).

    Per grid step the kernel keeps, in 4-byte lanes (S8 = slots padded
    to a sublane multiple; G and H are stacked, 2·S8 sublanes):
      bin one-hot (B·TC, TR) plus the repeated bins and the bin iota it
        is compared from — 3 × the dominant buffer;
      bins tile (TC, TR) and packed (8, TR), double-buffered;
      sublane iota, node one-hot, weights, their product —
        4 × (2·S8, TR);
      out block double-buffered + the partial — 3 × (2·S8, TC·B).
    The tiles fill at most 3/4 of the budget (the rest is the
    compiler's own scratch); the budget itself defaults to 64 MiB of
    the v5e's 128 MiB VMEM and is also the limit the kernel is compiled
    with. SHIFU_TPU_HIST_VMEM_MB overrides for other parts.

    Blocks must stay (8, 128)-aligned unless they span the whole axis:
    the row tile is a multiple of 128 lanes, and a column tile below
    the whole column axis is a multiple of `unit` columns so that both
    it and its TC·B output lanes are aligned."""
    budget = _vmem_budget() * 3 // 4
    s8 = _slots8(n_slots)
    unit = max(8, 128 // math.gcd(128, n_bins))
    col_tile = n_cols if n_cols <= 128 else 128
    row_tile = 128 if highest else 512

    def usage(ct, rt):
        return 4 * (3 * n_bins * ct * rt
                    + 2 * ct * rt + 2 * 8 * rt
                    + 4 * 2 * s8 * rt
                    + 3 * 2 * s8 * ct * n_bins)

    while usage(col_tile, row_tile) > budget and row_tile > 128:
        row_tile //= 2
    while usage(col_tile, row_tile) > budget and col_tile > unit:
        col_tile = max(unit, (col_tile // 2) // unit * unit)
    return row_tile, col_tile


def _packed_rows(slot, grad, hess, n_slots: int, pad_r: int):
    """The per-row vectors as one (8, R + pad_r) f32 block [slot, grad,
    hess, 0...]: a bare (R,) or (R, 1) operand would lane-pad to 128×
    its size in HBM. Out-of-level and pad rows carry the dump slot
    n_slots."""
    r = slot.shape[0]
    slot = jnp.where((slot >= 0) & (slot < n_slots), slot, n_slots)
    packed = jnp.zeros((8, r + pad_r), jnp.float32)
    packed = packed.at[0, :r].set(slot.astype(jnp.float32))
    packed = packed.at[1, :r].set(grad.astype(jnp.float32))
    packed = packed.at[2, :r].set(hess.astype(jnp.float32))
    if pad_r:
        packed = packed.at[0, r:].set(float(n_slots))
    return packed


def _hist_call(kern, operands, in_specs, n_slots: int, lanes: int,
               grid, interpret: bool, name: str):
    """The pallas_call both kernels share: one (2·S8, lanes) output
    block a column tile, revisited along the row (reduction) axis."""
    rows = 2 * _slots8(n_slots)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((rows, lanes), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((rows, grid[0] * lanes),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_budget()),
        interpret=interpret,
        name=name,
    )(*operands)


def _split_gh(out, n_slots: int, n_bins: int, col_tile: int, c: int):
    """The kernel's (2·S8, [tile j][bin b][col c]) output → the G and
    the H histogram, each (S, C, B); cheap XLA slices and transposes on
    the small output."""
    def reassemble(a):
        a = a.reshape(n_slots, -1, n_bins, col_tile)
        a = a.transpose(0, 1, 3, 2).reshape(n_slots, -1, n_bins)
        return a[:, :c, :]

    s8 = _slots8(n_slots)
    return reassemble(out[:n_slots]), reassemble(out[s8:s8 + n_slots])


def level_histograms_pallas(binsT: jax.Array, slot: jax.Array,
                            grad: jax.Array, hess: jax.Array,
                            n_slots: int, n_bins: int,
                            row_tile: int = 0, col_tile: int = 0,
                            interpret: bool = False):
    """(C, R) transposed bins + (R,) slot/grad/hess → two
    (n_slots, C, n_bins) histograms. `slot` values outside
    [0, n_slots) are ignored (rows belonging to finished nodes /
    padding). Tile sizes derive from the VMEM budget by default
    (`derive_tiles`); pass row_tile/col_tile > 0 to pin them.

    Precision: the MXU multiplies in bf16 by default — the one-hot
    side is exact, so only grad/hess values truncate (~0.3% relative
    per element, statistically inert for split gains; measured on
    v5e: 0.10 s vs the XLA scatter's 10.1 s at 2M×128 depth-6).
    SHIFU_TPU_HIST_PRECISION=highest switches to the f32-exact
    multi-pass algorithm, which starts from the smallest aligned row
    tile (128 lanes) to leave VMEM for its extra passes."""
    highest = (knob_str("SHIFU_TPU_HIST_PRECISION", "") or
               "").lower() == "highest"
    d_row, d_col = derive_tiles(binsT.shape[0], n_slots, n_bins, highest)
    row_tile = row_tile or d_row
    col_tile = col_tile or d_col
    return _level_histograms_pallas(binsT, slot, grad, hess, n_slots,
                                    n_bins, row_tile, col_tile, interpret,
                                    highest)


@functools.partial(jax.jit, static_argnames=("n_slots", "n_bins",
                                             "row_tile", "col_tile",
                                             "interpret", "highest"))
def _level_histograms_pallas(binsT, slot, grad, hess,
                             n_slots: int, n_bins: int,
                             row_tile: int, col_tile: int,
                             interpret: bool, highest: bool):
    precision = jax.lax.Precision.HIGHEST if highest \
        else jax.lax.Precision.DEFAULT
    c, r = binsT.shape
    row_tile = min(row_tile, max(8, r))
    col_tile = min(col_tile, max(1, c))
    pad_r = (-r) % row_tile
    pad_c = (-c) % col_tile
    packed = _packed_rows(slot, grad, hess, n_slots, pad_r)
    if pad_r or pad_c:
        binsT = jnp.pad(binsT, ((0, pad_c), (0, pad_r)))
    cp, rp = binsT.shape
    # (col_tiles, row_tiles) — rows innermost; see _hist_kernel
    grid = (cp // col_tile, rp // row_tile)
    kern = functools.partial(_hist_kernel, n_slots=n_slots, n_bins=n_bins,
                             precision=precision)
    out = _hist_call(
        kern, (binsT.astype(jnp.int32), packed),
        [pl.BlockSpec((col_tile, row_tile), lambda j, i: (j, i)),
         pl.BlockSpec((8, row_tile), lambda j, i: (0, i))],
        n_slots, col_tile * n_bins, grid, interpret,
        "shifu_level_histograms")
    return _split_gh(out, n_slots, n_bins, col_tile, c)


def level_histograms_fused(valuesT: jax.Array, cutsT: jax.Array,
                           slot: jax.Array, grad: jax.Array,
                           hess: jax.Array, n_slots: int, n_bins: int,
                           row_tile: int = 0, col_tile: int = 0,
                           interpret: bool = False):
    """Fused variant of `level_histograms_pallas`: takes (C, R) RAW
    transposed feature values (NaN = missing) and each column's (C, K)
    ascending cut boundaries (+inf padded; categorical columns use
    identity boundaries over host-mapped codes — gbdt.make_fused_inputs
    packs both), and performs the bin lookup inside the kernel so the
    (C, R) int32 bin matrix never exists in HBM. Same tiling, output
    layout, and precision contract as the int-bins kernel."""
    highest = (knob_str("SHIFU_TPU_HIST_PRECISION", "") or
               "").lower() == "highest"
    d_row, d_col = derive_tiles(valuesT.shape[0], n_slots, n_bins, highest)
    row_tile = row_tile or d_row
    col_tile = col_tile or d_col
    return _level_histograms_fused(valuesT, cutsT, slot, grad, hess,
                                   n_slots, n_bins, row_tile, col_tile,
                                   interpret, highest)


@functools.partial(jax.jit, static_argnames=("n_slots", "n_bins",
                                             "row_tile", "col_tile",
                                             "interpret", "highest"))
def _level_histograms_fused(valuesT, cutsT, slot, grad, hess,
                            n_slots: int, n_bins: int,
                            row_tile: int, col_tile: int,
                            interpret: bool, highest: bool):
    precision = jax.lax.Precision.HIGHEST if highest \
        else jax.lax.Precision.DEFAULT
    c, r = valuesT.shape
    n_cuts = cutsT.shape[1]
    row_tile = min(row_tile, max(8, r))
    col_tile = min(col_tile, max(1, c))
    pad_r = (-r) % row_tile
    pad_c = (-c) % col_tile
    packed = _packed_rows(slot, grad, hess, n_slots, pad_r)
    if pad_r or pad_c:
        # pad columns bin to 0 and are sliced off after reassembly;
        # pad cut rows are +inf so they never count for any value
        valuesT = jnp.pad(valuesT, ((0, pad_c), (0, pad_r)))
        cutsT = jnp.pad(cutsT, ((0, pad_c), (0, 0)),
                        constant_values=jnp.inf)
    cp, rp = valuesT.shape
    grid = (cp // col_tile, rp // row_tile)
    kern = functools.partial(_fused_hist_kernel, n_slots=n_slots,
                             n_bins=n_bins, n_cuts=n_cuts,
                             precision=precision)
    out = _hist_call(
        kern, (valuesT.astype(jnp.float32), cutsT.astype(jnp.float32),
               packed),
        [pl.BlockSpec((col_tile, row_tile), lambda j, i: (j, i)),
         pl.BlockSpec((col_tile, n_cuts), lambda j, i: (j, 0)),
         pl.BlockSpec((8, row_tile), lambda j, i: (0, i))],
        n_slots, col_tile * n_bins, grid, interpret,
        "shifu_level_histograms_fused")
    return _split_gh(out, n_slots, n_bins, col_tile, c)
