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
- slot (int32), grad and hess (f32): each (R,) vector viewed as
  (R / TR, TR), one row tile a sublane, so no sublane or lane is
  padding and nothing row-sized is written for the kernel's sake.

A grid step covers EIGHT row tiles: a (TC, 8·TR) block of bins beside
the (8, TR) blocks of slot, grad and hess, sublane k being row tile k.
For each tile in ascending order the kernel expands the (TC, TR) bins
tile to its bin one-hot in a bin-major sublane layout (sublane
l = b·TC + c, built with the dedicated `tpu.repeat` op — no
128-alignment constraint on TC, verified on v5e at TC=28), builds the
node one-hot by comparing the slot lane-vector against a sublane iota,
and contracts the two on the MXU with an NT matmul. G and H share ONE
contraction: the node one-hot weighted by grad in sublanes [0, S8) and
by hess in [S8, 2·S8) (S8 = S slots padded to the sublane tile) is a
single (2·S8, TR) operand against the (L, TR) bin one-hot, so the bin
one-hot — the side whose streaming through the 128-wide array sets
the kernel's time — passes once for both, not once each. The eight
tiles are a Python loop, so they lie in one basic block: there the
scheduler builds tile k+1's one-hot on the vector unit while tile k
streams through the MXU, which a rolled loop or a tile a grid step
cannot (PERF.md, PR 36, has the forms side by side on the v5e). The
(2·S8, L) output block accumulates tile by tile and across grid steps
(TPU grids iterate sequentially, so adding into the same output block
is the standard reduction pattern); the two (S, C, B) histograms are
sliced and reassembled by cheap XLA reshape/transpose outside the
kernel.

The kernel meets every slot count from 1: `models/gbdt._grow_tree`
calls it once a level at what that level reads (under sibling
subtraction 1, 1, 2, ..., 2^(max_depth-1)), and the leaf level on
one column only (`gbdt._leaf_columns`). Up to S = 64 a call is one
pass of the array's width; the row tile is 512 whatever S is, so a
slot's sum adds the same products in the same order at every S. The
kernel itself reads S8 and not S, and the jitted entry is keyed on S8:
the levels of 1, 1, 2, 4 and 8 slots are one traced and lowered
program.

A lockstep group (`gbdt._forest_level_histograms`: the trees of a
random forest, or the bags of a bagged GBT, over one bin matrix) hands
its (T, R) slot, grad and hess to the same entries. The bin one-hot
depends on the bins alone, so `trees_per_pass` trees share it: their
(2·S8, TR) node one-hots stack into one (2·Tp·S8, TR) operand, one MXU
contraction a row tile for all of them, where `vmap` over the (R,) call
built the one-hot again for every tree. Tp is the largest whose stacked
operand is at most one pass of the array's 128 rows and whose tiles are
one tree's (another row tile would change the order of the sums), so a
tree's histograms are its own call's bit for bit. The passes are an
outer grid axis of the one call: a level of a group is one kernel
event, as under `vmap`.

`interpret=True` runs the same kernel on CPU for tests (conftest's
8-device CPU mesh), keeping kernel parity checkable without a chip.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from shifu_tpu.config.environment import knob_int, knob_str

__all__ = ["level_histograms_pallas", "level_histograms_fused",
           "bins_from_values", "trees_per_pass"]


def _slots8(n_slots: int) -> int:
    """Slots padded to the sublane tile: where H starts in the stacked
    operand and in the kernel's output block."""
    return -(-n_slots // 8) * 8


def _hist_body(bins_of, n_cols: int, slot_ref, grad_ref, hess_ref, out_ref,
               *, n_bins: int, precision):
    """Shared contraction body of a grid step: for each of the (T, TR)
    slot, grad and hess blocks' T row tiles in ascending order, the
    (TC, TR) int32 bins tile `bins_of(lanes)` gives for that tile's lane
    slice → accumulate the (2·S8, B·TC) output block, G in sublanes
    [0, S), H in [S8, S8 + S). `n_cols` is TC.

    Grouped form: (Tp, T, TR) blocks of slot, grad and hess hold the
    T row tiles of Tp trees, and the output block is Tp such (2·S8, B·TC)
    blocks stacked, tree t's at sublanes [2·t·S8, 2·(t+1)·S8). The bin
    one-hot, which depends on the bins alone, is built once a row tile
    for all of them."""
    # grid = ([passes,] col_tiles, row_steps): the ROW (reduction)
    # dimension is innermost, so each output block's revisits are
    # consecutive grid steps — required for the += accumulation pattern
    # on TPU (the output VMEM buffer is flushed between non-consecutive
    # revisits)
    grouped = slot_ref.ndim == 3
    i = pl.program_id(2 if grouped else 1)
    n_trees = slot_ref.shape[0] if grouped else 1
    tiles, tr = slot_ref.shape[-2:]
    s8 = out_ref.shape[0] // (2 * n_trees)
    # what no tile changes is built once a step (and traced once): the
    # slot a sublane of a tree's stacked operand stands for, and the
    # sublane index of the bin one-hot
    sub = jax.lax.broadcasted_iota(jnp.int32, (2 * s8, tr), 0)
    is_h = sub >= s8
    sub_slot = jnp.where(is_h, sub - s8, sub)
    lane = jax.lax.broadcasted_iota(jnp.int32, (n_cols * n_bins, tr), 0)

    def row(ref, t, k):
        return ref[t, k:k + 1, :] if grouped else ref[k:k + 1, :]

    # a Python loop: the tiles lie in ONE basic block, where the
    # scheduler builds tile k+1's one-hot on the vector unit while tile
    # k streams through the MXU; a rolled loop cannot, and a fori_loop
    # unrolled by the lowering costs a first job 0.6 s more tracing than
    # these eight short bodies (PERF.md, PR 36)
    for k in range(tiles):
        binsT = bins_of(slice(k * tr, (k + 1) * tr))
        rows = [(row(slot_ref, t, k), row(grad_ref, t, k),
                 row(hess_ref, t, k)) for t in range(n_trees)]  # (1, TR)

        # bin one-hot, transposed + bin-major (sublane l = b·TC + c):
        # tpu.repeat stacks B copies of the (TC, TR) tile along sublanes
        # (rows l % TC; off the chip its implementation is jnp.tile).
        # Sublane l stands for bin l // TC; the vector unit has no
        # integer division (iota // TC, rebuilt every tile, was 40% of
        # a call), so rep == l // TC is told as 0 <= l - rep·TC < TC
        off = lane - pltpu.repeat(binsT, n_bins, axis=0) * n_cols
        onehot_bins = jnp.logical_and(off >= 0, off < n_cols
                                      ).astype(jnp.float32)  # (B·TC, TR)

        # node one-hot weighted by grad in the first S8 sublanes and by
        # hess in the next S8: ONE (2·S8, TR) operand a tree, so the bin
        # one-hot streams through the MXU once for both (at S ≤ 64 the
        # two together fill no more of the 128-wide array than either
        # did), and the trees of a pass stacked on it. A row's dump slot
        # (S; rows not in this level) matches at most a pad sublane,
        # which the caller drops.
        ghw = [(slot == sub_slot).astype(jnp.float32)
               * jnp.where(is_h, hess, grad)
               for slot, grad, hess in rows]             # (2·S8, TR) each
        ghw = ghw[0] if n_trees == 1 else jnp.concatenate(ghw, axis=0)

        # MXU NT contraction over rows: (2·Tp·S8, TR) · (B·TC, TR)ᵀ
        part = jax.lax.dot_general(
            ghw, onehot_bins, (((1,), (1,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)

        if k:
            out_ref[:, :] += part
            continue

        @pl.when(i == 0)
        def _init():
            out_ref[:, :] = part

        @pl.when(i > 0)
        def _accum():
            out_ref[:, :] += part


def _hist_kernel(binsT_ref, slot_ref, grad_ref, hess_ref, out_ref, *,
                 n_bins: int, precision):
    _hist_body(lambda lanes: binsT_ref[:, lanes], binsT_ref.shape[0],
               slot_ref, grad_ref, hess_ref, out_ref, n_bins=n_bins,
               precision=precision)


def _fused_hist_kernel(valT_ref, cuts_ref, slot_ref, grad_ref, hess_ref,
                       out_ref, *, n_bins: int, n_cuts: int, precision):
    """Fused bin-lookup + histogram: the (TC, TR) tile arrives as RAW
    feature values (NaN = missing) plus each column's ascending cut
    boundaries, and the bin index is derived in-register — GBT level
    building never materializes the (C, R) bin-index matrix in HBM.

    Bin semantics match gbdt.bin_dataset / ops.stats.bin_index_numeric:
    bin = #(v >= cut) clamped to n_bins-2 (cuts are +inf padded, so
    pad entries never count for finite v), NaN → the shared missing
    bin n_bins-1. The per-cut compare loop is statically unrolled
    (n_cuts ≤ n_bins-1 iterations of one VPU compare+add each)."""
    cuts = cuts_ref[:, :]                           # (TC, K) f32

    def bins_of(lanes):
        valT = valT_ref[:, lanes]                   # (TC, TR) f32
        bins = jnp.zeros(valT.shape, jnp.int32)
        for k in range(n_cuts):
            bins += (valT >= cuts[:, k:k + 1]).astype(jnp.int32)
        bins = jnp.minimum(bins, n_bins - 2)
        return jnp.where(jnp.isnan(valT), n_bins - 1, bins)

    _hist_body(bins_of, valT_ref.shape[0], slot_ref, grad_ref, hess_ref,
               out_ref, n_bins=n_bins, precision=precision)


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


def _precision(highest: bool):
    return (jax.lax.Precision.HIGHEST if highest
            else jax.lax.Precision.DEFAULT)


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

    Per row tile the kernel keeps, in 4-byte lanes (S8 = slots padded
    to a sublane multiple; G and H are stacked, 2·S8 sublanes):
      bin one-hot (B·TC, TR) plus the repeated bins and the sublane
        index it is told from — 3 × the dominant buffer;
      a grid step's eight bins tiles (TC, 8·TR) and its three (8, TR)
        blocks of slot, grad and hess, double-buffered;
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
                    + 2 * 8 * ct * rt + 3 * 2 * 8 * rt
                    + 4 * 2 * s8 * rt
                    + 3 * 2 * s8 * ct * n_bins)

    while usage(col_tile, row_tile) > budget and row_tile > 128:
        row_tile //= 2
    while usage(col_tile, row_tile) > budget and col_tile > unit:
        col_tile = max(unit, (col_tile // 2) // unit * unit)
    return row_tile, col_tile


# rows of the MXU's array (v5e: 128 x 128): the stacked operand of a
# grouped pass is held to one pass of it
_MXU_ROWS = 128


def trees_per_pass(n_trees: int, n_slots: int, n_cols: int, n_bins: int,
                   highest: Optional[bool] = None) -> int:
    """Trees of a lockstep group whose histograms one grouped pass
    builds (Tp), from shapes alone: the largest Tp <= n_trees whose
    stacked (2·Tp·S8, TR) operand is at most one pass of the MXU's rows
    (4 trees up to 16 slots, 2 at 32, 1 from 64 up) and whose tiles
    (`derive_tiles` at Tp·S8 slots) are a single tree's. Another row
    tile would change the order of the float32 sums, and with it every
    tree's bits; above 64 slots a pass is bound by the MXU's rows, and
    one tree a pass gives up nothing. `highest` None reads
    SHIFU_TPU_HIST_PRECISION."""
    if highest is None:
        highest = _highest()
    s8 = _slots8(n_slots)
    tiles = derive_tiles(n_cols, s8, n_bins, highest)
    tp = max(1, min(n_trees, _MXU_ROWS // (2 * s8)))
    while tp > 1 and derive_tiles(n_cols, tp * s8, n_bins, highest) != tiles:
        tp -= 1
    return tp


def _row_tiles(slot, grad, hess, row_tile: int, trees_per_pass: int = 1):
    """The (R,) per-row vectors as (tiles, row_tile) views, one row tile
    a sublane, and the row tiles a grid step covers: eight, or every
    tile where there are fewer. Rows that fill no whole step are padded
    with slot -1, which like every slot outside [0, S8) matches no
    sublane of the node one-hot. A group's (T, R) row state views as
    (T, tiles, row_tile), its trees padded the same way to a whole
    number of passes of `trees_per_pass`."""
    r = slot.shape[-1]
    tiles = -(-r // row_tile)
    step = min(8, tiles)
    pad = -(-tiles // step) * step * row_tile - r
    pads = [(0, -n % trees_per_pass) for n in slot.shape[:-1]] + [(0, pad)]

    def view(v, dtype, fill):
        v = v.astype(dtype)
        if any(p for _, p in pads):
            v = jnp.pad(v, pads, constant_values=fill)
        return v.reshape(v.shape[:-1] + (-1, row_tile))

    return (view(slot, jnp.int32, -1), view(grad, jnp.float32, 0),
            view(hess, jnp.float32, 0)), step


def _hist_call(kern, matrix, cuts, slot, grad, hess, slots8: int,
               n_bins: int, row_tile: int, col_tile: int, interpret: bool,
               name: str, trees_per_pass: int = 1):
    """The pallas_call both kernels share. `matrix` is the (C, R) bins
    or raw values, padded to whole column and row tiles, `cuts` the
    fused kernel's (C, K) cut table or None; slot, grad and hess go in
    as `_row_tiles` views them. A grid step takes `step` row tiles of
    each (rows past the matrix's last whole step are read as whatever
    the block holds there and weigh nothing: their slot is the pad's
    -1); one (2·S8, col_tile·n_bins) output block a column tile,
    revisited along the row (reduction) axis.

    A group's (T, R) row state adds an outer grid axis of passes, each
    over `trees_per_pass` trees' (Tp, step, TR) blocks into one
    (2·Tp·S8, col_tile·n_bins) output block: still ONE `pallas_call`,
    one device event a level of a group."""
    views, step = _row_tiles(slot, grad, hess, row_tile, trees_per_pass)
    cp, rp = matrix.shape
    # ([passes,] col_tiles, row_steps) — rows innermost; see _hist_body
    grid = (cp // col_tile, -(-rp // (step * row_tile)))
    block = (2 * slots8 * trees_per_pass, col_tile * n_bins)
    if slot.ndim == 2:
        passes = views[0].shape[0] // trees_per_pass
        grid = (passes,) + grid
        rows_block = (trees_per_pass, step, row_tile)
        maps = (lambda p, j, i: (j, i), lambda p, j, i: (j, 0),
                lambda p, j, i: (p, i, 0), lambda p, j, i: (p, j))
    else:
        passes = 1
        rows_block = (step, row_tile)
        maps = (lambda j, i: (j, i), lambda j, i: (j, 0),
                lambda j, i: (i, 0), lambda j, i: (0, j))
    mat_map, cut_map, row_map, out_map = maps
    operands = [matrix]
    in_specs = [pl.BlockSpec((col_tile, step * row_tile), mat_map)]
    if cuts is not None:
        operands.append(cuts)
        in_specs.append(pl.BlockSpec((col_tile, cuts.shape[1]), cut_map))
    in_specs += [pl.BlockSpec(rows_block, row_map)] * 3
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(block, out_map),
        out_shape=jax.ShapeDtypeStruct(
            (passes * block[0], grid[-2] * block[1]), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * (len(grid) - 1)
            + ("arbitrary",),
            vmem_limit_bytes=_vmem_budget()),
        interpret=interpret,
        name=name,
    )(*operands, *views)


def _highest() -> bool:
    """SHIFU_TPU_HIST_PRECISION=highest: the f32-exact contraction."""
    return (knob_str("SHIFU_TPU_HIST_PRECISION", "") or
            "").lower() == "highest"


def _tiles(c: int, r: int, n_slots: int, n_bins: int, row_tile: int,
           col_tile: int, slot):
    """(highest, row_tile, col_tile, trees, trees_per_pass) of a call:
    SHIFU_TPU_HIST_PRECISION, the tiles pinned by the caller or derived
    from the VMEM budget, held to the matrix's own extent; for a group's
    (T, R) `slot` its T and `trees_per_pass`, for one tree's (R,) 0 and
    1."""
    highest = _highest()
    d_row, d_col = derive_tiles(c, n_slots, n_bins, highest)
    trees = slot.shape[0] if slot.ndim == 2 else 0
    tp = trees_per_pass(trees, n_slots, c, n_bins, highest) if trees else 1
    return (highest, min(row_tile or d_row, max(8, r)),
            min(col_tile or d_col, max(1, c)), trees, tp)


def _split_gh(out, n_slots: int, n_bins: int, col_tile: int, c: int,
              n_trees: int = 0):
    """The kernel's (2·S8, [tile j][bin b][col c]) output → the G and
    the H histogram, each (S, C, B); a group's output, tree t's
    (2·S8, ...) block the t-th, → each (T, S, C, B). Cheap XLA slices
    and transposes on the small output."""
    def reassemble(a):
        lead = a.shape[:-2]
        a = a.reshape(lead + (n_slots, -1, n_bins, col_tile))
        a = jnp.swapaxes(a, -1, -2).reshape(lead + (n_slots, -1, n_bins))
        return a[..., :c, :]

    s8 = _slots8(n_slots)
    if n_trees:
        out = out.reshape(-1, 2 * s8, out.shape[-1])[:n_trees]
    return (reassemble(out[..., :n_slots, :]),
            reassemble(out[..., s8:s8 + n_slots, :]))


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

    A lockstep group's (T, R) slot/grad/hess → two
    (T, n_slots, C, n_bins) histograms, each tree's bit for bit what its
    own (R,) call gives: `trees_per_pass` trees a pass share every row
    tile's bin one-hot, and the passes are a grid axis of the one call.

    Precision: the MXU multiplies in bf16 by default — the one-hot
    side is exact, so only grad/hess values truncate (~0.3% relative
    per element, statistically inert for split gains; measured on
    v5e: 0.10 s vs the XLA scatter's 10.1 s at 2M×128 depth-6).
    SHIFU_TPU_HIST_PRECISION=highest switches to the f32-exact
    multi-pass algorithm, which starts from the smallest aligned row
    tile (128 lanes) to leave VMEM for its extra passes."""
    c, r = binsT.shape
    highest, row_tile, col_tile, trees, tp = _tiles(
        c, r, n_slots, n_bins, row_tile, col_tile, slot)
    out = _level_histograms_pallas(binsT, slot, grad, hess,
                                   _slots8(n_slots), n_bins, row_tile,
                                   col_tile, interpret, highest, tp)
    return _split_gh(out, n_slots, n_bins, col_tile, c, trees)


@functools.partial(jax.jit, static_argnames=("slots8", "n_bins",
                                             "row_tile", "col_tile",
                                             "interpret", "highest",
                                             "trees_per_pass"))
def _level_histograms_pallas(binsT, slot, grad, hess,
                             slots8: int, n_bins: int,
                             row_tile: int, col_tile: int,
                             interpret: bool, highest: bool,
                             trees_per_pass: int):
    c, r = binsT.shape
    pad_r = (-r) % row_tile
    pad_c = (-c) % col_tile
    if pad_r or pad_c:
        binsT = jnp.pad(binsT, ((0, pad_c), (0, pad_r)))
    kern = functools.partial(_hist_kernel, n_bins=n_bins,
                             precision=_precision(highest))
    return _hist_call(kern, binsT.astype(jnp.int32), None, slot, grad, hess,
                      slots8, n_bins, row_tile, col_tile, interpret,
                      "shifu_level_histograms", trees_per_pass)


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
    layout, grouped form and precision contract as the int-bins
    kernel."""
    c, r = valuesT.shape
    highest, row_tile, col_tile, trees, tp = _tiles(
        c, r, n_slots, n_bins, row_tile, col_tile, slot)
    out = _level_histograms_fused(valuesT, cutsT, slot, grad, hess,
                                  _slots8(n_slots), n_bins, row_tile,
                                  col_tile, interpret, highest, tp)
    return _split_gh(out, n_slots, n_bins, col_tile, c, trees)


@functools.partial(jax.jit, static_argnames=("slots8", "n_bins",
                                             "row_tile", "col_tile",
                                             "interpret", "highest",
                                             "trees_per_pass"))
def _level_histograms_fused(valuesT, cutsT, slot, grad, hess,
                            slots8: int, n_bins: int,
                            row_tile: int, col_tile: int,
                            interpret: bool, highest: bool,
                            trees_per_pass: int):
    c, r = valuesT.shape
    pad_r = (-r) % row_tile
    pad_c = (-c) % col_tile
    if pad_r or pad_c:
        # pad columns bin to 0 and are sliced off after reassembly;
        # pad cut rows are +inf so they never count for any value
        valuesT = jnp.pad(valuesT, ((0, pad_c), (0, pad_r)))
        cutsT = jnp.pad(cutsT, ((0, pad_c), (0, 0)),
                        constant_values=jnp.inf)
    kern = functools.partial(_fused_hist_kernel, n_bins=n_bins,
                             n_cuts=cutsT.shape[1],
                             precision=_precision(highest))
    return _hist_call(kern, valuesT.astype(jnp.float32),
                      cutsT.astype(jnp.float32), slot, grad, hess, slots8,
                      n_bins, row_tile, col_tile, interpret,
                      "shifu_level_histograms_fused", trees_per_pass)
