"""Pallas TPU kernel: fused GBT/RF ensemble inference.

The tree serving path (`models/gbdt.predict`) used to run three host
round-trips per request: a numpy `bin_dataset` pass over the raw
cleaned features, the interpretive `_walk_trees` per-level gather walk
(max_depth dispatches of cross-sublane gathers per tree), and a numpy
convert (mean / lr·sum + clipped sigmoid). This kernel fuses all three
for a whole ensemble × request batch in VMEM:

- **in-register binning** — the raw (C, TR) value tile is binned by
  the same `Σ(v >= cut)` compare-count as the fused histogram kernel
  (`ops/pallas_hist.bins_from_values` semantics: clamp to n_bins-2,
  NaN → the missing bin n_bins-1), so the per-request host-numpy
  `bin_dataset` pass disappears. Categorical columns arrive
  host-mapped to float bin ids with identity cuts (0.5, 1.5, …) via
  `gbdt.make_fused_inputs` — exactly the FusedBins convention.
- **gather-free breadth-first walk** — every tree's nodes ride ONE
  packed (8, T·N) f32 block (sublanes: feature, split bin,
  default_left, stop, leaf_value; see `pack_ensemble`). A one-hot of
  each node's split feature contracts with the bin tile on the MXU
  (exact: 0/1 × small ints at HIGHEST precision), yielding every
  node's routed bin for every row at once; ones-outer-products
  broadcast the per-node scalars into the same (S, TR) layout. The
  walk itself is max_depth data-independent select steps over a
  (T, N, TR) view — no gathers, no per-level dispatches — with
  missing values routed by `default_left` and rows parked at leaves
  (`stop`), matching `_walk_trees` decision-for-decision.
- **in-kernel convert** — RF mean, GBT lr·sum with the exact
  ±30-clip sigmoid of `gbdt.predict` for log loss.

Routing: SHIFU_TPU_TREE_FUSED = auto (Pallas on TPU, XLA elsewhere) |
pallas | xla — same contract as SHIFU_TPU_SCORE_FUSED /
SHIFU_TPU_SPLIT_FUSED. `interpret=True` runs the kernel on CPU for
tests; the interpretive `predict_trees` walk stays the pinned parity
reference (tests/test_pallas_trees.py).

Parity note: per-row routing is integer-exact, so tree STRUCTURE
decisions bit-match the walk and scores are invariant to the row tile
and to bucket padding (each row only sees its own lane). The final
score may differ from the numpy reference at f32-ulp scale: the
per-row leaf sum accumulates tree-by-tree where numpy's `sum(axis=0)`
pairwise-reassociates, and jnp.exp vs np.exp in the sigmoid.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from shifu_tpu.config.environment import knob_int, knob_str

__all__ = ["tree_fused_mode", "pack_ensemble", "predict_ensemble"]


def tree_fused_mode() -> str:
    """Fused tree-inference route: "pallas" | "xla"; "auto" resolves
    by backend (Pallas on TPU, XLA fallback elsewhere)."""
    mode = knob_str("SHIFU_TPU_TREE_FUSED").lower()
    if mode in ("pallas", "xla"):
        return mode
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def pack_ensemble(trees: Dict[str, Any]) -> Tuple[np.ndarray, int]:
    """Flatten a (T, n_nodes) tree pytree into the kernel's packed
    node block: (8, T·N_pad) f32, node axis padded to a sublane
    multiple so the kernel's flat→(T, N_pad, TR) reshape stays
    tile-aligned. Sublanes:

      0 feature       split feature id, -1 for leaves/unset/pad
      1 bin           split bin threshold (bin <= it goes left)
      2 default_left  missing-value direction, 1.0 = left
      3 stop          is_leaf | feature < 0 — the walk's park flag,
                      precomputed host-side (pad nodes stop too)
      4 leaf_value    0 on internal/pad nodes

    Returns (packed, N_pad). Node ids stay perfect-binary-tree local
    (children of i at 2i+1 / 2i+2 < n_nodes ≤ N_pad), so a walking
    row can never land on a pad node."""
    feat = np.asarray(trees["feature"], np.float32)
    t, n = feat.shape
    n_pad = max(8, -(-n // 8) * 8)

    def lane(a, fill):
        return np.pad(np.asarray(a, np.float32),
                      ((0, 0), (0, n_pad - n)), constant_values=fill)

    stop = (np.asarray(trees["is_leaf"], bool) |
            (np.asarray(trees["feature"]) < 0))
    packed = np.zeros((8, t * n_pad), np.float32)
    packed[0] = lane(feat, -1.0).reshape(-1)
    packed[1] = lane(trees["bin"], 0.0).reshape(-1)
    packed[2] = lane(trees["default_left"], 0.0).reshape(-1)
    packed[3] = lane(stop, 1.0).reshape(-1)
    packed[4] = lane(trees["leaf_value"], 0.0).reshape(-1)
    return packed, n_pad


# Mosaic unrolls every vector op over the block's vregs, so compile
# time (and VMEM) grow with the (tree-tile nodes × row tile) maps, not
# with the ensemble: 256 vregs (1024 f32 each) per map keeps a bucket's
# compile to seconds where a whole 500-tree ensemble would not fit VMEM
_MAP_ELEMS = 256 * 1024


def _derive_tiles(n_trees: int, n_pad: int, n_cols: int, n_cuts: int,
                  n_rows: int):
    """(row_tile, tree_tile). The row tile covers the request (a
    multiple of 128 lanes, at most 2048); the tree tile is however many
    whole trees keep one (tree-tile nodes, row tile) f32 map inside
    both the compile-time cap and SHIFU_TPU_TREE_VMEM_MB — per grid
    step the kernel keeps ~12 such maps live (routed bin, the four
    broadcast node scalars, go_left, the walk's selects and their
    masked operands) plus the (C, TR) value/bin tiles and the resident
    (8, S) node block + (C, K) cuts."""
    budget = knob_int("SHIFU_TPU_TREE_VMEM_MB") << 20
    row_tile = max(128, min(2048, -(-n_rows // 128) * 128))
    while True:
        fixed = 4 * (n_cols * max(n_cuts, 1) + 3 * n_cols * row_tile)
        elems = min(_MAP_ELEMS, max(0, budget - fixed) // (4 * 12))
        tree_tile = elems // (n_pad * row_tile)
        if tree_tile >= 1 or row_tile == 128:
            break
        row_tile = max(128, row_tile // 2 // 128 * 128)
    return int(row_tile), int(max(1, min(n_trees, tree_tile)))


def _tree_kernel(vals_ref, cuts_ref, nodes_ref, out_ref, *,
                 n_trees: int, tree_tile: int, n_pad: int, n_cols: int,
                 n_bins: int, n_cuts: int, max_depth: int, kind: str,
                 loss: str, lr: float):
    # grid = (row_tiles, tree_tiles): the TREE (reduction) dimension is
    # innermost, so each output block's revisits are consecutive grid
    # steps — the += accumulation pattern on TPU
    t = pl.program_id(1)
    v = vals_ref[:, :]                                # (C, TR) raw
    tr = v.shape[1]
    s = tree_tile * n_pad
    # in-register binning — bins_from_values semantics (+inf pad cuts
    # never fire for finite values; the clamp keeps the Σ at the last
    # main bin when they do for +inf values)
    bins = jnp.zeros(v.shape, jnp.float32)
    for k in range(n_cuts):
        bins += (v >= cuts_ref[:, k:k + 1]).astype(jnp.float32)
    bins = jnp.minimum(bins, float(n_bins - 2))
    bins = jnp.where(jnp.isnan(v), float(n_bins - 1), bins)

    dot = functools.partial(
        jax.lax.dot_general, dimension_numbers=(((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    # every node's routed bin for every row: one-hot(feature) × bins on
    # the MXU — 0/1 times integer-valued f32, exact at HIGHEST. Mosaic
    # only makes integer iotas, so ids are compared as int32.
    feat = nodes_ref[0:1, :].astype(jnp.int32)        # (1, S)
    oh = (jax.lax.broadcasted_iota(jnp.int32, (n_cols, s), 0)
          == feat).astype(jnp.float32)                # (C, S)
    rb = dot(oh, bins)                                # (S, TR)
    # per-node scalars broadcast across rows as ones-outer-products
    ones = jnp.ones((1, tr), jnp.float32)
    sbin = dot(nodes_ref[1:2, :], ones)               # (S, TR)
    dl = dot(nodes_ref[2:3, :], ones)
    stop = dot(nodes_ref[3:4, :], ones)
    lval = dot(nodes_ref[4:5, :], ones)

    miss = rb == float(n_bins - 1)
    # (selecting between two bool vectors does not lower — widen first)
    go_left = jnp.where(miss, (dl > 0.0).astype(jnp.int32),
                        (rb <= sbin).astype(jnp.int32))
    # flat (S, TR) → (T, N_pad, TR): N_pad is a sublane multiple so the
    # split is tile-aligned; the walk is select-only from here on
    gl3 = go_left.reshape(tree_tile, n_pad, tr)
    st3 = (stop > 0.0).astype(jnp.int32).reshape(tree_tile, n_pad, tr)
    lv3 = lval.reshape(tree_tile, n_pad, tr)
    iota_n = jax.lax.broadcasted_iota(jnp.int32,
                                      (tree_tile, n_pad, tr), 1)
    node = jnp.zeros((tree_tile, 1, tr), jnp.int32)
    for _ in range(max_depth):
        sel = iota_n == node                          # (T, N_pad, TR)
        gl_here = jnp.max(jnp.where(sel, gl3, 0), axis=1,
                          keepdims=True)              # (T, 1, TR)
        st_here = jnp.max(jnp.where(sel, st3, 0), axis=1,
                          keepdims=True)
        # left child 2i+1, right 2i+2
        node = jnp.where(st_here > 0, node, 2 * node + 2 - gl_here)
    sel = iota_n == node
    contrib = jnp.sum(jnp.where(sel, lv3, 0.0), axis=1,
                      keepdims=True)                  # (T, 1, TR)
    total = jnp.sum(contrib, axis=0)                  # (1, TR)

    @pl.when(t == 0)
    def _init():
        out_ref[:, :] = jnp.broadcast_to(total, out_ref.shape)

    @pl.when(t > 0)
    def _accum():
        out_ref[:, :] += jnp.broadcast_to(total, out_ref.shape)

    @pl.when(t == pl.num_programs(1) - 1)
    def _convert():
        total_ = out_ref[:, :]
        if kind == "rf":
            score = total_ / float(n_trees)
        else:
            raw = float(lr) * total_
            if loss.startswith("log"):
                raw = jnp.clip(raw, -30.0, 30.0)      # predict()'s clip
                score = 1.0 / (1.0 + jnp.exp(-raw))
            else:
                score = raw
        out_ref[:, :] = score


@functools.partial(jax.jit, static_argnames=(
    "n_trees", "kind", "loss", "learning_rate", "max_depth", "n_bins",
    "row_tile", "tree_tile", "interpret"))
def _predict_ensemble_pallas(nodes, valuesT, cuts, n_trees: int,
                             kind: str, loss: str, learning_rate: float,
                             max_depth: int, n_bins: int, row_tile: int,
                             tree_tile: int, interpret: bool):
    c, r = valuesT.shape
    s = nodes.shape[1]
    n_pad = s // n_trees
    k = cuts.shape[1]
    pad_r = (-r) % row_tile
    vp = jnp.pad(valuesT.astype(jnp.float32), ((0, 0), (0, pad_r)))
    rp = r + pad_r
    # pad trees are a lone parked root with leaf value 0: they add 0
    pad_s = ((-n_trees) % tree_tile) * n_pad
    nodes = jnp.pad(nodes, ((0, 0), (0, pad_s)))
    nodes = nodes.at[0, s:].set(-1.0).at[3, s:].set(1.0)
    ts = tree_tile * n_pad
    grid = (rp // row_tile, (s + pad_s) // ts)

    out = pl.pallas_call(
        functools.partial(
            _tree_kernel, n_trees=n_trees, tree_tile=tree_tile,
            n_pad=n_pad, n_cols=c, n_bins=n_bins, n_cuts=k,
            max_depth=max_depth, kind=kind, loss=loss, lr=learning_rate),
        grid=grid,
        in_specs=[
            pl.BlockSpec((c, row_tile), lambda i, t: (0, i)),
            pl.BlockSpec((c, k), lambda i, t: (0, 0)),
            pl.BlockSpec((8, ts), lambda i, t: (0, t)),
        ],
        out_specs=pl.BlockSpec((8, row_tile), lambda i, t: (0, i)),
        out_shape=jax.ShapeDtypeStruct((8, rp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=knob_int("SHIFU_TPU_TREE_VMEM_MB") << 20),
        interpret=interpret,
        name="shifu_predict_ensemble",
    )(vp, cuts.astype(jnp.float32), nodes)
    return out[0, :r]


def predict_ensemble(nodes, valuesT, cuts, *, n_trees: int, kind: str,
                     loss: str = "squared", learning_rate: float = 0.1,
                     max_depth: int, n_bins: int, row_tile: int = 0,
                     tree_tile: int = 0, interpret: bool = False):
    """Packed ensemble (`pack_ensemble`) + FusedBins-style raw inputs
    (`gbdt.make_fused_inputs`: valuesT (C, R) f32 NaN-missing, cuts
    (C, K) +inf-padded) → (R,) final scores with `gbdt.predict`
    convert semantics (RF mean; GBT lr·sum, log loss → ±30-clip
    sigmoid). One kernel launch for the whole request — no host
    binning, no per-level walk dispatches; trees beyond one tile's
    worth accumulate across the inner grid axis."""
    d_row, d_tree = _derive_tiles(n_trees, nodes.shape[1] // n_trees,
                                  valuesT.shape[0], cuts.shape[1],
                                  valuesT.shape[1])
    return _predict_ensemble_pallas(
        nodes, valuesT, cuts, n_trees=n_trees, kind=kind, loss=loss,
        learning_rate=float(learning_rate), max_depth=max_depth,
        n_bins=n_bins, row_tile=row_tile or d_row,
        tree_tile=tree_tile or d_tree, interpret=interpret)
