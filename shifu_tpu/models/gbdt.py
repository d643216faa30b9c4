"""Histogram GBDT / Random Forest — XLA-native tree ensembles.

Replaces the reference's Guagua tree trainer (`dt/DTMaster.java:93`
level-order node queue + per-(node,feature) histogram aggregation,
`dt/DTWorker.java:107` per-instance stat accumulation, impurity math in
`dt/Impurity.java`, losses in `dt/Loss.java`) with the dense
histogram formulation XLA compiles well:

- every feature is pre-binned (numeric: the stats phase's exact
  quantile boundaries; categorical: bins ordered by positive rate so
  threshold splits act as optimal subset splits, the LightGBM trick);
- one level of every tree grows at a time: a single scatter-add builds
  the (node × feature × bin) gradient/hessian histograms for the whole
  level — the DTWorker hot loop (`DTWorker.java:914-944`) becomes one
  kernel; the master's aggregation over workers is the row-sharded
  `psum` of the same scatter under shard_map;
- split selection is an argmax over cumulative histogram sums with
  XGBoost-style gain G²/(H+λ) (equivalent to the reference's variance
  impurity when hess≡1) and LightGBM-style missing-direction choice
  (the reference routes missing to its own bin);
- GBT boosts sequentially with first/second-order gradients of
  squared/log loss (`dt/DTWorker.java:1486` pseudo-residual update);
  RF trees are independent → built in ONE vmapped call with per-tree
  Poisson bagging weights and feature-subset masks
  (`FeatureSubsetStrategy.java` ALL/HALF/ONETHIRD/TWOTHIRDS/SQRT/LOG2).

Trees are flat arrays in a perfect-binary-tree layout (node i's
children are 2i+1 / 2i+2), so prediction is `max_depth` vectorized
gathers — no per-row recursion.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from shifu_tpu.config.environment import knob_bool, knob_int, knob_str
from shifu_tpu.data.pipeline import add_stage_count, host_fetch
from shifu_tpu.obs import trace as obs_trace

@dataclass(frozen=True)
class TreeConfig:
    """Static hyper-parameters (train#params for RF/GBT:
    `ModelTrainConf.createParamsByAlg:551-569`)."""
    max_depth: int = 6
    n_bins: int = 64              # histogram width incl. the missing slot
    min_instances_per_node: int = 1
    min_info_gain: float = 0.0
    reg_lambda: float = 1.0
    learning_rate: float = 0.1    # GBT shrinkage
    loss: str = "squared"         # squared | log (dt/Loss.java)

    @property
    def n_nodes(self) -> int:
        return 2 ** (self.max_depth + 1) - 1

    @property
    def n_internal(self) -> int:
        return 2 ** self.max_depth - 1


def feature_subset_count(strategy: str, n_features: int) -> int:
    """`core/dtrain/FeatureSubsetStrategy.java` ALL/HALF/ONETHIRD/
    TWOTHIRDS/SQRT/LOG2/AUTO."""
    s = (strategy or "ALL").upper()
    if s in ("ALL", "AUTO"):
        return n_features
    if s == "HALF":
        return max(1, n_features // 2)
    if s == "ONETHIRD":
        return max(1, n_features // 3)
    if s == "TWOTHIRDS":
        return max(1, (2 * n_features) // 3)
    if s == "SQRT":
        return max(1, int(math.sqrt(n_features)))
    if s == "LOG2":
        return max(1, int(math.log2(max(n_features, 2))))
    try:
        return max(1, min(n_features, int(s)))
    except ValueError:
        return n_features


# ---------------------------------------------------------------------------
# Single-level histogram + split kernel
# ---------------------------------------------------------------------------

def _hist_mode() -> str:
    """Histogram backend: "pallas" (MXU one-hot contraction kernel,
    ops/pallas_hist.py), "xla" (scatter-add), or "auto" (pallas on TPU,
    xla elsewhere). Override with SHIFU_TPU_HIST=pallas|xla."""
    import os
    mode = knob_str("SHIFU_TPU_HIST").lower()
    if mode in ("pallas", "xla"):
        return mode
    return "pallas" if jax.default_backend() == "tpu" else "xla"


class FusedBins(NamedTuple):
    """Raw feature values + per-column cut boundaries, carried in place
    of the pre-binned int32 matrix when SHIFU_TPU_HIST_FUSED=1: the
    histogram kernel re-derives bin indices in-register from these
    (ops/pallas_hist.level_histograms_fused), so the resident GBT level
    build never materializes the (C, R) bin-index intermediate in HBM.

    valuesT: (C, R) f32, transposed like binsT; NaN = missing.
    Categorical columns carry their host-mapped bin id as a float —
    identity cuts at 0.5, 1.5, … make the in-kernel compare count
    reproduce the id exactly (see make_fused_inputs).
    cuts: (C, K) f32, ascending per row, +inf padded.
    """
    valuesT: Any
    cuts: Any

    @property
    def shape(self):
        return self.valuesT.shape


def hist_fused_enabled() -> bool:
    """SHIFU_TPU_HIST_FUSED=1 routes the resident GBT build through
    FusedBins instead of the pre-binned int32 matrix."""
    return knob_bool("SHIFU_TPU_HIST_FUSED")


def make_fused_inputs(tables: Dict[str, np.ndarray],
                      dense: Optional[np.ndarray],
                      codes: Optional[np.ndarray],
                      n_bins: int) -> FusedBins:
    """Host-side packing for the fused histogram path — the FusedBins
    analog of bin_dataset (same column order: numeric then categorical,
    same missing semantics).

    Numeric columns pass through raw (NaN = missing) with their stats
    cut boundaries; the kernel's `Σ(v >= cut)` count equals
    ops/stats.bin_index_numeric exactly (+inf pad cuts never fire for
    finite values). Categorical columns are host-mapped through
    cat_map — same as bin_dataset — and the resulting bin id rides as
    a float with identity boundaries 0.5, 1.5, …; missing (id
    n_bins-1) becomes NaN so the kernel's NaN→missing rule lands it
    in the same slot."""
    num_cuts = np.asarray(tables["num_cuts"], np.float32)   # (K0, Cn)
    vals_parts: List[Any] = []
    cut_parts: List[np.ndarray] = []
    if dense is not None and dense.shape[1]:
        if isinstance(dense, jax.Array):
            # the serving plane pre-placed the raw numeric block on
            # device (its timed h2d stage) — transpose there; np.asarray
            # would drag it back through the host
            vals_parts.append(jnp.asarray(dense, jnp.float32).T)
        else:
            vals_parts.append(np.asarray(dense, np.float32).T)  # (Cn, R)
        cut_parts.append(np.ascontiguousarray(num_cuts.T))  # (Cn, K0)
    if codes is not None and codes.shape[1]:
        cat_map = tables["cat_map"]
        cc = codes.shape[1]
        safe = np.clip(codes, 0, cat_map.shape[1] - 1)
        mapped = cat_map[np.arange(cc)[None, :], safe]
        mapped = np.where(codes < 0, n_bins - 1, mapped)    # (R, Cc)
        v = mapped.T.astype(np.float32)                     # (Cc, R)
        v[v == (n_bins - 1)] = np.nan
        vals_parts.append(v)
        ident = 0.5 + np.arange(n_bins - 2, dtype=np.float32)
        cut_parts.append(np.broadcast_to(ident, (cc, n_bins - 2)))
    if not vals_parts:
        raise ValueError("no features to bin")
    k = max(p.shape[1] for p in cut_parts)
    cut_parts = [np.pad(p, ((0, 0), (0, k - p.shape[1])),
                        constant_values=np.inf) for p in cut_parts]
    if any(isinstance(p, jax.Array) for p in vals_parts):
        valuesT = jnp.concatenate([jnp.asarray(p, jnp.float32)
                                   for p in vals_parts])
    else:
        valuesT = np.ascontiguousarray(np.concatenate(vals_parts))
    return FusedBins(valuesT,
                     np.ascontiguousarray(np.concatenate(cut_parts)))


def _data_size(mesh) -> int:
    return 1 if mesh is None else int(mesh.shape.get("data", 1))


def _row_sharding(mesh, ndim: int):
    """Rows (the last of `ndim` axes) over `mesh`'s 'data' axis, every
    other axis whole: how the bin matrix and all per-row state lie."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P(*[None] * (ndim - 1), "data"))


def _by_row(mesh, x):
    """`x`, whose last axis is the rows, STATED to lie as the bin matrix
    lies: divided over `mesh`'s 'data' axis, every other axis whole. The
    row state of a build (predictions, gradients, node ids, slots) is
    made inside the jitted round, where nothing else says where it
    lives; one replicated copy of it is 4 bytes a row on every chip and
    an all-gather a round. No mesh, or one chip on its data axis: `x`
    itself, and the program is the one-device program."""
    if _data_size(mesh) == 1:
        return x
    return jax.lax.with_sharding_constraint(x, _row_sharding(mesh, x.ndim))


def _replicated(mesh, tree):
    """The (small) tree arrays stated whole on every chip of `mesh`."""
    if _data_size(mesh) == 1:
        return tree
    from shifu_tpu.parallel.mesh import replicated
    return jax.lax.with_sharding_constraint(tree, replicated(mesh))


def psum_bytes(cfg: "TreeConfig", n_cols: int, mesh, subtract: bool,
               n_trees: int = 1) -> int:
    """Bytes a step (one tree; a lockstep level of `n_trees` trees)
    hands its all-reduces, from shapes alone: float32 G and H over the
    slots each level's kernel call covers (`_kernel_slots`, the growth
    loop's own schedule) by columns by bins, the leaf level on its
    `_LEAF_COLUMNS`; tests/test_gbt_mesh.py holds it to the operands of
    the compiled round's all-reduces. 0 on one chip."""
    if _data_size(mesh) == 1:
        return 0
    slots = [_kernel_slots(d, subtract) for d in range(cfg.max_depth + 1)]
    cells = sum(slots[:-1]) * n_cols + slots[-1] * min(n_cols,
                                                       _LEAF_COLUMNS)
    return 2 * 4 * cells * cfg.n_bins * n_trees


def _local_level_histograms(binsT, slot, grad, hess, n_level_nodes, n_bins):
    """Single-shard histogram kernel (slot already computed, incl. the
    trailing dump slot for inactive rows). binsT is TRANSPOSED (C, R) —
    rows on the lane axis, so narrow feature matrices don't pay the
    TPU's 128-lane minor-dim padding. A FusedBins binsT routes to the
    fused bin-and-accumulate kernel (or bins on the fly for the XLA
    scatter fallback)."""
    if isinstance(binsT, FusedBins):
        if _hist_mode() == "pallas":
            from shifu_tpu.ops.pallas_hist import level_histograms_fused
            return level_histograms_fused(
                binsT.valuesT, binsT.cuts, slot, grad, hess,
                n_level_nodes, n_bins,
                interpret=jax.default_backend() != "tpu")
        from shifu_tpu.ops.pallas_hist import bins_from_values
        binsT = bins_from_values(binsT.valuesT, binsT.cuts, n_bins)
    c, r = binsT.shape
    if _hist_mode() == "pallas":
        from shifu_tpu.ops.pallas_hist import level_histograms_pallas
        return level_histograms_pallas(
            binsT, slot, grad, hess, n_level_nodes, n_bins,
            interpret=jax.default_backend() != "tpu")

    col_ids = jnp.broadcast_to(jnp.arange(c, dtype=jnp.int32)[:, None], (c, r))
    node_ids = jnp.broadcast_to(slot[None, :], (c, r)).astype(jnp.int32)

    def scatter(v):
        z = jnp.zeros((n_level_nodes + 1, c, n_bins), jnp.float32)
        return z.at[node_ids, col_ids, binsT].add(v[None, :])[:n_level_nodes]

    return scatter(grad), scatter(hess)


@jax.named_scope("hist")
def _level_histograms(binsT, node_of_row, grad, hess, level_offset,
                      n_level_nodes, n_bins, mesh=None):
    """Per-level G/H histograms.

    binsT: (C, R) int32 in [0, n_bins), transposed; node_of_row: (R,)
    global node ids (rows at inactive/finished nodes carry id -1 and
    scatter into a dumped slot). Returns (n_level_nodes, C, n_bins) G
    and H.

    With a multi-device `mesh`, rows shard over the 'data' axis and each
    device builds its local histogram which ONE psum reduces (G and H
    as one stacked block: a level is one rendezvous of the chips, a
    tree max_depth + 1) — exactly the
    DTWorker per-split accumulation + DTMaster aggregation
    (`dt/DTWorker.java:914-944`, `dt/DTMaster.java:276`), explicit via
    shard_map so no silent all-gather of the row-sharded bin matrix can
    slip in. On TPU the local kernel is the Pallas MXU one-hot
    contraction (ops/pallas_hist.py); elsewhere an XLA scatter-add.
    """
    local = node_of_row - level_offset  # (R,)
    valid = (local >= 0) & (local < n_level_nodes)
    slot = _by_row(mesh, jnp.where(valid, local, n_level_nodes))  # dump slot

    if _data_size(mesh) > 1:
        from jax.sharding import PartitionSpec as P

        # FusedBins: rows of valuesT shard like binsT; the small (C, K)
        # cut table is replicated on every device
        bspec = (FusedBins(P(None, "data"), P(None, None))
                 if isinstance(binsT, FusedBins) else P(None, "data"))

        @partial(jax.shard_map, mesh=mesh,
                 in_specs=(bspec, P("data"), P("data"), P("data")),
                 out_specs=(P(), P()), check_vma=False)
        def sharded(b, s, g, h):
            local = jnp.stack(_local_level_histograms(
                b, s, g, h, n_level_nodes, n_bins))
            with jax.named_scope("allreduce"):
                return tuple(jax.lax.psum(local, "data"))

        return sharded(binsT, slot, grad, hess)

    return _local_level_histograms(binsT, slot, grad, hess, n_level_nodes,
                                   n_bins)


@jax.named_scope("hist")
def _forest_level_histograms(binsT, node_T, grad_T, hess_T, level_offset,
                             n_level_nodes, n_bins, mesh=None):
    """Per-level G/H histograms for T trees grown in LOCKSTEP.

    binsT: (C, R) shared bin matrix; node_T/grad_T/hess_T: (T, R)
    per-tree row state. Returns (T, n_level_nodes, C, n_bins) G and H.

    Same explicit shard_map + psum structure as _level_histograms —
    rows shard over 'data', each device builds local histograms for
    ALL trees (vmap over the tree axis), one psum reduces G and H of
    all of them, stacked. RF used to
    rely on GSPMD partitioning a vmapped scatter here; that both risks
    a silent all-gather of the row-sharded bins AND compiles
    pathologically slowly (>9 min for a toy shape on the 8-device CPU
    mesh), so the forest path now shares the GBT path's collective.
    """
    local = node_T - level_offset                       # (T, R)
    valid = (local >= 0) & (local < n_level_nodes)
    slot_T = _by_row(mesh, jnp.where(valid, local, n_level_nodes))

    def local_hists(b, s, g, h):
        return jax.vmap(lambda s_, g_, h_: _local_level_histograms(
            b, s_, g_, h_, n_level_nodes, n_bins))(s, g, h)

    if _data_size(mesh) > 1:
        from jax.sharding import PartitionSpec as P

        @partial(jax.shard_map, mesh=mesh,
                 in_specs=(P(None, "data"), P(None, "data"),
                           P(None, "data"), P(None, "data")),
                 out_specs=(P(), P()), check_vma=False)
        def sharded(b, s, g, h):
            local = jnp.stack(local_hists(b, s, g, h))
            with jax.named_scope("allreduce"):
                return tuple(jax.lax.psum(local, "data"))

        return sharded(binsT, slot_T, grad_T, hess_T)

    return local_hists(binsT, slot_T, grad_T, hess_T)


@partial(jax.jit, static_argnames=("cfg", "mesh", "subtract",
                                   "return_nodes"))
def build_forest(cfg: TreeConfig, binsT, grad_T, hess_T, feature_masks,
                 mesh=None, subtract=None, return_nodes=False):
    """Grow T independent trees level-by-level in lockstep (the RF
    analog of build_tree; one histogram collective AND one split
    search per level cover every tree). grad_T/hess_T: (T, R);
    feature_masks: (T, C). Returns a stacked (T, n_nodes) tree pytree;
    with return_nodes=True also the (T, R) landing node of every row
    per tree (growth already routed rows to their final nodes — see
    build_tree — so lockstep boosting gathers leaf_value[node] instead
    of re-walking T trees)."""
    r = binsT.shape[1]
    n_trees = grad_T.shape[0]
    trees = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (n_trees,) + a.shape),
        _empty_tree(cfg))
    grad_T, hess_T = _by_row(mesh, grad_T), _by_row(mesh, hess_T)
    node_T = _by_row(mesh, jnp.zeros((n_trees, r), jnp.int32))

    prev_g = prev_h = side_T = half_T = None
    for depth in range(cfg.max_depth):
        g, h = _forest_child_histograms(cfg, binsT, node_T, half_T, grad_T,
                                        hess_T, depth, prev_g, prev_h,
                                        trees, mesh, subtract, side_T)
        trees = _forest_apply_level(cfg, trees, g, h, feature_masks,
                                    depth, mesh=mesh)
        side_T = jax.vmap(lambda t, hh: _smaller_child(cfg, t, hh, depth)
                          )(trees, h)
        node_T, half_T = (_by_row(mesh, n) for n in jax.vmap(
            lambda t, n, sd: _route_level(cfg, t, binsT, n, depth, sd)
        )(trees, node_T, side_T))
        prev_g, prev_h = g, h

    g, h = _forest_child_histograms(
        cfg, _leaf_columns(binsT), node_T, half_T, grad_T, hess_T,
        cfg.max_depth, _leaf_columns(prev_g, -2), _leaf_columns(prev_h, -2),
        trees, mesh, subtract, side_T)
    trees = jax.vmap(lambda t, gh, hh: _final_leaves(cfg, t, gh, hh)
                     )(trees, g, h)
    if return_nodes:
        return trees, node_T
    return trees


def _forest_child_histograms(cfg: TreeConfig, binsT, node_T, half_T, grad_T,
                             hess_T, depth: int, prev_g, prev_h, trees,
                             mesh, subtract, side_T):
    """Sibling-subtraction for the lockstep forest build (see
    _child_level_histograms): each parent's smaller child (`side_T`,
    (T, P); its rows `half_T`, (T, R)) through the kernel, its sibling
    by parent − built, per tree."""
    level_offset = 2 ** depth - 1
    n_level = 2 ** depth
    use = _use_hist_subtract() if subtract is None else subtract
    slots = _kernel_slots(depth, use)
    if slots == n_level:
        return _forest_level_histograms(binsT, node_T, grad_T, hess_T,
                                        level_offset, n_level,
                                        cfg.n_bins, mesh=mesh)
    gb, hb = _forest_level_histograms(binsT, half_T, grad_T, hess_T,
                                      level_offset, slots, cfg.n_bins,
                                      mesh=mesh)
    split = _parent_split_mask(trees["is_leaf"], trees["feature"],
                               depth)                    # (T, P)
    return _subtract_siblings(prev_g, prev_h, gb, hb, split, side_T)


@jax.named_scope("split")
def _best_splits(gh, cfg: TreeConfig, feature_mask, mesh=None):
    """Pick the best (feature, bin, missing-direction) per node.

    gh: (G, H) each (N, C, B) with the missing bin LAST (index B-1).
    feature_mask: (C,) 1/0 shared by every node (RF feature
    subsetting), or (N, C) per node — the lockstep forest flattens
    (T, P) level nodes to N = T·P and carries each tree's own mask.
    Routed by SHIFU_TPU_SPLIT_FUSED: "pallas" runs the whole
    cumsum+gain+argmax chain as one fused kernel
    (ops/pallas_split.py); this XLA chain is the parity reference.
    Both routes break gain ties identically — lowest flat
    feature·(B-1)+bin index wins (jnp.argmax first-occurrence
    semantics; the kernel docstring explains how it reproduces that
    across column tiles).
    Returns dict of per-node arrays: feature, bin, gain, default_left,
    plus g_tot/h_tot ((N, C) here; (N,) from the fused kernel — the
    per-feature copies are redundant, totals match feature 0's).
    """
    g, h = gh
    from shifu_tpu.ops.pallas_split import (best_splits_pallas,
                                            split_fused_mode)
    if split_fused_mode() == "pallas":
        mask2 = feature_mask if feature_mask.ndim == 2 else \
            jnp.broadcast_to(feature_mask[None, :], g.shape[:2])
        search = partial(best_splits_pallas, lam=float(cfg.reg_lambda),
                         min_inst=float(cfg.min_instances_per_node),
                         interpret=jax.default_backend() != "tpu")
        if mesh is not None and mesh.size > 1:
            # the psum'd histograms are replicated over `mesh`, and a
            # Mosaic kernel cannot be partitioned automatically: every
            # device runs the (small) search on its own copy
            from jax.sharding import PartitionSpec as P
            search = jax.shard_map(search, mesh=mesh,
                                   in_specs=(P(), P(), P()),
                                   out_specs=P(), check_vma=False)
        return search(g, h, mask2)
    lam = cfg.reg_lambda
    g_miss = g[:, :, -1]
    h_miss = h[:, :, -1]
    g_main = g[:, :, :-1]
    h_main = h[:, :, :-1]
    gl = jnp.cumsum(g_main, axis=2)      # left sums for split after bin b
    hl = jnp.cumsum(h_main, axis=2)
    g_tot = gl[:, :, -1] + g_miss        # (N, C)
    h_tot = hl[:, :, -1] + h_miss

    def gain_of(gl_, hl_):
        gr_ = g_tot[:, :, None] - gl_
        hr_ = h_tot[:, :, None] - hl_
        score = (gl_ ** 2 / (hl_ + lam) + gr_ ** 2 / (hr_ + lam)
                 - (g_tot ** 2 / (h_tot + lam))[:, :, None])
        # minimum instances per side (hess≈count when hess=1)
        ok = (hl_ >= cfg.min_instances_per_node) & \
             (hr_ >= cfg.min_instances_per_node)
        return jnp.where(ok, score, -jnp.inf)

    gain_left = gain_of(gl + g_miss[:, :, None], hl + h_miss[:, :, None])
    gain_right = gain_of(gl, hl)
    default_left = gain_left >= gain_right          # (N, C, B-1)
    gain = jnp.maximum(gain_left, gain_right)
    mask3 = feature_mask[None, :, None] if feature_mask.ndim == 1 \
        else feature_mask[:, :, None]
    gain = jnp.where(mask3 > 0, gain, -jnp.inf)
    # the last main bin as split point sends everything left — exclude
    gain = gain.at[:, :, -1].set(-jnp.inf)

    n, c, bm = gain.shape
    flat = gain.reshape(n, c * bm)
    best = jnp.argmax(flat, axis=1)
    best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
    best_feat = (best // bm).astype(jnp.int32)
    best_bin = (best % bm).astype(jnp.int32)
    best_dl = jnp.take_along_axis(
        default_left.reshape(n, c * bm), best[:, None], axis=1)[:, 0]
    return {"feature": best_feat, "bin": best_bin, "gain": best_gain,
            "default_left": best_dl, "g_tot": g_tot, "h_tot": h_tot}


def _empty_tree(cfg: TreeConfig):
    n_nodes = cfg.n_nodes
    return {"feature": jnp.full(n_nodes, -1, jnp.int32),
            "bin": jnp.zeros(n_nodes, jnp.int32),
            "default_left": jnp.zeros(n_nodes, bool),
            "is_leaf": jnp.zeros(n_nodes, bool),
            "leaf_value": jnp.zeros(n_nodes, jnp.float32),
            "gain": jnp.zeros(n_nodes, jnp.float32)}


def _apply_level(cfg: TreeConfig, tree, g_hist, h_hist, feature_mask,
                 depth: int, mesh=None):
    """Fold one level's histograms into the tree state: pick best
    splits, turn no-gain nodes into leaves (value -G/(H+λ)). Shared by
    the resident builder and the out-of-core chunked builder."""
    s = _best_splits((g_hist, h_hist), cfg, feature_mask, mesh=mesh)
    return _fold_splits(cfg, tree, s, depth)


@jax.named_scope("split")
def _fold_splits(cfg: TreeConfig, tree, s, depth: int):
    """Write one level's chosen splits (a `_best_splits` dict) into the
    flat tree arrays. Split off from _apply_level so the lockstep
    forest can run ONE split search over all trees and fold the
    reshaped results per tree (_forest_apply_level)."""
    level_offset = 2 ** depth - 1
    n_level = 2 ** depth
    can_split = (s["gain"] > cfg.min_info_gain) & jnp.isfinite(s["gain"])
    ids = level_offset + jnp.arange(n_level)
    tree = dict(tree)
    tree["feature"] = tree["feature"].at[ids].set(
        jnp.where(can_split, s["feature"], -1))
    tree["bin"] = tree["bin"].at[ids].set(s["bin"])
    tree["default_left"] = tree["default_left"].at[ids].set(
        s["default_left"])
    tree["gain"] = tree["gain"].at[ids].set(
        jnp.where(can_split, s["gain"], 0.0))
    # g_tot/h_tot are identical across features — the XLA chain hands
    # back per-feature copies (take feature 0), the fused kernel (N,)
    g_tot = s["g_tot"] if s["g_tot"].ndim == 1 else s["g_tot"][:, 0]
    h_tot = s["h_tot"] if s["h_tot"].ndim == 1 else s["h_tot"][:, 0]
    val = -g_tot / (h_tot + cfg.reg_lambda)
    tree["is_leaf"] = tree["is_leaf"].at[ids].set(~can_split)
    tree["leaf_value"] = tree["leaf_value"].at[ids].set(
        jnp.where(can_split, 0.0, val))
    return tree


def _forest_apply_level(cfg: TreeConfig, trees, g, h, feature_masks,
                        depth: int, mesh=None):
    """One split search for ALL T trees of a lockstep level: the
    (T, P, C, B) histograms flatten to T·P nodes so the search — fused
    kernel or XLA chain — launches once per level instead of once per
    tree; each tree's RF feature mask rides along per node. This is
    the split-search half of lockstep sharing (the histogram half is
    _forest_level_histograms)."""
    t, p, c, b = g.shape
    mask2 = jnp.repeat(feature_masks, p, axis=0)           # (T·P, C)
    s = _best_splits((g.reshape(t * p, c, b), h.reshape(t * p, c, b)),
                     cfg, mask2, mesh=mesh)
    s_T = jax.tree.map(lambda a: a.reshape((t, p) + a.shape[1:]), s)
    return jax.vmap(lambda tr, sv: _fold_splits(cfg, tr, sv, depth)
                    )(trees, s_T)


@jax.named_scope("leaf")
def _final_leaves(cfg: TreeConfig, tree, g_hist, h_hist):
    """Everything alive at the last level becomes a leaf."""
    level_offset = 2 ** cfg.max_depth - 1
    n_level = 2 ** cfg.max_depth
    g_tot = g_hist[:, 0, :].sum(axis=1)
    h_tot = h_hist[:, 0, :].sum(axis=1)
    ids = level_offset + jnp.arange(n_level)
    tree = dict(tree)
    tree["is_leaf"] = tree["is_leaf"].at[ids].set(True)
    tree["leaf_value"] = tree["leaf_value"].at[ids].set(
        -g_tot / (h_tot + cfg.reg_lambda))
    return tree


# _final_leaves reads the histogram of column 0 and no other, so the
# leaf level's pass is given that column alone (a (1, rows) bins block
# spans its whole axis, which Mosaic takes: PERF.md, PR 27)
_LEAF_COLUMNS = 1


def _leaf_columns(x, axis: int = 0):
    """The first _LEAF_COLUMNS columns of a bin matrix ((C, R) or
    FusedBins, axis 0) or of a level's histograms ((..., P, C, B),
    axis -2); None stays None."""
    if x is None:
        return None
    if isinstance(x, FusedBins):
        return FusedBins(_leaf_columns(x.valuesT), _leaf_columns(x.cuts))
    return jax.lax.slice_in_dim(x, 0, _LEAF_COLUMNS, axis=axis)


def _select(hit, values):
    """The value that `hit` marks along axis 0, bit for bit; 0 (False)
    where it marks none. A select and an integer sum over that axis,
    which XLA fuses into one streamed reduce: no gather op, and float32
    travels as its bits (a float sum against zeros would turn -0.0
    into 0.0)."""
    is_f32 = values.dtype == jnp.float32
    bits = (jax.lax.bitcast_convert_type(values, jnp.int32) if is_f32
            else values.astype(jnp.int32))
    picked = jnp.sum(jnp.where(hit, bits, 0), axis=0)
    if is_f32:
        return jax.lax.bitcast_convert_type(picked, jnp.float32)
    return picked.astype(values.dtype)


def _lookup(table, idx):
    """table[idx] for a small (S,) or (S, K) table and (R,) ids, by a
    compare of every id against the S slots (a per-row gather from a
    511-entry table ran at ~100 M rows/s on the v5e; PERF.md, PR 25).
    The cost is S compares a row, so callers hand it no more than they
    read: routing one level's nodes (S from 1 to 2^(max_depth-1)), the
    boosting update a whole tree's leaf values. An id outside [0, S)
    reads 0. Returns (R,), or (K, R) for a (S, K) table: rows stay on
    the lane axis."""
    s = table.shape[0]
    slots = jnp.arange(s, dtype=jnp.int32).reshape((s,) + (1,) * table.ndim)
    return _select(idx == slots, table[..., None])


def _pick_row(matT, idx):
    """matT[idx[r], r] for a (C, R) matrix and (R,) row ids, the
    gather-free twin of take_along_axis over the C axis: one read of
    the matrix. An id outside [0, C) reads 0."""
    rows = jnp.arange(matT.shape[0], dtype=jnp.int32)
    return _select(idx[None, :] == rows[:, None], matT)


def _route_level(cfg: TreeConfig, tree, binsT, node_of_row, depth: int,
                 side):
    """Advance rows one level: bin <= split_bin → left child (2i+1);
    missing uses the node's default direction. binsT: (C, R). `side`
    (2^depth,): the child of each of the level's nodes that the next
    level's half-width histogram pass builds (`_smaller_child`).
    Returns (the rows' nodes a level down, the node that pass counts
    each row under: `_route_level_at`)."""
    return _route_level_at(cfg, tree, binsT, node_of_row,
                           2 ** depth - 1, 2 ** depth, side)


@jax.named_scope("route")
def _route_level_at(cfg: TreeConfig, tree, binsT, node_of_row,
                    level_offset: int, n_level: int, side):
    """_route_level's core, for the level that holds the n_level nodes
    from level_offset on: both Python ints, so the selects below run
    over that level's own width (1 at the root, 2^d at depth d) and
    never over the 2^max_depth slots the deepest level holds. Both
    per-row lookups (the split of the row's node, the row's bin in
    that split's feature) are selects (`_lookup`, `_pick_row`), all in
    integers: exact, so every builder routes bitwise alike. Rows
    outside the level (parked at a leaf, -1 pad rows) match no slot,
    read feature -1 and stay where they are.

    The second result is sibling subtraction's row selection, made here
    because the row's node is already looked up: a row that went to the
    child `side` names for its node carries that node's slot at the
    child level's offset (the id `_level_histograms` takes for a pass
    of n_level slots over the 2 * n_level children), every other row
    -1, the dump slot. `side` rides the default direction's lookup, so
    it costs no pass of its own; a caller that does not subtract drops
    the result and XLA the arithmetic."""
    level = slice(level_offset, level_offset + n_level)

    def of_node(table):
        # each row's slot of the level's own (n_level,) table (a static
        # slice of the tree's, under vmap over trees too)
        return _lookup(table, node_of_row - level_offset)

    # feature + 1, so that a row no slot matched reads feature -1
    node_feat = of_node(tree["feature"][level] + 1) - 1
    node_bin = of_node(tree["bin"][level])
    dl_side = of_node(tree["default_left"][level] + 2 * side)
    node_dl, node_side = dl_side % 2 == 1, dl_side // 2
    if isinstance(binsT, FusedBins):
        # bin the routed feature's raw value on the fly: the row's
        # value and its feature's K cuts, then a boundary compare; no
        # (C, R) bin matrix exists on the fused path
        vals = _pick_row(binsT.valuesT, node_feat)         # (R,)
        cuts = _lookup(binsT.cuts, node_feat)              # (K, R)
        row_bin = jnp.sum(vals[None, :] >= cuts,
                          axis=0).astype(jnp.int32)
        row_bin = jnp.minimum(row_bin, cfg.n_bins - 2)
        row_bin = jnp.where(jnp.isnan(vals), cfg.n_bins - 1, row_bin)
    else:
        row_bin = _pick_row(binsT, node_feat)
    miss = row_bin == (cfg.n_bins - 1)
    go_left = jnp.where(miss, node_dl, row_bin <= node_bin)
    child = jnp.where(go_left, 0, 1)
    moved = node_feat >= 0
    # the barrier has both results written where they are made: without
    # it XLA hands the three masks on and redoes this arithmetic inside
    # the fusion that writes the kernel's (1, R) slot row, where an
    # elementwise op fills an eighth of a vector register (+0.9 ms a
    # level at 2^24 rows; PERF.md, PR 30)
    return jax.lax.optimization_barrier(
        (jnp.where(moved, 2 * node_of_row + 1 + child, node_of_row),
         jnp.where(moved & (child == node_side), node_of_row + n_level, -1)))


@partial(jax.jit, static_argnames=("cfg", "mesh", "subtract",
                                   "return_nodes"))
def build_tree(cfg: TreeConfig, binsT, grad, hess, feature_mask, mesh=None,
               subtract=None, return_nodes=False):
    """Grow one tree level-by-level (all nodes of a level at once —
    DTMaster's todoNodes batch IS the level here), all levels in this
    one jit (_grow_tree): the histogram kernel is called once a level
    at that level's own slot count (1, 1, 2, ..., 2^(max_depth-1)
    under sibling subtraction), the leaf level on the columns its
    totals read (_leaf_columns).

    binsT: (C, R) int32 TRANSPOSED bin matrix, missing = n_bins-1 (rows
    ride the lane axis — a row-major (R, C) array with C < 128 would
    waste up to 128/C × HBM to lane padding). grad/hess: (R,) float32
    (for RF: grad=label·w, hess=w → leaf = mean label).
    `mesh`: row-shard the histogram build over its 'data' axis
    (see _level_histograms).
    Returns flat arrays sized n_nodes: feature, bin, default_left,
    is_leaf, leaf_value. With return_nodes=True also returns the
    (R,) landing node of every row — growth already routed each row
    to its final node (leaves park: _route_level only advances rows
    whose node has feature >= 0), so callers that need per-row leaf
    values (the boosting update) can gather leaf_value[node] instead
    of re-walking the tree from the root (predict_trees), saving
    max_depth gathers over the (C, R) bin matrix per round.
    """
    tree, node_of_row = _grow_tree(cfg, binsT, grad, hess, feature_mask,
                                   mesh, subtract)
    if return_nodes:
        return tree, node_of_row
    return tree


def _grow_tree(cfg: TreeConfig, binsT, grad, hess, feature_mask, mesh,
               subtract, node0=None):
    """THE growth loop of a single tree, unrolled over depth when the
    caller's jit traces it, so every level's shapes are that level's
    own: its histogram pass covers the slots it reads
    (`_kernel_slots`), its split search and its routing selects the 2^d
    nodes it holds. node0: the rows' starting nodes (the resident
    streaming tier parks its pad rows at -1, which no level's slots
    match). On a
    data mesh the loop's row state (gradients, node ids; the slot row in
    `_level_histograms`) is held to the rows' layout level by level
    (`_by_row`), not left to sharding propagation.
    Returns (tree, landing node of every row)."""
    tree = _empty_tree(cfg)
    grad, hess = _by_row(mesh, grad), _by_row(mesh, hess)
    node_of_row = _by_row(mesh, jnp.zeros(binsT.shape[1], jnp.int32)
                          if node0 is None else node0)

    prev_g = prev_h = side = half_node = None
    for depth in range(cfg.max_depth):
        g_hist, h_hist = _child_level_histograms(
            cfg, binsT, node_of_row, half_node, grad, hess, depth, prev_g,
            prev_h, tree["is_leaf"], tree["feature"], mesh, subtract, side)
        tree = _apply_level(cfg, tree, g_hist, h_hist, feature_mask, depth,
                            mesh=mesh)
        side = _smaller_child(cfg, tree, h_hist, depth)
        node_of_row, half_node = (
            _by_row(mesh, n) for n in _route_level(cfg, tree, binsT,
                                                   node_of_row, depth, side))
        prev_g, prev_h = g_hist, h_hist

    g_hist, h_hist = _child_level_histograms(
        cfg, _leaf_columns(binsT), node_of_row, half_node, grad, hess,
        cfg.max_depth, _leaf_columns(prev_g, -2), _leaf_columns(prev_h, -2),
        tree["is_leaf"], tree["feature"], mesh, subtract, side)
    return _final_leaves(cfg, tree, g_hist, h_hist), node_of_row


def _use_hist_subtract() -> bool:
    import os
    return knob_bool("SHIFU_TPU_HIST_SUBTRACT")


def _kernel_slots(depth: int, subtract: bool) -> int:
    """Slots the histogram pass of level `depth` covers: one child of
    every parent under sibling subtraction (2^(depth-1); the root has no
    parent), the level's 2^depth nodes without."""
    return 2 ** (depth - 1) if subtract and depth > 0 else 2 ** depth


def _child_level_histograms(cfg: TreeConfig, binsT, node_of_row, half_node,
                            grad, hess, depth: int, prev_g, prev_h,
                            is_leaf, feature, mesh, subtract, side):
    """Level histograms with the sibling-subtraction trick: at depth
    d ≥ 1 only ONE child of every parent goes through the histogram
    kernel, at its parent's slot (children of parent k land at
    level-local 2k/2k+1), and the sibling = parent − built from the
    previous level's histograms. `side` (P,) says which: 0 the left
    child, 1 the right (`_smaller_child`: the one of less hessian), and
    `half_node` is the rows' selection for that pass, as the routing
    into this level made it (`_route_level`). Kernel work per level
    halves (Σ 2^d slot-levels → Σ 2^(d-1)), the standard GBDT
    histogram-subtraction optimization, and building the SMALLER child
    is the standard form of it: a float32 histogram over 10^8 rows is
    off by a few units a bin, which a subtraction hands on whole, so it
    has to land on the larger sibling, where it is nothing, and never
    on a sibling of a few rows, where it would be everything (PERF.md,
    PR 30). Children of leaf parents are masked to zero (the
    subtraction would otherwise resurrect the parent's rows as a
    phantom child). Disable with SHIFU_TPU_HIST_SUBTRACT=0 (`subtract`
    None reads it)."""
    level_offset = 2 ** depth - 1
    n_level = 2 ** depth
    use = _use_hist_subtract() if subtract is None else subtract
    slots = _kernel_slots(depth, use)
    if slots == n_level:
        return _level_histograms(binsT, node_of_row, grad, hess,
                                 level_offset, n_level, cfg.n_bins,
                                 mesh=mesh)
    gb, hb = _level_histograms(binsT, half_node, grad, hess,
                               level_offset, slots, cfg.n_bins, mesh=mesh)
    split = _parent_split_mask(is_leaf, feature, depth)
    return _subtract_siblings(prev_g, prev_h, gb, hb, split, side)


@partial(jax.jit, static_argnames=("cfg", "depth"))
@jax.named_scope("hist")
def _smaller_child(cfg: TreeConfig, tree, h_hist, depth: int):
    """(2^depth,) int32: for every node of the level `depth`, whose
    splits `tree` now holds and whose hessian histograms are h_hist
    (P, C, B), the child that gets less of the node's hessian: 0 left,
    1 right. Read off the split feature's own bins, as selects over the
    small histogram block (no gather)."""
    level = slice(2 ** depth - 1, 2 ** (depth + 1) - 1)
    feat, split_bin = tree["feature"][level], tree["bin"][level]
    cols = jnp.arange(h_hist.shape[1], dtype=jnp.int32)
    h_f = jnp.sum(jnp.where(feat[:, None, None] == cols[None, :, None],
                            h_hist, 0.0), axis=1)              # (P, B)
    bins = jnp.arange(cfg.n_bins - 1, dtype=jnp.int32)
    h_left = jnp.sum(jnp.where(bins[None, :] <= split_bin[:, None],
                               h_f[:, :-1], 0.0), axis=1) \
        + jnp.where(tree["default_left"][level], h_f[:, -1], 0.0)
    return (jnp.sum(h_f, axis=1) - h_left < h_left).astype(jnp.int32)


@jax.named_scope("hist")
def _parent_split_mask(is_leaf, feature, depth):
    """(... , P) bool: which previous-level parents actually split
    (their children exist). is_leaf/feature index node arrays with an
    optional leading tree axis."""
    parent_ids = (2 ** (depth - 1) - 1) + jnp.arange(2 ** (depth - 1))
    return (~is_leaf[..., parent_ids]) & (feature[..., parent_ids] >= 0)


@jax.named_scope("hist")
def _subtract_siblings(prev_g, prev_h, gb, hb, split, side):
    """Shared sibling-subtraction core (single tree (P, C, B) or
    lockstep forest (T, P, C, B); `split` and `side` carry the matching
    leading dims): mask leaf parents, derive sibling = parent − built,
    where the built child (gb, hb) is `side`'s, and interleave (left0,
    right0, left1, ...) back into the full level of 2 P nodes. The one
    subtraction rule of every builder, so child ordering can never
    desynchronize between them."""
    m = split[..., None, None]
    gb = jnp.where(m, gb, 0.0)
    hb = jnp.where(m, hb, 0.0)
    go = jnp.where(m, prev_g - gb, 0.0)
    ho = jnp.where(m, prev_h - hb, 0.0)
    left_built = (side == 0)[..., None, None]
    gl, gr = jnp.where(left_built, gb, go), jnp.where(left_built, go, gb)
    hl, hr = jnp.where(left_built, hb, ho), jnp.where(left_built, ho, hb)
    lead, (p, c, b) = gl.shape[:-3], gl.shape[-3:]
    g = jnp.stack([gl, gr], axis=-3).reshape(lead + (2 * p, c, b))
    h = jnp.stack([hl, hr], axis=-3).reshape(lead + (2 * p, c, b))
    return g, h


def _walk_trees(trees, binsT, max_depth: int, n_bins: int):
    """Per-tree landing node of every row. binsT: (C, R)."""
    if isinstance(binsT, FusedBins):
        # prediction re-walks every feature per level — bin once here
        # rather than re-deriving per gather (the fused path optimizes
        # the level BUILD; a resume/val predict is a one-off)
        from shifu_tpu.ops.pallas_hist import bins_from_values
        binsT = bins_from_values(binsT.valuesT, binsT.cuts, n_bins)

    def one_tree(tree):
        r = binsT.shape[1]
        node = jnp.zeros(r, jnp.int32)
        for _ in range(max_depth):
            feat = tree["feature"][node]
            sbin = tree["bin"][node]
            dl = tree["default_left"][node]
            leaf = tree["is_leaf"][node]
            row_bin = jnp.take_along_axis(
                binsT, jnp.maximum(feat, 0)[None, :], axis=0)[0]
            miss = row_bin == (n_bins - 1)
            go_left = jnp.where(miss, dl, row_bin <= sbin)
            nxt = 2 * node + jnp.where(go_left, 1, 2)
            node = jnp.where(leaf | (feat < 0), node, nxt)
        return node

    return jax.vmap(one_tree)(trees)


@partial(jax.jit, static_argnames=("max_depth", "n_bins"))
def predict_trees(trees, binsT, max_depth: int, n_bins: int):
    """Sum of per-tree leaf values. trees: pytree of (T, n_nodes)
    arrays; binsT: (C, R) transposed. Returns (T, R) raw scores (caller
    averages for RF / shrinks+offsets for GBT)."""
    nodes = _walk_trees(trees, binsT, max_depth, n_bins)
    return jax.vmap(lambda tree, n: tree["leaf_value"][n])(trees, nodes)


@partial(jax.jit, static_argnames=("max_depth", "n_bins"))
def leaf_indices(trees, binsT, max_depth: int, n_bins: int):
    """Per-tree landing leaf id for every row — the tree-path encoding
    of `udf/EncodeDataUDF.java` (each record becomes one categorical
    value per tree). binsT: (C, R). Returns (T, R) int32 node ids."""
    return _walk_trees(trees, binsT, max_depth, n_bins)


# ---------------------------------------------------------------------------
# Forest builders
# ---------------------------------------------------------------------------

@jax.named_scope("gradients")
def gbt_gradients(y, pred_raw, weights, loss: str):
    """First/second-order gradients (dt/Loss.java squared/log).
    Elementwise, so broadcasting y (R,) or (1, R) against (T, R)
    predictions/weights yields per-bag gradients for the lockstep
    bagged build."""
    if loss.startswith("log"):
        p = jax.nn.sigmoid(pred_raw)
        return (p - y) * weights, p * (1 - p) * weights
    return (pred_raw - y) * weights, jnp.ones_like(y) * weights


@partial(jax.jit, static_argnames=("loss",))
def _val_error(vraw, vy, vw, loss: str):
    """THE early-stop validation metric — weighted mean squared error
    on (sigmoid-squashed, for log loss) raw scores. One shared jitted
    definition (same dtype, same f32 jnp reduction) for build_gbt, the
    lockstep bagged builder, and BOTH streaming tiers, so an
    early-stop decision can never diverge between builders on metric
    arithmetic. vraw broadcasts: (R,) → scalar, (T, R) → per-bag (T,)
    errors in one dispatch."""
    vp = jax.nn.sigmoid(vraw) if loss.startswith("log") else vraw
    return (jnp.sum((vp - vy) ** 2 * vw, axis=-1)
            / jnp.maximum(jnp.sum(vw), 1e-12))


def _pace_dispatch(x) -> None:
    """Sync via a LOCALLY-addressable shard of a device array: `x` is
    row-sharded, and indexing x[0] on a multi-host mesh raises "spans
    non-addressable devices" on the processes that don't hold shard 0.
    The sync IS the point — it paces the grouped-scan dispatch loops to
    one long execute in flight by fetching a value, which cannot
    return before the device produced it — so the lint rule is wrong
    to want it hoisted. (Whether pacing still pays on a directly
    attached chip is ROADMAP D2's A/B; behaviour is unchanged here.)"""
    np.asarray(x.addressable_shards[0].data[:1])  # lint: disable=host-sync-in-hot-loop -- deliberate scalar fetch paces device dispatch


def _gbt_round_core(cfg: TreeConfig, binsT, y, weights, pred_raw,
                    feature_mask, mesh=None, subtract=None):
    grad, hess = gbt_gradients(y, _by_row(mesh, pred_raw), weights,
                               cfg.loss)
    # growth already landed every row on its leaf: one (R,) lookup of
    # leaf_value replaces a full predict_trees re-walk (max_depth
    # passes over the (C, R) bin matrix) for the boosting update
    tree, node_of_row = build_tree(cfg, binsT, grad, hess, feature_mask,
                                   mesh=mesh, subtract=subtract,
                                   return_nodes=True)
    with jax.named_scope("leaf"):
        contrib = _lookup(tree["leaf_value"], node_of_row)
        return (_replicated(mesh, tree),
                _by_row(mesh, pred_raw + cfg.learning_rate * contrib))


@partial(jax.jit, static_argnames=("cfg", "mesh", "subtract"))
def _gbt_round(cfg: TreeConfig, binsT, y, weights, pred_raw, feature_mask,
               mesh=None, subtract=None):
    return _gbt_round_core(cfg, binsT, y, weights, pred_raw, feature_mask,
                           mesh=mesh, subtract=subtract)


@partial(jax.jit, static_argnames=("cfg", "n_rounds", "mesh", "subtract"))
def _gbt_rounds(cfg: TreeConfig, binsT, y, weights, pred_raw,
                feature_mask, n_rounds: int, mesh=None, subtract=None):
    """ALL boosting rounds in one dispatch (lax.scan over rounds): a
    20-tree build is one host→device round-trip instead of 20. Rounds
    are sequential by nature, but each round's shapes are identical, so
    the whole loop compiles once and runs device-side, paying one
    dispatch latency instead of one per round. Used whenever no
    per-round early stop is
    requested; returns (stacked trees with a leading round axis,
    final raw predictions)."""
    def body(pred, _):
        tree, pred2 = _gbt_round_core(cfg, binsT, y, weights, pred,
                                      feature_mask, mesh=mesh,
                                      subtract=subtract)
        return pred2, tree
    pred_out, trees = jax.lax.scan(body, pred_raw, None, length=n_rounds)
    return trees, pred_out


def _build_meshes(bins):
    """(mesh that host inputs are placed over, histogram mesh or None)
    of a resident build, from what the builder is handed. Host inputs:
    the process's default data mesh. A device input says where it
    lives: rows over the 'data' axis of its own mesh make that mesh
    the histogram mesh (`rows.rows_mesh`; any other layout over several
    devices raises), and an array on one device is built on that
    device, whatever else the host holds. The histogram mesh is None
    where its data axis is one chip: the one-device program."""
    from shifu_tpu.parallel import mesh as mesh_mod, rows
    if isinstance(bins, jax.Array):
        hist_mesh = rows.rows_mesh(bins, axis=1)
        return hist_mesh or mesh_mod.make_mesh(
            n_data=1, devices=list(bins.sharding.device_set)), hist_mesh
    mesh = mesh_mod.default_mesh()
    return mesh, (mesh if _data_size(mesh) > 1 else None)


def _n_columns(bins) -> int:
    """Columns of a builder's bins: axis 0 of the (C, R) device and
    FusedBins layouts, axis 1 of row-major host bins."""
    if isinstance(bins, (jax.Array, FusedBins)):
        return int(bins.shape[0])
    return int(np.shape(bins)[1])


def _zeros_by_row(shape, mesh):
    """float32 zeros of a per-row shape, made where the rows lie."""
    if mesh is None:
        return jnp.zeros(shape, jnp.float32)
    return jnp.zeros(shape, jnp.float32,
                     device=_row_sharding(mesh, len(shape)))


def build_gbt(cfg: TreeConfig, bins: np.ndarray, y: np.ndarray,
              weights: np.ndarray, n_trees: int,
              feature_mask: Optional[np.ndarray] = None,
              init_trees: Optional[Any] = None,
              val_data: Optional[Tuple] = None,
              early_stop_window: int = 0):
    """Sequential boosting (host loop — rounds are data-dependent).
    Returns (stacked trees pytree, per-round val errors). init_trees
    resumes a previous ensemble (GBT continuous training appends
    trees, TrainModelProcessor.java:1064-1073).

    Host inputs ((R, C) bins, row-major) shard by row over the
    process's default data mesh; zero-weight padding keeps
    gradients/hessians (and hence histograms and leaf values) exact.

    A device input (`bins` a jax.Array) is taken as ALREADY transposed,
    (C, R), and placed, and the build runs where it lies
    (`_build_meshes`): on its one device, or, with its rows (axis 1)
    divided over the 'data' axis of a mesh and nothing else divided
    (`NamedSharding(mesh, P(None, "data"))`, so R is a multiple of the
    axis size), on that mesh: per-chip histograms, one all-reduce a
    level, row state sharded as the rows are. Any other layout over
    several devices raises; nothing is padded, moved or gathered. `y`
    and `weights` are placed by row beside it, or must lie so already.
    """
    from shifu_tpu.parallel import mesh as mesh_mod, rows
    mesh, hist_mesh = _build_meshes(bins)
    n_cols = _n_columns(bins)
    # env resolved HERE, outside jit: subtract is a static jit arg, so
    # an env flip after first compile must produce a fresh trace, not a
    # silent cache hit on whatever was compiled first
    subtract = _use_hist_subtract()
    with obs_trace.span("train.job", family="gbt", rows=int(y.shape[0]),
                        steps=n_trees, bags=1, chips=_data_size(hist_mesh),
                        psum_bytes=psum_bytes(cfg, n_cols, hist_mesh,
                                              subtract)):
        with obs_trace.span("train.prepare"):
            fm = jnp.asarray(feature_mask if feature_mask is not None
                             else np.ones(n_cols, np.float32))
        with obs_trace.span("train.place"):
            # device bins are TRANSPOSED (C, R): rows on the lane axis, so a
            # narrow feature matrix doesn't lane-pad to 128 columns in HBM
            # (device-resident data skips the host round-trip entirely).
            if isinstance(bins, jax.Array):
                jb = bins
                jy = rows.rows_over(hist_mesh, y)
                jw = rows.rows_over(hist_mesh, weights)
            elif isinstance(bins, FusedBins):
                # fused path (SHIFU_TPU_HIST_FUSED): raw values shard like the
                # bin matrix would (NaN pad rows land in the missing bin with
                # zero weight); the small cut table replicates
                jb = FusedBins(
                    mesh_mod.shard_axis(
                        mesh,
                        np.ascontiguousarray(
                            np.asarray(bins.valuesT, np.float32)),
                        1, pad_value=np.nan),
                    jnp.asarray(np.asarray(bins.cuts, np.float32)))
                jy, jw = mesh_mod.shard_rows(mesh, np.asarray(y, np.float32),
                                             np.asarray(weights, np.float32))
            else:
                jb = mesh_mod.shard_axis(
                    mesh,
                    np.ascontiguousarray(np.asarray(bins, np.int32).T), 1,
                    pad_value=0)
                jy, jw = mesh_mod.shard_rows(mesh, np.asarray(y, np.float32),
                                             np.asarray(weights, np.float32))
            trees: List[Any] = []
            pred = _zeros_by_row((jb.shape[1],), hist_mesh)
            if init_trees is not None:
                n_prev = init_trees["feature"].shape[0]
                trees = [jax.tree.map(lambda a, i=i: a[i], init_trees)
                         for i in range(n_prev)]
                pred = cfg.learning_rate * jnp.sum(predict_trees(
                    init_trees, jb, cfg.max_depth, cfg.n_bins), axis=0)
            val_errs = []
            best_val, bad = np.inf, 0
            vraw = None
            if val_data is not None:
                vb, vy = val_data
                n_val = vb.shape[0]
                vb = mesh_mod.shard_axis(
                    mesh, np.ascontiguousarray(np.asarray(vb, np.int32).T), 1)
                vy, vw = mesh_mod.shard_rows(
                    mesh, np.asarray(vy, np.float32),
                    np.ones(n_val, np.float32))
                vraw = jnp.zeros(vb.shape[1], jnp.float32)
                if init_trees is not None:
                    vraw = cfg.learning_rate * jnp.sum(predict_trees(
                        init_trees, vb, cfg.max_depth, cfg.n_bins), axis=0)
        if val_data is None and n_trees > 0:
            # no per-round host decision to make → scan rounds device-side
            # (see _gbt_rounds), in groups of SHIFU_TPU_GBT_SCAN_GROUP
            # rounds per dispatch (0/unset = all rounds in one). Grouping
            # bounds how long a single execute runs; equal-size groups
            # reuse one compiled program, and a scalar FETCH between groups
            # (_pace_dispatch) keeps exactly one long execute in flight.
            # Whether a directly attached chip needs either is ROADMAP D2.
            group = knob_int("SHIFU_TPU_GBT_SCAN_GROUP")
            group = n_trees if group <= 0 else min(group, n_trees)
            parts = []
            for start in range(0, n_trees, group):
                k = min(group, n_trees - start)
                with obs_trace.span("train.program", steps=k):
                    part, pred = _gbt_rounds(cfg, jb, jy, jw, pred, fm,
                                             k, mesh=hist_mesh,
                                             subtract=subtract)
                if start + k < n_trees:
                    with obs_trace.span("train.wait"):
                        _pace_dispatch(pred)
                parts.append(part)
            # the host waiting on the device, apart from the copies
            # and the assembly it then makes
            with obs_trace.span("train.wait"):
                jax.block_until_ready(parts)
            with obs_trace.span("train.fetch"):
                new_stacked = parts[0] if len(parts) == 1 else \
                    jax.tree.map(lambda *a: jnp.concatenate(a), *parts)
                if init_trees is not None:
                    # continuous-training resume: prepend the old
                    # ensemble (init_trees IS the stacked pytree already)
                    new_stacked = jax.tree.map(
                        lambda p, n: jnp.concatenate([jnp.asarray(p), n]),
                        init_trees, new_stacked)
                return jax.tree.map(np.asarray, new_stacked), []
        for t in range(n_trees):
            with obs_trace.span("train.program", steps=1):
                tree, pred = _gbt_round(cfg, jb, jy, jw, pred, fm,
                                        mesh=hist_mesh, subtract=subtract)
                if val_data is not None:
                    vraw = vraw + cfg.learning_rate * predict_trees(
                        jax.tree.map(lambda a: a[None], tree), vb,
                        cfg.max_depth, cfg.n_bins)[0]
            trees.append(tree)
            if val_data is not None:
                # weighted mean (_val_error) so zero-weight padding rows
                # don't bias it; the early-stop decision is a per-round
                # host branch, so this sync is intentional — host_fetch
                # times and counts it
                with obs_trace.span("train.wait"):
                    err = float(host_fetch(
                        _val_error(vraw, vy, vw, cfg.loss)))
                val_errs.append(err)
                if err < best_val - 1e-9:
                    best_val, bad = err, 0
                else:
                    bad += 1
                    if early_stop_window and bad >= early_stop_window:
                        break
        with obs_trace.span("train.fetch"):
            stacked = jax.tree.map(lambda *a: jnp.stack(a), *trees)
            stacked = jax.tree.map(np.asarray, stacked)
        return stacked, val_errs


def _gbt_bagged_round_core(cfg: TreeConfig, binsT, y, w_T, pred_T,
                           fm_T, mesh=None, subtract=None):
    grad_T, hess_T = gbt_gradients(y[None, :], _by_row(mesh, pred_T), w_T,
                                   cfg.loss)
    trees_T, node_T = build_forest(cfg, binsT, grad_T, hess_T, fm_T,
                                   mesh=mesh, subtract=subtract,
                                   return_nodes=True)
    with jax.named_scope("leaf"):
        contrib_T = jax.vmap(lambda tr, n: _lookup(tr["leaf_value"], n)
                             )(trees_T, node_T)
        return (_replicated(mesh, trees_T),
                _by_row(mesh, pred_T + cfg.learning_rate * contrib_T))


@partial(jax.jit, static_argnames=("cfg", "mesh", "subtract"))
def _gbt_bagged_round(cfg: TreeConfig, binsT, y, w_T, pred_T, fm_T,
                      mesh=None, subtract=None):
    return _gbt_bagged_round_core(cfg, binsT, y, w_T, pred_T, fm_T,
                                  mesh=mesh, subtract=subtract)


@partial(jax.jit, static_argnames=("cfg", "n_rounds", "mesh", "subtract"))
def _gbt_bagged_rounds(cfg: TreeConfig, binsT, y, w_T, pred_T, fm_T,
                       n_rounds: int, mesh=None, subtract=None):
    def body(pred, _):
        trees_T, pred2 = _gbt_bagged_round_core(
            cfg, binsT, y, w_T, pred, fm_T, mesh=mesh, subtract=subtract)
        return pred2, trees_T
    pred_out, trees = jax.lax.scan(body, pred_T, None, length=n_rounds)
    return trees, pred_out


def build_gbt_bagged(cfg: TreeConfig, bins: np.ndarray, y: np.ndarray,
                     weights_T: np.ndarray, n_trees: int,
                     feature_mask: Optional[np.ndarray] = None,
                     val_data: Optional[Tuple] = None,
                     early_stop_window: int = 0):
    """Lockstep bagged boosting: grow the round-t tree of ALL n_bags
    sibling ensembles at once through the forest kernels — one
    histogram collective and one split search per level cover every
    bag, where the per-bag sequential loop (processor/train_tree)
    dispatched them T times. Bags stay mathematically independent
    (each sees only its own weight row of `weights_T` (T, R)), so each
    bag's ensemble is parity-gated against a sequential build_gbt with
    the same weights (tests/test_gbt_device.py).

    Early stop is per bag: every bag keeps building in lockstep (a
    stopped bag's extra rounds cost nothing extra — they ride the same
    dispatch) and its ensemble/val history is truncated to its own
    stop round afterwards, which is exactly what the sequential loop
    would have kept. Returns a list of (stacked trees pytree,
    val_errs) per bag. Device inputs as `build_gbt` takes them
    (`weights_T` with its rows on axis 1)."""
    from shifu_tpu.parallel import mesh as mesh_mod, rows
    n_bags = int(weights_T.shape[0])
    mesh, hist_mesh = _build_meshes(bins)
    n_cols = _n_columns(bins)
    subtract = _use_hist_subtract()
    with obs_trace.span("train.job", family="gbt", rows=int(y.shape[0]),
                        steps=n_trees, bags=n_bags,
                        chips=_data_size(hist_mesh),
                        psum_bytes=psum_bytes(cfg, n_cols, hist_mesh,
                                              subtract, n_bags)):
        with obs_trace.span("train.prepare"):
            fm = np.asarray(feature_mask if feature_mask is not None
                            else np.ones(n_cols, np.float32), np.float32)
        with obs_trace.span("train.place"):
            if isinstance(bins, jax.Array):
                jb = bins
                jy = rows.rows_over(hist_mesh, y)
                jw_T = rows.rows_over(hist_mesh, weights_T, axis=1)
            else:
                jb = mesh_mod.shard_axis(
                    mesh,
                    np.ascontiguousarray(np.asarray(bins, np.int32).T), 1,
                    pad_value=0)
                jy = mesh_mod.shard_rows(mesh, np.asarray(y, np.float32))
                jw_T = mesh_mod.shard_axis(
                    mesh, np.asarray(weights_T, np.float32), 1)
            fm_T = jnp.asarray(np.broadcast_to(fm[None, :],
                                               (n_bags, fm.size)))
            pred_T = _zeros_by_row((n_bags, jb.shape[1]), hist_mesh)

        if val_data is None and n_trees > 0:
            # no per-round host decision → scan rounds device-side in
            # SHIFU_TPU_GBT_SCAN_GROUP-sized dispatches (see build_gbt)
            group = knob_int("SHIFU_TPU_GBT_SCAN_GROUP")
            group = n_trees if group <= 0 else min(group, n_trees)
            parts = []
            for start in range(0, n_trees, group):
                k = min(group, n_trees - start)
                with obs_trace.span("train.program", steps=k):
                    part, pred_T = _gbt_bagged_rounds(
                        cfg, jb, jy, jw_T, pred_T, fm_T, k, mesh=hist_mesh,
                        subtract=subtract)
                if start + k < n_trees:
                    with obs_trace.span("train.wait"):
                        _pace_dispatch(pred_T)
                parts.append(part)
            with obs_trace.span("train.wait"):
                jax.block_until_ready(parts)
            with obs_trace.span("train.fetch"):
                rounds_T = parts[0] if len(parts) == 1 else jax.tree.map(
                    lambda *a: jnp.concatenate(a), *parts)
                rounds_np = jax.tree.map(np.asarray,
                                         rounds_T)   # (rounds, T, nodes)
                return [(jax.tree.map(lambda a, b=b: a[:, b], rounds_np),
                         []) for b in range(n_bags)]

        with obs_trace.span("train.place"):
            vb, vy = val_data
            n_val = vb.shape[0]
            vb = mesh_mod.shard_axis(
                mesh, np.ascontiguousarray(np.asarray(vb, np.int32).T), 1)
            vy, vw = mesh_mod.shard_rows(
                mesh, np.asarray(vy, np.float32),
                np.ones(n_val, np.float32))
            vraw_T = jnp.zeros((n_bags, vb.shape[1]), jnp.float32)
        round_trees: List[Any] = []
        val_errs = [[] for _ in range(n_bags)]
        best_val = np.full(n_bags, np.inf)
        bad = np.zeros(n_bags, np.int64)
        stop_round = np.full(n_bags, 0)
        for t in range(n_trees):
            with obs_trace.span("train.program", steps=1):
                trees_T, pred_T = _gbt_bagged_round(
                    cfg, jb, jy, jw_T, pred_T, fm_T, mesh=hist_mesh,
                    subtract=subtract)
                vraw_T = vraw_T + cfg.learning_rate * predict_trees(
                    trees_T, vb, cfg.max_depth, cfg.n_bins)
            round_trees.append(trees_T)
            # ONE fetch decides every bag's round: (T,) error vector
            with obs_trace.span("train.wait"):
                errs = host_fetch(_val_error(vraw_T, vy, vw, cfg.loss))
            for b in range(n_bags):
                if stop_round[b]:
                    continue
                err = float(errs[b])
                val_errs[b].append(err)
                if err < best_val[b] - 1e-9:
                    best_val[b], bad[b] = err, 0
                else:
                    bad[b] += 1
                    if early_stop_window and bad[b] >= early_stop_window:
                        stop_round[b] = t + 1
            if early_stop_window and stop_round.all():
                break
        with obs_trace.span("train.fetch"):
            stop_round[stop_round == 0] = len(round_trees)
            stacked = jax.tree.map(lambda *a: jnp.stack(a), *round_trees)
            stacked = jax.tree.map(np.asarray,
                                   stacked)  # (rounds, T, nodes)
            return [(jax.tree.map(lambda a, b=b: a[:stop_round[b], b],
                                  stacked),
                     val_errs[b]) for b in range(n_bags)]


# ---------------------------------------------------------------------------
# Random forest: lockstep groups, bags and feature subsets drawn on the
# device (models/rf_draw.py)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg", "mesh", "subtract"))
def _rf_grow(cfg: TreeConfig, binsT, y, w, inst_w, masks, mesh=None,
             subtract=None):
    """One lockstep group of a random forest: the trees whose instance
    weights are `inst_w` (G, R) and feature masks `masks` (G, C). Leaf
    value = weighted mean label: grad = -y·w·iw, hess = w·iw."""
    with jax.named_scope("gradients"):
        inst_w = _by_row(mesh, inst_w)
        grad_T = -(y * w * inst_w)
        hess_T = w * inst_w
    return _replicated(mesh, build_forest(cfg, binsT, grad_T, hess_T, masks,
                                          mesh=mesh, subtract=subtract))


# What a lockstep group of G trees holds beside the table at the deepest
# level of `_rf_grow`, in bytes a row, as the chip's compiler lays it out
# (tests/test_chip_compile.py holds the compiled program to it). A
# (G, rows) array lies in sublane tiles of 1, 2, 4 or 8 trees, so G pads
# to `_sublane_tile(G)`: ten such 4-byte arrays are live at once (grad,
# hess, node, the half-level node, the kernel's slot row, routing's two
# results beside the two they replace, the masks they are selected by):
# 40 B a padded tree. The kernel's packed (8, rows) [slot, grad, hess]
# operand is 32 B a tree, and under `vmap` its three row writes keep a
# second copy alive beside it: 72 B a tree with the level's small change.
_RF_ROW_BYTES_A_PADDED_TREE = 40
_RF_ROW_BYTES_A_TREE = 72
# The share of the device's memory a group may plan to fill: the rest is
# the allocator's fragmentation and the caller's other arrays.
_RF_MEMORY_SHARE = 0.75


def _sublane_tile(n_trees: int) -> int:
    """Trees a (G, rows) 32-bit array of n_trees is padded to: the
    sublane tile (1, 2, 4, then multiples of 8)."""
    return next((t for t in (1, 2, 4) if n_trees <= t), -(-n_trees // 8) * 8)


def rf_group_bytes(n_trees: int, rows_a_chip: int, n_cols: int) -> int:
    """Bytes a chip holds while a lockstep group of n_trees grows: the
    table ((columns padded to 8 sublanes) x rows int32 bins, labels,
    weights), the group's instance weights, and its row state."""
    tile = _sublane_tile(n_trees)
    table = 4 * (-(-n_cols // 8) * 8 + 2)
    return rows_a_chip * (table + (4 + _RF_ROW_BYTES_A_PADDED_TREE) * tile
                          + _RF_ROW_BYTES_A_TREE * n_trees)


def _rf_group_trees(n_trees: int, rows_a_chip: int, n_cols: int,
                    device) -> int:
    """Trees `build_rf` grows in lockstep at a time, from bytes alone:
    the largest whole sublane tile (8 and its multiples, else 4, 2, 1:
    a (G, rows) array pads G to its tile, so a group between tiles pays
    for trees it does not grow) whose `rf_group_bytes` stay inside
    `_RF_MEMORY_SHARE` of the device's memory
    (`memory_stats()["bytes_limit"]`). A backend that reports no limit
    (the CPU) takes the whole forest as one group."""
    limit = (device.memory_stats() or {}).get("bytes_limit")
    if not limit:
        return n_trees
    room = _RF_MEMORY_SHARE * limit

    def fits(g):
        return rf_group_bytes(g, rows_a_chip, n_cols) <= room

    if fits(n_trees):
        return n_trees
    group, tile = 1, 2
    while tile < n_trees and fits(tile):
        group, tile = tile, _sublane_tile(tile + 1)
    return group


def build_rf(cfg: TreeConfig, bins: np.ndarray, y: np.ndarray,
             weights: np.ndarray, n_trees: int, subset_strategy: str,
             bagging_rate: float, seed: int,
             stratified: bool = False, neg_only: bool = False):
    """Random forest: independent trees grown in lockstep groups
    (build_forest: one histogram pass and one split search a level cover
    a group) with per-tree Poisson instance weights (DTWorker's Poisson
    sampling) and feature subsets. The histograms go through the same
    explicit shard_map + psum collective as GBT.

    Inputs as `build_gbt` takes them: host (R, C) bins are placed by row
    over the process's default data mesh; a device input is taken as
    ALREADY transposed, (C, R), and placed, and the forest is built
    where it lies (`_build_meshes`), `y` and `weights` laid by row
    beside it; nothing is fetched, transposed or gathered.

    **The draw** is the same for host and device inputs and is made on
    the device (`models/rf_draw.py`, whose docstring is the rule: tree
    t's bag and subset hang on `fold_in(jax.random.key(seed), t)`; a
    bag is Poisson(`bagging_rate`) by inversion of 32 uniform bits a
    row, a subset the `feature_subset_count(subset_strategy, C)`
    columns of smallest drawn bits; scope `bag`, span `train.bag`). A
    tree's gradients are `-y·w·iw`, its hessians `w·iw`, made inside
    the group's program (`_rf_grow`).

    `stratified`/`neg_only` (train.stratifiedSample / sampleNegOnly)
    shape the per-TREE instance weights — the reference DTWorker honors
    both for RF (`dt/DTWorker.java:530,660,1390,1550`): exact per-class
    counts need the labels on the host, so these two keep
    `trainer.bagging_weights`' host draw (its semantics, its numpy
    generator) and upload a group's weights at a time; the feature
    subsets are the device's either way.

    **Groups.** The forest grows `_rf_group_trees` trees at a time, a
    number read from the rows, the columns and the device's memory
    (`rf_group_bytes`): a lockstep group holds about 112 bytes a row for
    every tree, so on a 16 GB chip 2^24 rows take 4 trees at a time and
    2^23 rows 8. Trees are independent and every tree's draw hangs on
    its own index t, so the grouping changes no tree. One group's
    program is in flight at a time."""
    from shifu_tpu.models import rf_draw
    from shifu_tpu.parallel import mesh as mesh_mod, rows
    mesh, hist_mesh = _build_meshes(bins)
    on_device = isinstance(bins, jax.Array)
    c, r = _n_columns(bins), int(y.shape[0])
    subtract = _use_hist_subtract()
    chips = _data_size(hist_mesh)
    k = feature_subset_count(subset_strategy, c)
    group = _rf_group_trees(n_trees, -(-r // chips), c,
                            next(iter(mesh.devices.flat)))
    starts = range(0, n_trees, group)
    by_row = _row_sharding(hist_mesh, 2) if chips > 1 else None
    with obs_trace.span("train.job", family="rf", rows=r, steps=n_trees,
                        bags=1, chips=chips, trees=n_trees,
                        group_trees=group, groups=len(starts),
                        subset_cols=k, bag_rate=float(bagging_rate),
                        psum_bytes=psum_bytes(cfg, c, hist_mesh, subtract,
                                              group)):
        with obs_trace.span("train.prepare"):
            key = jax.random.key(int(seed))
            edges = rf_draw.poisson_thresholds(bagging_rate)
            host_w = None
            if stratified or neg_only:
                from shifu_tpu.train.trainer import bagging_weights
                host_w = bagging_weights(
                    r, n_trees, bagging_rate, with_replacement=True,
                    seed=seed, labels=np.asarray(y, np.float32),
                    stratified=stratified, neg_only=neg_only)

        with obs_trace.span("train.place"):
            if on_device:
                jb = bins
                jy = rows.rows_over(hist_mesh, y)
                jw = rows.rows_over(hist_mesh, weights)
            else:
                jb = mesh_mod.shard_axis(
                    mesh, np.ascontiguousarray(np.asarray(bins, np.int32).T),
                    1)
                jy, jw = mesh_mod.shard_rows(
                    mesh, np.asarray(y, np.float32),
                    np.asarray(weights, np.float32))

        parts = []
        for start in starts:
            ids = np.arange(start, min(start + group, n_trees),
                            dtype=np.int32)
            with obs_trace.span("train.bag", trees=len(ids)):
                masks = rf_draw.masks(key, ids, c, k)
                if host_w is None:
                    inst_w = rf_draw.bags(key, ids, int(jb.shape[1]), edges,
                                          sharding=by_row)
                else:
                    inst_w = mesh_mod.shard_axis(mesh, host_w[ids], axis=1)
            with obs_trace.span("train.program", steps=len(ids)):
                parts.append(_rf_grow(cfg, jb, jy, jw, inst_w, masks,
                                      mesh=hist_mesh, subtract=subtract))
            with obs_trace.span("train.wait"):
                jax.block_until_ready(parts[-1])
        with obs_trace.span("train.fetch"):
            stacked = parts[0] if len(parts) == 1 else jax.tree.map(
                lambda *a: jnp.concatenate(a), *parts)
            return jax.tree.map(np.asarray, stacked)


# ---------------------------------------------------------------------------
# Out-of-core (>HBM) builders — chunked histogram accumulation
# ---------------------------------------------------------------------------

def gbt_resident_state_mode(n_train: int, n_val: int = 0) -> bool:
    """Row-state tier for the streaming GBT builder.
    SHIFU_TPU_GBT_RESIDENT_STATE = 1 forces device-resident state, 0
    forces the host-numpy path, auto (default) goes resident when the
    state fits SHIFU_TPU_GBT_STATE_BUDGET_MB. Footprint ≈ 24 B per
    train row (node i32 + pred/grad/hess f32 + the y/w f32 copies that
    let gradients compute on device) + 12 B per val row (vraw/vy/vw
    f32) — the bins matrix itself still streams from disk either way."""
    mode = knob_str("SHIFU_TPU_GBT_RESIDENT_STATE").lower()
    if mode in ("0", "off", "false"):
        return False
    if mode in ("1", "on", "true"):
        return True
    budget = knob_int("SHIFU_TPU_GBT_STATE_BUDGET_MB") << 20
    return n_train * 24 + n_val * 12 <= budget


@partial(jax.jit, static_argnames=("cfg", "depth", "mesh", "half"))
def _stream_level_chunk(cfg: TreeConfig, tree, binsT_c, node_c, grad_c,
                        hess_c, side, depth: int, mesh=None, half=False):
    """One chunk's work for one level: lazily route the chunk's rows
    through the PREVIOUS level's just-decided splits, then build this
    level's partial histograms — histograms are additive over row
    chunks, so the level's G/H are the sum of these partials (the same
    associativity Guagua exploits to combine DTWorkerParams across
    workers, dt/DTWorker.java:914-944). Fusing route+hist keeps disk
    IO at one bins pass per level. binsT_c: (C, chunk) transposed.

    half=True: sibling-subtraction mode — of every parent only the
    child that `side` names (the previous level's `_smaller_child`;
    None at the root, which routes nothing) through the kernel at
    parent-slot positions; the caller reconstructs the siblings from
    the previous level's accumulated histograms (_subtract_siblings)."""
    binsT_c = binsT_c.astype(jnp.int32)
    hist_node = node_c
    if depth > 0:
        node_c, half_c = _route_level(cfg, tree, binsT_c, node_c,
                                      depth - 1, side)
        hist_node = half_c if half else node_c
    g, h = _level_histograms(binsT_c, hist_node, grad_c, hess_c,
                             2 ** depth - 1, _kernel_slots(depth, half),
                             cfg.n_bins, mesh=mesh)
    return node_c, g, h


@partial(jax.jit, static_argnames=("cfg",))
def _leaf_contrib_chunk(cfg: TreeConfig, tree, node_c):
    return tree["leaf_value"][node_c]


@partial(jax.jit, static_argnames=("cfg",))
def _predict_chunk(cfg: TreeConfig, tree, binsT_c):
    return predict_trees(jax.tree.map(lambda a: a[None], tree),
                         binsT_c.astype(jnp.int32),
                         cfg.max_depth, cfg.n_bins)[0]


@partial(jax.jit, static_argnames=("loss",))
def _grad_chunk(y_c, pred_c, w_c, loss: str):
    """On-device gradient refresh for one resident state chunk — the
    device twin of build_gbt_streaming's host `grad_of_chunk` (same
    f32 math; the log-loss sigmoid is jax.nn.sigmoid vs numpy exp, a
    documented ulp-level difference)."""
    return gbt_gradients(y_c, pred_c, w_c, loss)


def _apply_contrib_chunk(cfg: TreeConfig, tree, node_c, pred_c):
    """Boosting update for a resident chunk: gather leaf values at the
    routed nodes (_leaf_contrib_chunk) and shrink-add — predictions
    never leave the device.

    Deliberately NOT jitted as a whole: under one jit XLA:CPU fuses
    the shrink-multiply and the accumulate into an FMA, which rounds
    differently (1 ulp) from the host tier's separate numpy multiply
    then add — enough to flip a later round's split argmax on ~10% of
    datasets (the resume-parity failure). Eager mul/add are single-op
    XLA programs, exactly rounded like numpy, and stay device-side
    (no host sync); only the gather is worth a jit."""
    return pred_c + cfg.learning_rate * _leaf_contrib_chunk(
        cfg, tree, node_c)


def _add_predict_chunk(cfg: TreeConfig, tree, binsT_c, vraw_c):
    """Add one tree's shrunk prediction on a freshly-streamed bins
    chunk to a device-resident raw-score chunk (val scores / resume).
    Not jitted for the same FMA-parity reason as
    `_apply_contrib_chunk` — the host tier computes `lr * predict`
    and the add as two exactly-rounded ops."""
    return vraw_c + cfg.learning_rate * _predict_chunk(cfg, tree,
                                                       binsT_c)


@partial(jax.jit, static_argnames=("loss",))
def _val_error_parts(vraw, vy, vw, loss: str):
    """Per-chunk partial sums of the _val_error numerator/denominator —
    device-accumulated across val chunks so the round's early-stop
    decision costs ONE host fetch (the PR-4 deferred-metric pattern).
    For a single val chunk the quotient is bit-identical to
    _val_error."""
    vp = jax.nn.sigmoid(vraw) if loss.startswith("log") else vraw
    return jnp.sum((vp - vy) ** 2 * vw), jnp.sum(vw)


def _build_tree_streaming(cfg: TreeConfig, bins_mm, grad_of_chunk,
                          node_host: np.ndarray, chunk_rows: int,
                          feature_mask, mesh, hist_mesh):
    """Grow one tree over a bins matrix that never fully enters HBM.

    bins_mm: (R, C) memory-mapped int matrix; grad_of_chunk(a, b) →
    host (grad, hess) float32 slices; node_host: (R,) int32 scratch the
    caller owns (reset to 0 per tree), updated in place to the landing
    node of every row. One bins pass per level, chunks double-buffered
    host→HBM like train/streaming.py."""
    from shifu_tpu.parallel import mesh as mesh_mod
    r = bins_mm.shape[0]
    bounds = [(s, min(s + chunk_rows, r)) for s in range(0, r, chunk_rows)]
    tree = _empty_tree(cfg)
    fm = jnp.asarray(feature_mask)

    def put(b_):
        a, b = b_
        pad = chunk_rows - (b - a)
        binsT_c = np.ascontiguousarray(bins_mm[a:b].T)   # (C, chunk)
        node_c = node_host[a:b]
        grad_c, hess_c = grad_of_chunk(a, b)
        if pad:  # fixed chunk shape → one compile; padding is inert
            binsT_c = np.pad(binsT_c, ((0, 0), (0, pad)))
            node_c = np.pad(node_c, (0, pad), constant_values=-1)
            grad_c = np.pad(grad_c, (0, pad))
            hess_c = np.pad(hess_c, (0, pad))
        return (mesh_mod.shard_axis(mesh, binsT_c, 1),
                mesh_mod.shard_axis(mesh, node_c, 0, pad_value=-1),
                mesh_mod.shard_axis(mesh, grad_c, 0),
                mesh_mod.shard_axis(mesh, hess_c, 0))

    prev_g = prev_h = side = None
    subtract = _use_hist_subtract()
    for depth in range(cfg.max_depth + 1):
        half = subtract and depth > 0
        g_acc = h_acc = None
        cur = put(bounds[0])
        for ci, (a, b) in enumerate(bounds):
            # dispatch the current chunk FIRST (jax dispatch is async),
            # THEN prepare the next one so host-side transpose/pad/put
            # overlaps device compute, THEN sync on the routed nodes
            node_c, g, h = _stream_level_chunk(
                cfg, tree, *cur, side, depth=depth, mesh=hist_mesh,
                half=half)
            add_stage_count("tree_build_dispatches")
            if ci + 1 < len(bounds):
                cur = put(bounds[ci + 1])
            node_host[a:b] = host_fetch(node_c)[:b - a]
            g_acc = g if g_acc is None else g_acc + g
            h_acc = h if h_acc is None else h_acc + h
        if half:
            # right siblings from the previous level's full histograms
            split = _parent_split_mask(tree["is_leaf"], tree["feature"],
                                       depth)
            g_acc, h_acc = _subtract_siblings(prev_g, prev_h, g_acc,
                                              h_acc, split, side)
        # only the subtraction mode needs last level's histograms; with
        # it disabled, holding them would pin extra HBM on exactly the
        # memory-scarce path this builder exists for
        prev_g, prev_h = (g_acc, h_acc) if subtract else (None, None)
        if depth < cfg.max_depth:
            tree = _apply_level(cfg, tree, g_acc, h_acc, fm, depth,
                                mesh=hist_mesh)
            side = _smaller_child(cfg, tree, h_acc, depth)
        else:
            tree = _final_leaves(cfg, tree, g_acc, h_acc)
    return tree


def _build_tree_streaming_device(cfg: TreeConfig, bins_put, n_chunks: int,
                                 node_state, grad_state, hess_state,
                                 feature_mask, hist_mesh):
    """Resident-state analog of _build_tree_streaming: per-row state
    (node/grad/hess) lives on device between levels, only the bins
    chunks stream host→HBM, and the routed nodes are KEPT on device —
    a whole level runs with ZERO device→host syncs (the host loop only
    queues async dispatches; tests/test_gbt_device.py pins this with
    the pipeline `host_syncs` counter). node_state is a list of
    per-chunk device arrays, updated in place with each level's
    routing so the caller can gather leaf contributions afterwards."""
    tree = _empty_tree(cfg)
    fm = jnp.asarray(feature_mask)
    prev_g = prev_h = side = None
    subtract = _use_hist_subtract()
    for depth in range(cfg.max_depth + 1):
        half = subtract and depth > 0
        g_acc = h_acc = None
        cur = bins_put(0)
        for ci in range(n_chunks):
            node_c, g, h = _stream_level_chunk(
                cfg, tree, cur, node_state[ci], grad_state[ci],
                hess_state[ci], side, depth=depth, mesh=hist_mesh,
                half=half)
            add_stage_count("tree_build_dispatches")
            if ci + 1 < n_chunks:
                cur = bins_put(ci + 1)  # h2d overlaps device compute
            node_state[ci] = node_c
            g_acc = g if g_acc is None else g_acc + g
            h_acc = h if h_acc is None else h_acc + h
        if half:
            split = _parent_split_mask(tree["is_leaf"], tree["feature"],
                                       depth)
            g_acc, h_acc = _subtract_siblings(prev_g, prev_h, g_acc,
                                              h_acc, split, side)
        prev_g, prev_h = (g_acc, h_acc) if subtract else (None, None)
        if depth < cfg.max_depth:
            tree = _apply_level(cfg, tree, g_acc, h_acc, fm, depth,
                                mesh=hist_mesh)
            side = _smaller_child(cfg, tree, h_acc, depth)
        else:
            tree = _final_leaves(cfg, tree, g_acc, h_acc)
    return tree


@partial(jax.jit, static_argnames=("cfg", "mesh"))
def _build_tree_fused_resident(cfg: TreeConfig, binsT_c, node0, grad_c,
                               hess_c, fm, mesh=None):
    """Whole-tree single-dispatch build for the resident streaming
    tier when the data is ONE chunk: build_tree's growth loop inside
    this jit, so a round costs one dispatch instead of (max_depth+1).
    node0 carries the pad rows at -1 (hist dump slot + routing no-op),
    exactly like _stream_level_chunk."""
    return _grow_tree(cfg, binsT_c.astype(jnp.int32), grad_c, hess_c,
                      fm, mesh, None, node0=node0)


def _build_gbt_streaming_resident(cfg: TreeConfig, bins_mm, y_mm, w_mm,
                                  n_trees: int, chunk_rows: int, fm,
                                  init_trees, early_stop_window: int,
                                  n_train: int, n_val: int, mesh,
                                  hist_mesh):
    """Device-resident row-state tier of build_gbt_streaming (see
    gbt_resident_state_mode): node/pred/grad/hess (plus the y/w inputs
    the gradients need) live as per-chunk sharded device arrays for
    the whole build, bins still stream from disk. Gradients and the
    log-loss sigmoid compute on device; the boosting update is a leaf
    gather on the resident routed nodes; the early-stop val metric is
    device-accumulated per chunk and fetched ONCE per round at the
    decision point. Host syncs: zero inside a level, ≤1 per round."""
    from shifu_tpu.parallel import mesh as mesh_mod
    r = n_train + n_val
    bounds = [(s, min(s + chunk_rows, n_train))
              for s in range(0, n_train, chunk_rows)]
    n_chunks = len(bounds)

    def put_bins(a, b):
        pad = chunk_rows - (b - a)
        binsT_c = np.ascontiguousarray(bins_mm[a:b].T)   # (C, chunk)
        if pad:  # fixed chunk shape → one compile; padding is inert
            binsT_c = np.pad(binsT_c, ((0, 0), (0, pad)))
        return mesh_mod.shard_axis(mesh, binsT_c, 1)

    def bins_put(ci):
        return put_bins(*bounds[ci])

    # row state placed ONCE: labels/weights (gradient inputs), raw
    # predictions, and a reusable node-reset template. Pad rows park
    # at node -1 (the histogram dump slot) with weight 0, so their
    # gradients/hessians are exactly zero and they can never leak into
    # histograms, leaf values, or the val metric.
    y_dev, w_dev, pred_dev, node_init = [], [], [], []
    for a, b in bounds:
        pad = chunk_rows - (b - a)
        y_c = np.pad(np.asarray(y_mm[a:b], np.float32), (0, pad))
        w_c = np.pad(np.asarray(w_mm[a:b], np.float32), (0, pad))
        n_c = np.full(chunk_rows, -1, np.int32)
        n_c[:b - a] = 0
        y_dev.append(mesh_mod.shard_axis(mesh, y_c, 0))
        w_dev.append(mesh_mod.shard_axis(mesh, w_c, 0))
        pred_dev.append(jnp.zeros_like(y_dev[-1]))
        node_init.append(mesh_mod.shard_axis(mesh, n_c, 0, pad_value=-1))

    vbounds = [(s, min(s + chunk_rows, r))
               for s in range(n_train, r, chunk_rows)]
    vraw_dev, vy_dev, vw_dev = [], [], []
    for a, b in vbounds:
        pad = chunk_rows - (b - a)
        vy_c = np.pad(np.asarray(y_mm[a:b], np.float32), (0, pad))
        # unit val weights — parity with build_gbt (zero on pads)
        vw_c = np.pad(np.ones(b - a, np.float32), (0, pad))
        vy_dev.append(mesh_mod.shard_axis(mesh, vy_c, 0))
        vw_dev.append(mesh_mod.shard_axis(mesh, vw_c, 0))
        vraw_dev.append(jnp.zeros_like(vy_dev[-1]))

    trees: List[Any] = []
    if init_trees is not None:
        n_prev = init_trees["feature"].shape[0]
        prev = [jax.tree.map(lambda a_, i=i: jnp.asarray(a_[i]),
                             init_trees)
                for i in range(n_prev)]
        trees.extend(prev)
        for tree in prev:   # warm train+val scores, all device-side
            for ci in range(n_chunks):
                pred_dev[ci] = _add_predict_chunk(
                    cfg, tree, bins_put(ci), pred_dev[ci])
            for vi, (a, b) in enumerate(vbounds):
                vraw_dev[vi] = _add_predict_chunk(
                    cfg, tree, put_bins(a, b), vraw_dev[vi])

    grad_state: List[Any] = [None] * n_chunks
    hess_state: List[Any] = [None] * n_chunks
    # single-chunk data ⇒ the bins chunk stays resident across rounds
    # and a whole tree is ONE dispatch per round (counted via
    # tree_build_dispatches; tests/test_gbt_device.py)
    resident_fused = n_chunks == 1
    bins_resident = bins_put(0) if resident_fused else None
    val_errs: List[float] = []
    best_val, bad = np.inf, 0
    for t in range(n_trees):
        node_state = list(node_init)
        for ci in range(n_chunks):  # on-device gradient refresh
            grad_state[ci], hess_state[ci] = _grad_chunk(
                y_dev[ci], pred_dev[ci], w_dev[ci], loss=cfg.loss)
        if resident_fused:
            tree, node_c = _build_tree_fused_resident(
                cfg, bins_resident, node_state[0], grad_state[0],
                hess_state[0], jnp.asarray(fm), mesh=hist_mesh)
            node_state[0] = node_c
            add_stage_count("tree_build_dispatches")
        else:
            tree = _build_tree_streaming_device(
                cfg, bins_put, n_chunks, node_state, grad_state,
                hess_state, fm, hist_mesh)
        trees.append(tree)
        for ci in range(n_chunks):  # leaf gather — no IO, no sync
            pred_dev[ci] = _apply_contrib_chunk(
                cfg, tree, node_state[ci], pred_dev[ci])
        if n_val:
            num = den = None
            for vi, (a, b) in enumerate(vbounds):
                vraw_dev[vi] = _add_predict_chunk(
                    cfg, tree, put_bins(a, b), vraw_dev[vi])
                nm, dn = _val_error_parts(vraw_dev[vi], vy_dev[vi],
                                          vw_dev[vi], loss=cfg.loss)
                num = nm if num is None else num + nm
                den = dn if den is None else den + dn
            # THE round's single device→host sync: the early-stop
            # branch is a host decision — host_fetch times+counts it
            err = float(host_fetch(num / jnp.maximum(den, 1e-12)))
            val_errs.append(err)
            if err < best_val - 1e-9:
                best_val, bad = err, 0
            else:
                bad += 1
                if early_stop_window and bad >= early_stop_window:
                    break
    stacked = jax.tree.map(lambda *a_: jnp.stack(a_), *trees)
    return jax.tree.map(np.asarray, stacked), val_errs


def build_gbt_streaming(cfg: TreeConfig, bins_mm, y_mm, w_mm, n_trees: int,
                        valid_rate: float = 0.0,
                        chunk_rows: int = 1 << 20,
                        feature_mask: Optional[np.ndarray] = None,
                        init_trees: Optional[Any] = None,
                        early_stop_window: int = 0,
                        n_val: Optional[int] = None):
    """Out-of-core boosting: the bin matrix streams from disk chunk by
    chunk (max_depth+1 passes per tree). Per-row state has two tiers
    (gbt_resident_state_mode): when it fits the HBM budget, node/pred/
    grad/hess live as device arrays for the whole build — zero host
    syncs per level, one per round (_build_gbt_streaming_resident);
    otherwise state lives on the host at 8 bytes/row as before. The
    resident build_gbt path covers data whose BINS fit HBM; this is
    the TPU answer to the reference's disk-spill dataset feeding
    DTWorker (MemoryDiskFloatMLDataSet + dt/DTWorker.java:578).
    Validation is the trailing valid_rate fraction — ≈ random because
    `norm` writes the streaming layout in seeded-shuffled row order
    (like train/streaming.py)."""
    from shifu_tpu.parallel import mesh as mesh_mod
    r, c = bins_mm.shape
    if n_val is None:
        # streaming norm records the EXACT trailing-region size; when
        # the caller passes it, the split matches the written layout
        # row-for-row instead of round-tripping through a float rate
        n_val = int(r * max(valid_rate, 0.0))
    n_train = r - n_val
    if n_train <= 0:
        raise ValueError("streaming GBT needs at least one training row")
    mesh = mesh_mod.default_mesh()
    hist_mesh = mesh if mesh.shape.get("data", 1) > 1 else None
    fm = feature_mask if feature_mask is not None \
        else np.ones(c, np.float32)
    if gbt_resident_state_mode(n_train, n_val):
        return _build_gbt_streaming_resident(
            cfg, bins_mm, y_mm, w_mm, n_trees, chunk_rows, fm,
            init_trees, early_stop_window, n_train, n_val, mesh,
            hist_mesh)

    pred = np.zeros(n_train, np.float32)
    vraw = np.zeros(n_val, np.float32)
    node_host = np.zeros(n_train, np.int32)
    trees: List[Any] = []
    if init_trees is not None:
        n_prev = init_trees["feature"].shape[0]
        prev = [jax.tree.map(lambda a, i=i: jnp.asarray(a[i]), init_trees)
                for i in range(n_prev)]
        trees.extend(prev)
        for tree in prev:       # warm predictions from the resumed trees
            _accumulate_pred(cfg, tree, bins_mm, pred, vraw, n_train,
                             chunk_rows, mesh)

    def grad_of_chunk(a, b):
        y_c = np.asarray(y_mm[a:b], np.float32)
        w_c = np.asarray(w_mm[a:b], np.float32)
        if cfg.loss.startswith("log"):
            p = 1.0 / (1.0 + np.exp(-pred[a:b]))
            return (p - y_c) * w_c, p * (1 - p) * w_c
        return (pred[a:b] - y_c) * w_c, np.ones_like(y_c) * w_c

    val_errs: List[float] = []
    best_val, bad = np.inf, 0
    for t in range(n_trees):
        node_host[:] = 0
        tree = _build_tree_streaming(
            cfg, bins_mm[:n_train], grad_of_chunk, node_host, chunk_rows,
            fm, mesh, hist_mesh)
        trees.append(tree)
        # prediction update needs only node_host + leaf values (no IO)
        for a in range(0, n_train, chunk_rows):
            b = min(a + chunk_rows, n_train)
            contrib = _leaf_contrib_chunk(
                cfg, tree, jnp.asarray(node_host[a:b]))
            pred[a:b] += cfg.learning_rate * host_fetch(contrib)
        if n_val:
            for a in range(n_train, r, chunk_rows):
                b = min(a + chunk_rows, r)
                contrib = _predict_chunk(
                    cfg, tree, jnp.asarray(np.ascontiguousarray(
                        bins_mm[a:b].T)))
                vraw[a - n_train:b - n_train] += \
                    cfg.learning_rate * host_fetch(contrib)
            vy = np.asarray(y_mm[n_train:r], np.float32)
            # unit val weights — parity with build_gbt (and keeps any
            # caller-side bagging weight view out of the val metric),
            # computed through the SAME jitted _val_error as the
            # resident builders so early-stop arithmetic can't diverge
            err = float(host_fetch(_val_error(
                jnp.asarray(vraw), jnp.asarray(vy),
                jnp.asarray(np.ones_like(vy)), cfg.loss)))
            val_errs.append(err)
            if err < best_val - 1e-9:
                best_val, bad = err, 0
            else:
                bad += 1
                if early_stop_window and bad >= early_stop_window:
                    break
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *trees)
    return jax.tree.map(np.asarray, stacked), val_errs


def _accumulate_pred(cfg, tree, bins_mm, pred, vraw, n_train, chunk_rows,
                     mesh):
    """Add one tree's shrunk contribution to train+val raw scores by
    streaming the bin matrix (used when resuming from init_trees)."""
    r = bins_mm.shape[0]
    for a in range(0, r, chunk_rows):
        b = min(a + chunk_rows, r)
        contrib = cfg.learning_rate * host_fetch(_predict_chunk(
            cfg, tree, jnp.asarray(np.ascontiguousarray(bins_mm[a:b].T))))
        if a < n_train:
            hi = min(b, n_train)
            pred[a:hi] += contrib[:hi - a]
        if b > n_train:
            lo = max(a, n_train)
            vraw[lo - n_train:b - n_train] += contrib[lo - a:]


def build_rf_streaming(cfg: TreeConfig, bins_mm, y_mm, w_mm, n_trees: int,
                       subset_strategy: str, bagging_rate: float,
                       seed: int, chunk_rows: int = 1 << 20):
    """Out-of-core random forest: trees build sequentially (the
    resident path vmaps them — that needs the whole matrix in HBM),
    each with counter-based Poisson instance weights and a feature
    subset of `feature_subset_count` columns, streaming the bin matrix
    like build_gbt_streaming. Its draws are NOT `build_rf`'s
    (`models/rf_draw.py`): a chunk's weights come from numpy's Philox
    keyed by the tree and the chunk's first row, a tree's columns from
    numpy's `choice`, both on the host, so the same seed gives another
    forest here than on the resident path."""
    from shifu_tpu.parallel import mesh as mesh_mod
    r, c = bins_mm.shape
    rng = np.random.default_rng(seed)
    k = feature_subset_count(subset_strategy, c)
    mesh = mesh_mod.default_mesh()
    hist_mesh = mesh if mesh.shape.get("data", 1) > 1 else None
    node_host = np.zeros(r, np.int32)
    trees = []
    for t in range(n_trees):
        mask = np.zeros(c, np.float32)
        mask[rng.choice(c, size=k, replace=False)] = 1.0

        def grad_of_chunk(a, b, t=t):
            y_c = np.asarray(y_mm[a:b], np.float32)
            w_c = np.asarray(w_mm[a:b], np.float32)
            gen = np.random.Generator(np.random.Philox(
                key=seed + 104729 * t, counter=a))
            iw = gen.poisson(max(bagging_rate, 1e-6),
                             b - a).astype(np.float32)
            return -(y_c * w_c * iw), w_c * iw

        node_host[:] = 0
        trees.append(_build_tree_streaming(
            cfg, bins_mm, grad_of_chunk, node_host, chunk_rows,
            mask, mesh, hist_mesh))
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *trees)
    return jax.tree.map(np.asarray, stacked)


# ---------------------------------------------------------------------------
# Binning front-end (shared by train + predict)
# ---------------------------------------------------------------------------

def make_bin_tables(num_cuts: np.ndarray, cat_posrate_order: List[np.ndarray],
                    n_bins: int) -> Dict[str, np.ndarray]:
    """Pack the per-column binning tables shipped inside the model spec.

    num_cuts: (B-1, Cn) interior boundaries (+inf padded) from stats.
    cat_posrate_order: per categorical column, an array mapping raw code
    → posRate-ordered bin id (LightGBM-style category ordering).
    """
    cc = len(cat_posrate_order)
    # width vmax+1 so each column's own missing slot (code == vocab_len)
    # maps to the shared missing bin even for the widest vocabulary
    vmax = max([len(m) for m in cat_posrate_order], default=0) + 1
    cat_map = np.full((cc, vmax), n_bins - 1, np.int32)
    for j, m in enumerate(cat_posrate_order):
        cat_map[j, :len(m)] = m
    return {"num_cuts": num_cuts.astype(np.float32), "cat_map": cat_map}


def bin_dataset(tables: Dict[str, np.ndarray], dense: np.ndarray,
                codes: Optional[np.ndarray], n_bins: int) -> np.ndarray:
    """Raw cleaned data → (R, Cn+Cc) int32 bin matrix, missing =
    n_bins-1."""
    from shifu_tpu.ops.stats import bin_index_numeric
    parts = []
    if dense is not None and dense.shape[1]:
        cuts = jnp.asarray(tables["num_cuts"])
        idx = np.asarray(bin_index_numeric(jnp.asarray(dense), cuts))
        n_cut_slots = tables["num_cuts"].shape[0] + 1  # missing slot id
        idx = np.where(idx >= n_cut_slots, n_bins - 1,
                       np.minimum(idx, n_bins - 2))
        parts.append(idx.astype(np.int32))
    if codes is not None and codes.shape[1]:
        cat_map = tables["cat_map"]
        cc = codes.shape[1]
        safe = np.clip(codes, 0, cat_map.shape[1] - 1)
        mapped = cat_map[np.arange(cc)[None, :], safe]
        mapped = np.where(codes < 0, n_bins - 1, mapped)
        parts.append(mapped.astype(np.int32))
    if not parts:
        raise ValueError("no features to bin")
    return np.concatenate(parts, axis=1)


def predict(meta: Dict[str, Any], params: Any, dense: np.ndarray,
            codes: Optional[np.ndarray],
            route: Optional[str] = None) -> np.ndarray:
    """Score a saved GBT/RF spec on raw cleaned features.

    route: None follows SHIFU_TPU_TREE_FUSED (auto|pallas|xla); the
    explicit values pin a path — "xla" is the interpretive
    bin_dataset + predict_trees walk kept as the parity reference
    (tests/test_pallas_trees.py), "pallas" the fused ensemble kernel
    (ops/pallas_trees.py: in-register binning + whole-ensemble walk +
    convert, one launch per row tile — no host bin_dataset pass).
    `dense` may be a device array on the pallas route (the serving
    plane's pre-placed h2d block rides through make_fused_inputs)."""
    from shifu_tpu.parallel import mesh as mesh_mod
    cfg_meta = meta["treeConfig"]
    n_bins = int(cfg_meta["n_bins"])
    tables = {"num_cuts": np.asarray(params["tables"]["num_cuts"]),
              "cat_map": np.asarray(params["tables"]["cat_map"])}
    from shifu_tpu.ops import pallas_trees
    mode = route or pallas_trees.tree_fused_mode()
    if mode == "pallas":
        fb = make_fused_inputs(tables, dense, codes, n_bins)
        trees_np = jax.tree.map(np.asarray, params["trees"])
        packed, _ = pallas_trees.pack_ensemble(trees_np)
        scores = pallas_trees.predict_ensemble(
            jnp.asarray(packed), jnp.asarray(fb.valuesT),
            jnp.asarray(fb.cuts),
            n_trees=int(trees_np["feature"].shape[0]),
            kind=str(meta["kind"]),
            loss=str(cfg_meta.get("loss", "squared")),
            learning_rate=float(cfg_meta["learning_rate"]),
            max_depth=int(cfg_meta["max_depth"]), n_bins=n_bins,
            interpret=jax.default_backend() != "tpu")
        return np.asarray(scores)
    if isinstance(dense, jax.Array):  # xla walk is a host-numpy path
        dense = np.asarray(dense)
    bins = bin_dataset(tables, dense, codes, n_bins)
    n_rows = bins.shape[0]
    trees = jax.tree.map(jnp.asarray, params["trees"])
    mesh = mesh_mod.default_mesh()
    jb = mesh_mod.shard_axis(mesh, np.ascontiguousarray(bins.T), 1)
    per_tree = np.asarray(predict_trees(trees, jb,
                                        int(cfg_meta["max_depth"]),
                                        n_bins))[:, :n_rows]
    if meta["kind"] == "rf":
        # RF trees were built with grad=-y·w, hess=w, so leaf values are
        # already +mean(label); the forest averages them
        return per_tree.mean(axis=0)
    raw = float(cfg_meta["learning_rate"]) * per_tree.sum(axis=0)
    if str(cfg_meta.get("loss", "squared")).startswith("log"):
        return 1.0 / (1.0 + np.exp(-np.clip(raw, -30, 30)))
    return raw
