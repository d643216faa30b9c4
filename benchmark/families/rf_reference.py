"""Plain reference for Shifu's random forest, and the comparison that
decides `correct` for the `rf` family.

Imports nothing of the program. A forest is `n_trees` independent
regression trees on the 0/1 label, each grown on its own Poisson bag of
the rows (instance weights `iw`) and its own subset of the columns; a
tree's gradients are `-y*w*iw`, its hessians `w*iw`, a split's gain is
`GL^2/(HL+lam) + GR^2/(HR+lam) - G^2/(H+lam)` (variance impurity's gain
when the hessian is the weight) and a leaf is `-G/(H+lam)`: the weighted
mean label under `reg_lambda`.

**The draw** is made here from the job's seed by the rule the program
documents (`shifu_tpu/models/gbdt.py::build_rf`), with jax's public
random API and nothing else: `key = jax.random.key(seed)`, `kt =
fold_in(key, t)` for tree t; `u = bits(fold_in(kt, 0), (rows,), uint32)`
and `iw[r] = #{k: u[r] >= T_k}` with `T_k = floor(F(k) * 2^32)`, F the
Poisson(rate) distribution function summed in float64 by the recurrence
`pmf(0) = exp(-rate)`, `pmf(j) = pmf(j-1) * rate / j`, while the floor is
under 2^32 - 1 (`poisson_thresholds`); `v = bits(fold_in(kt, 1),
(columns,), uint32)` and the tree keeps the k columns of smallest
`(v[c], c)` (`feature_masks`), k from the subset strategy
(`subset_count`).

**The comparison** follows two trees of the forest a job call returned,
the first and the last, level by level over all rows with the `gbt`
family's routing and float32 histograms (`gbt_reference`: the benchmark's
own, not the program's) of this file's own gradients, and reads what that
family reads: `split_regret` (the gain of the returned split under the
best the tree's own columns offer), `gain_gap`, `leaf_gap`. One thing
differs. A forest at `min_info_gain` 0 splits on any gain above zero, and
a float32 gain is the difference of three scores `G^2/(H+lam)`, each
rounded at 2^-24 of itself: a gain under some 2^-22 of its node's score IS
its own rounding, and a gap measured against it has no bound (on the chip
one seed of sixteen read 0.078 where the others stayed under 0.01). So
both numbers measure against the larger of the reference's gain and
`GAIN_FLOOR` = 2^-12 of the node's score (`_ForestReadings`): a thousand
roundings, and a hundredth of what the bfloat16 control's roundings are.
Over ALL
trees it counts `mask_violations`, returned splits on a column outside
the tree's subset, and `twin_trees`, pairs of trees with equal `feature`
and `bin` arrays (every tree has its own bag, so none may be): limit 0
each. The control computes histograms, gains and leaves in bfloat16
throughout.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families import gbt_reference as ref


def subset_count(strategy: str, n_cols: int) -> int:
    """Columns a tree keeps (Shifu's `FeatureSubsetStrategy`)."""
    return {"ALL": n_cols, "HALF": max(1, n_cols // 2),
            "ONETHIRD": max(1, n_cols // 3),
            "TWOTHIRDS": max(1, 2 * n_cols // 3),
            "SQRT": max(1, int(math.sqrt(n_cols))),
            "LOG2": max(1, int(math.log2(max(n_cols, 2))))}[strategy]


def poisson_thresholds(rate: float):
    out, pmf, cdf, k = [], math.exp(-rate), 0.0, 0
    while True:
        cdf += pmf
        edge = math.floor(cdf * 2.0 ** 32)
        if edge >= 2 ** 32 - 1:
            return np.asarray(out, np.uint32)
        out.append(edge)
        k += 1
        pmf *= rate / k


def _tree_key(seed: int, tree: int, stream: int):
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.key(seed), tree), stream)


def instance_weights(seed: int, tree: int, n_rows: int, rate: float):
    """(n_rows,) float32 on the device: tree `tree`'s Poisson bag."""
    u = jax.random.bits(_tree_key(seed, tree, 0), (n_rows,), jnp.uint32)
    iw = jnp.zeros((n_rows,), jnp.float32)
    for edge in poisson_thresholds(rate):
        iw = iw + (u >= edge)
    return jnp.where(jnp.sum(iw) == 0, 1.0, iw)


def feature_masks(seed: int, n_trees: int, n_cols: int, k_cols: int):
    """(n_trees, n_cols) bool on the host: the columns each tree keeps."""
    masks = np.zeros((n_trees, n_cols), bool)
    for t in range(n_trees):
        v = np.asarray(jax.random.bits(_tree_key(seed, t, 1), (n_cols,),
                                       jnp.uint32))
        masks[t, np.lexsort((np.arange(n_cols), v))[:k_cols]] = True
    return masks


def forest_counts(trees, masks):
    """(splits on a column outside their tree's subset, pairs of trees
    with equal feature and bin arrays), over all trees."""
    splits = (~trees["is_leaf"]) & (trees["feature"] >= 0)
    feature = np.where(splits, trees["feature"], 0)
    outside = splits & ~np.take_along_axis(masks, feature, axis=1)
    n = len(trees["feature"])
    twins = sum(np.array_equal(trees["feature"][a], trees["feature"][b])
                and np.array_equal(trees["bin"][a], trees["bin"][b])
                for a in range(n) for b in range(a + 1, n))
    return int(outside.sum()), int(twins)


GAIN_FLOOR = 2.0 ** -12


class _ForestReadings(ref._Readings):
    """The `gbt` family's readings, a split's two numbers measured
    against no less than `GAIN_FLOOR` of the node's score."""

    def split(self, best, chosen_ref, reported, score):
        floor = GAIN_FLOOR * score
        self.regret.append((best - chosen_ref) / max(best, floor))
        self.gain_gap.append(abs(reported - chosen_ref)
                             / max(chosen_ref, floor))


def follow(config, data, seed: int, trees, control: bool = False):
    """Read the returned forest against the reference (module docstring).
    `trees`: the stacked forest as host arrays, each (T, 2^(depth+1) - 1);
    `seed`: the job seed its bags and subsets were drawn from."""
    depth, n_bins = config["max_depth"], config["n_bins"]
    binsT, y, w = data["binsT"], data["y"], data["w"]
    n_cols, n_rows = binsT.shape
    n_trees = len(trees["feature"])
    masks = feature_masks(seed, n_trees, n_cols,
                          subset_count(config["feature_subset"], n_cols))
    n_pieces = 1 if config.get("matmul_operand_dtype") == "bfloat16" else 3
    got, low = _ForestReadings(), _ForestReadings()
    for t in sorted({0, n_trees - 1}):
        tree = {k: np.asarray(v[t]) for k, v in trees.items()}
        feature = np.where(tree["is_leaf"], -1, tree["feature"])
        dev = {k: jnp.asarray(v) for k, v in
               {"feature": feature, "bin": tree["bin"],
                "default_left": tree["default_left"]}.items()}
        iw = instance_weights(seed, t, n_rows, config["bagging_rate"])
        g, h = -(y * w * iw), w * iw
        node = jnp.zeros(y.shape, jnp.int32)
        root_gain = None
        for d in range(depth + 1):
            offset, n_level = 2 ** d - 1, 2 ** d
            cols = binsT if d < depth else binsT[:1]
            hist = np.asarray(ref.level_histograms(
                cols, node - offset, g, h, n_level, n_bins, "float32",
                n_pieces))
            gains, g_tot, h_tot = ref.split_gains(config, hist[0], hist[1])
            if d < depth:
                gains[:, ~masks[t]] = -np.inf
            flat = gains.reshape(n_level, -1)
            best = flat.max(axis=1)
            if d == 0:
                root_gain = float(best[0])
            if control:
                hist_low = ref.level_histograms(cols, node - offset, g, h,
                                                n_level, n_bins, "bfloat16",
                                                1)
                gains_low, g_low, h_low = ref.split_gains(
                    config, hist_low[0], hist_low[1], xp=jnp,
                    dt=jnp.bfloat16)
                leaf_low = ref._host64(ref._leaf_value(config, g_low, h_low))
                flat_low = ref._host64(gains_low)
                if d < depth:
                    flat_low[:, ~masks[t]] = -np.inf
                flat_low = flat_low.reshape(n_level, -1)
            for k in range(n_level):
                i = offset + k
                if h_tot[k] <= 0.0:        # no row came here
                    continue
                ref_leaf = float(ref._leaf_value(config, g_tot[k], h_tot[k]))
                score = g_tot[k] ** 2 / (h_tot[k] + config["reg_lambda"])
                if d < depth and feature[i] >= 0:
                    b, side = int(tree["bin"][i]), int(tree["default_left"][i])
                    chosen = gains[k, feature[i], b, side] \
                        if b < n_bins - 2 else -math.inf
                    got.split(best[k], chosen, float(tree["gain"][i]), score)
                    if control:
                        j = int(np.argmax(flat_low[k]))
                        low.split(best[k], flat[k, j], flat_low[k, j], score)
                else:
                    if d < depth:
                        got.unsplit(best[k], root_gain)
                    got.leaf(float(tree["leaf_value"][i]), ref_leaf)
                    if control:
                        low.leaf(leaf_low[k], ref_leaf)
            if d < depth:
                level = slice(offset, offset + n_level)
                node = ref.route(binsT, node, offset, dev["feature"][level],
                                 dev["bin"][level], dev["default_left"][level],
                                 n_bins)
    limits = config["limits"]
    outside, twins = forest_counts(trees, masks)
    counts = [{"name": "mask_violations", "value": float(outside),
               "limit": limits["mask_violations"]},
              {"name": "twin_trees", "value": float(twins),
               "limit": limits["twin_trees"]}]
    found = {"checks": got.checks(limits) + counts}
    if control:
        found["control_checks"] = low.checks(limits) + counts
    return found
