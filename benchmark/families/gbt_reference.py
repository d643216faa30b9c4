"""Plain reference for level-wise gradient-boosted trees, and the
comparison that decides `correct` for the `gbt` family.

Imports nothing of the program. It follows the trees a job call returned
the way a served model's tokens are followed: tree by tree and level by
level it routes every row down the returned splits with its own routing,
builds its own float32 histograms of its own gradients at every node the
tree has, and reads how far each returned answer lies from its own:
the gain of the returned split under the reference's best, the gain and
the leaf value the tree reports beside the reference's. Near-ties flip on
rounding, so a split is judged by what it gives up, not by its identity.
Rows go through in blocks; a histogram is an exact one-hot contraction
summed in float32 (`level_histograms`).

The control computes, at the same nodes, histograms, gains and leaf
values in bfloat16 throughout and reads the same numbers for the answers
that arithmetic would have returned.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_ROWS = 1 << 15
COMPARED_TREES = 2


def gradients(config, y, pred, w):
    if config["loss"] != "log":
        raise ValueError("reference knows the log loss")
    p = jax.nn.sigmoid(pred)
    return (p - y) * w, p * (1.0 - p) * w


def _pieces(v, n_pieces: int):
    """A float32 vector as bfloat16 pieces: three add up to it exactly
    (8 + 8 + 8 bits of mantissa), one is the vector rounded to bfloat16,
    what a configuration whose `matmul_operand_dtype` is bfloat16 states
    and what the bfloat16 control keeps."""
    parts, rest = [], v
    for _ in range(n_pieces):
        piece = rest.astype(jnp.bfloat16)
        parts.append(piece)
        rest = rest - piece.astype(jnp.float32)
    return parts


@functools.partial(jax.jit,
                   static_argnames=("n_level", "n_bins", "dt", "n_pieces"))
def level_histograms(binsT, slot, g, h, n_level: int, n_bins: int, dt,
                     n_pieces: int = 3):
    """(2, n_level, C, n_bins) sums of g and h by (node, column, bin) over
    the rows whose slot is in [0, n_level), accumulated in dtype dt.

    A histogram is a contraction of two one-hots, exact in bfloat16, with
    the values, which go in as bfloat16 pieces (`_pieces`), so every
    product is exact and the MXU sums them in dt, one pass a piece."""
    c, r = binsT.shape
    block = min(BLOCK_ROWS, r)
    n_blocks = -(-r // block)
    dt = jnp.dtype(dt)
    bf16 = jnp.bfloat16

    def one(i, acc):
        start = jnp.minimum(i * block, r - block)
        fresh = (start + jnp.arange(block)) >= i * block
        bb = jax.lax.dynamic_slice(binsT, (0, start), (c, block))
        sb = jax.lax.dynamic_slice_in_dim(slot, start, block)
        gb = jax.lax.dynamic_slice_in_dim(g, start, block)
        hb = jax.lax.dynamic_slice_in_dim(h, start, block)
        sb = jnp.where(fresh, sb, -1)
        node = (sb[None, :] == jnp.arange(n_level)[:, None]).astype(bf16)
        weighted = jnp.stack([
            jnp.stack([node * piece[None, :] for piece in _pieces(v, n_pieces)])
            for v in (gb, hb)])                       # (2, pieces, n, block)
        bins = (bb[:, None, :] == jnp.arange(n_bins)[None, :, None]
                ).astype(bf16)
        part = jnp.einsum("spnr,cbr->spncb", weighted, bins,
                          preferred_element_type=dt)
        return acc + jnp.sum(part, axis=1, dtype=dt)

    zero = jnp.zeros((2, n_level, c, n_bins), dt)
    return jax.lax.fori_loop(0, n_blocks, one, zero)


def lookup(table, index):
    """table[index] for a short table, as a chain of compare-and-select
    over its entries: a gather of tens of millions of indices is the
    slowest thing a TPU does, a fused chain is not. 0 where index is
    outside the table."""
    out = jnp.zeros(index.shape, table.dtype)
    for k in range(table.shape[0]):
        out = jnp.where(index == k, table[k], out)
    return out


@functools.partial(jax.jit, static_argnames=("n_bins",))
def route(binsT, node, offset, feature, split_bin, default_left, n_bins: int):
    """One level down: rows at a node of this level that splits (feature
    >= 0; the three tables hold the level's nodes) go to its left child
    (2i+1) where their bin is at most the split's, a missing value where
    the node sends it; every other row stays where it is."""
    packed = lookup((feature + 1) * 65536 + split_bin * 2
                    + default_left.astype(jnp.int32), node - offset)
    feat = packed // 65536 - 1
    row_bin = jnp.zeros_like(node)
    for col in range(binsT.shape[0]):
        row_bin = jnp.where(feat == col, binsT[col], row_bin)
    left = jnp.where(row_bin == n_bins - 1, packed % 2 == 1,
                     row_bin <= (packed % 65536) // 2)
    return jnp.where(feat >= 0, 2 * node + jnp.where(left, 1, 2), node)


def split_gains(config, g, h, xp=np, dt=np.float64):
    """Gain of every split of every node: (n, C, n_bins - 2, 2), the last
    axis the side a missing value takes (0 right, 1 left). g, h are
    (n, C, n_bins) with the missing bin last; a split after value bin b
    sends bins <= b left, and the last value bin is no split point. A
    child whose hessian sum is under `min_instances_per_node` rules the
    split out, as the program counts instances."""
    lam = dt(config["reg_lambda"])
    floor = dt(config["min_instances_per_node"])
    g, h = g.astype(dt), h.astype(dt)
    g_tot, h_tot = g.sum(axis=2, dtype=dt), h.sum(axis=2, dtype=dt)
    gl = xp.cumsum(g[:, :, :-1], axis=2, dtype=dt)[:, :, :-1]
    hl = xp.cumsum(h[:, :, :-1], axis=2, dtype=dt)[:, :, :-1]
    score = lambda a, b: a * a / (b + lam)  # noqa: E731
    sides = []
    for miss_left in (0, 1):
        gl_, hl_ = gl + miss_left * g[:, :, -1:], hl + miss_left * h[:, :, -1:]
        gr_, hr_ = g_tot[:, :, None] - gl_, h_tot[:, :, None] - hl_
        gain = (score(gl_, hl_) + score(gr_, hr_)
                - score(g_tot, h_tot)[:, :, None])
        sides.append(xp.where((hl_ >= floor) & (hr_ >= floor), gain,
                              -xp.inf))
    return xp.stack(sides, axis=-1), g_tot[:, 0], h_tot[:, 0]


def _leaf_value(config, g_tot, h_tot):
    return -g_tot / (h_tot + config["reg_lambda"])


def _host64(a):
    return np.asarray(jnp.asarray(a).astype(jnp.float32), np.float64)


class _Readings:
    """Widest gaps of one set of answers (the program's, or the control's)
    from the reference's."""

    def __init__(self):
        self.regret, self.gain_gap, self.leaf_gap = [], [], []
        self.leaf_ref = []

    def split(self, best, chosen_ref, reported):
        self.regret.append((best - chosen_ref) / best)
        self.gain_gap.append(abs(reported - chosen_ref) / chosen_ref)

    def unsplit(self, best, scale):
        self.regret.append(max(best, 0.0) / scale)

    def leaf(self, reported, ref):
        self.leaf_gap.append(abs(reported - ref))
        self.leaf_ref.append(abs(ref))

    def checks(self, limits):
        def widest(v):
            v = np.asarray(v, np.float64)
            return float(v.max()) if v.size and np.all(np.isfinite(v)) \
                else math.inf
        ref = np.asarray(self.leaf_ref, np.float64)
        leaf = np.asarray(self.leaf_gap) / np.maximum(ref, np.median(ref)) \
            if ref.size else []
        values = {"split_regret": widest(self.regret),
                  "gain_gap": widest(self.gain_gap),
                  "leaf_gap": widest(leaf)}
        return [{"name": k, "value": v, "limit": limits[k]}
                for k, v in values.items()]


def follow(config, data, trees, control: bool = False, n_trees=None):
    """Read the returned trees against the reference (module docstring).
    `trees` is the stacked ensemble as host arrays: feature, bin,
    default_left, is_leaf, leaf_value, gain, each (T, 2^(depth+1) - 1)."""
    depth, n_bins = config["max_depth"], config["n_bins"]
    lr = config["learning_rate"]
    binsT, y, w = data["binsT"], data["y"], data["w"]
    n_trees = min(n_trees or COMPARED_TREES, len(trees["feature"]))
    n_pieces = 1 if config.get("matmul_operand_dtype") == "bfloat16" else 3
    got, low = _Readings(), _Readings()
    pred = jnp.zeros(y.shape, jnp.float32)
    bf16 = jnp.bfloat16
    for t in range(n_trees):
        tree = {k: np.asarray(v[t]) for k, v in trees.items()}
        feature = np.where(tree["is_leaf"], -1, tree["feature"])
        dev = {k: jnp.asarray(v) for k, v in
               {"feature": feature, "bin": tree["bin"],
                "default_left": tree["default_left"],
                "leaf_value": tree["leaf_value"]}.items()}
        g, h = gradients(config, y, pred, w)
        node = jnp.zeros(y.shape, jnp.int32)
        root_gain = None
        for d in range(depth + 1):
            offset, n_level = 2 ** d - 1, 2 ** d
            # the last level only needs each node's totals: one column's
            # histogram holds them
            cols = binsT if d < depth else binsT[:1]
            hist = np.asarray(level_histograms(
                cols, node - offset, g, h, n_level, n_bins, "float32",
                n_pieces))
            gains, g_tot, h_tot = split_gains(config, hist[0], hist[1])
            flat = gains.reshape(n_level, -1)
            best = flat.max(axis=1)
            if d == 0:
                root_gain = float(best[0])
            if control:
                hist_low = level_histograms(cols, node - offset, g, h,
                                            n_level, n_bins, "bfloat16", 1)
                gains_low, g_low, h_low = split_gains(
                    config, hist_low[0], hist_low[1], xp=jnp, dt=bf16)
                leaf_low = _host64(_leaf_value(config, g_low, h_low))
                flat_low = _host64(gains_low).reshape(n_level, -1)
            for k in range(n_level):
                i = offset + k
                if h_tot[k] <= 0.0:        # no row came here
                    continue
                ref_leaf = float(_leaf_value(config, g_tot[k], h_tot[k]))
                splits = d < depth and feature[i] >= 0
                if splits:
                    b, side = int(tree["bin"][i]), int(tree["default_left"][i])
                    chosen = gains[k, feature[i], b, side] \
                        if b < n_bins - 2 else -math.inf
                    got.split(best[k], chosen, float(tree["gain"][i]))
                    if control:
                        j = int(np.argmax(flat_low[k]))
                        low.split(best[k], flat[k, j], flat_low[k, j])
                else:
                    if d < depth:
                        got.unsplit(best[k], root_gain)
                    got.leaf(float(tree["leaf_value"][i]), ref_leaf)
                    if control:
                        low.leaf(leaf_low[k], ref_leaf)
            if d < depth:
                level = slice(offset, offset + n_level)
                node = route(binsT, node, offset, dev["feature"][level],
                             dev["bin"][level], dev["default_left"][level],
                             n_bins)
        pred = pred + lr * jax.jit(lookup)(dev["leaf_value"], node)
    found = {"checks": got.checks(config["limits"])}
    if control:
        found["control_checks"] = low.checks(config["limits"])
    return found
