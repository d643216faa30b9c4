"""The `gbt_mesh` family: the `gbt` family's job (`gbt.make_call`: a fresh
`shifu_tpu.models.gbdt.build_gbt` on a placed (columns, rows) bin matrix)
with the rows divided by row over the chips of one host. Chip i draws its
own rows from `fold_in(key, i)`, block by block, on that chip: no row is
ever on another chip or on the host. `build_gbt` reads the mesh from the
bin matrix's own sharding.

The comparison is the `gbt` family's (`gbt_reference`'s functions: its
routing, its float32 histograms, its gains), made shard by shard: every
chip's rows go through the reference on that chip in its row blocks (the
reference's function on a chip's own rows, the chips side by side under
`jax.shard_map`, which exchanges nothing), and a level's histogram is the
chips' partial histograms added in float32 on the host, in the order
chip 0 + chip 1 + chip 2 + chip 3 (the control's in bfloat16, the same
order).
"""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark.families import gbt
from benchmark.families import gbt_reference as ref

# `shifu_tpu.parallel.rows` is where the builders read a device input's
# own layout: a checkout without it takes its mesh from the process, not
# from the rows it is handed, and does not support this deployment
PROGRAM_MODULES = gbt.PROGRAM_MODULES + ("shifu_tpu.parallel.rows",)
RATE_METRIC = gbt.RATE_METRIC
units_per_call = gbt.units_per_call
make_call = gbt.make_call
outputs = gbt.outputs

BINS, ROWS = P(None, "data"), P("data")        # (columns, rows); (rows,)
BY_ROW = (BINS, ROWS, ROWS)                    # binsT, y, w


def data_mesh(chips: int):
    from shifu_tpu.parallel import mesh as mesh_mod
    return mesh_mod.make_mesh(n_data=chips, devices=jax.devices()[:chips])


def make_data(config, seed: int, chips: int):
    """The binned rows, a chip's share made on that chip."""
    rows = config["train_rows"]
    if rows % chips:
        raise ValueError(f"{rows} rows do not divide over {chips} chips")
    dataset = importlib.import_module(
        "benchmark.datasets." + config["dataset"])

    def local(key, cuts):
        chip = jax.lax.axis_index("data")
        return gbt._binned(dataset, cuts, config["n_bins"],
                           jax.random.fold_in(key, chip), rows // chips)

    make = jax.jit(jax.shard_map(local, mesh=data_mesh(chips),
                                 in_specs=(P(), P()), out_specs=(BINS, ROWS),
                                 check_vma=False))
    binsT, y = make(dataset.seed_key(seed, 0),
                    jnp.asarray(gbt.equal_frequency_cuts(
                        config["value_bins"])))
    return {"binsT": binsT, "y": y,
            "w": jnp.ones(y.shape, y.dtype, device=y.sharding)}


def _chips(mesh, local, in_specs, out_specs):
    return jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


@functools.partial(jax.jit, static_argnames=("mesh", "loss"))
def _gradients(y, pred, w, mesh, loss: str):
    return _chips(mesh, lambda y, pred, w: ref.gradients(
        {"loss": loss}, y, pred, w), (ROWS,) * 3, (ROWS, ROWS))(
            y, pred, w)


@functools.partial(jax.jit, static_argnames=(
    "mesh", "leaf", "offset", "n_level", "n_bins", "dt", "pieces"))
def _partial_histograms(binsT, node, g, h, mesh, leaf: bool, offset: int,
                        n_level: int, n_bins: int, dt: str, pieces: int):
    """(chips, 2, n_level, C, n_bins): `gbt_reference.level_histograms` of
    every chip's own rows, made on that chip in its row blocks; the last
    level only needs each node's totals, which one column's histogram
    holds."""
    def local(binsT, node, g, h):
        return ref.level_histograms(
            binsT[:1] if leaf else binsT, node - offset, g, h, n_level,
            n_bins, dt, pieces)[None]

    return _chips(mesh, local, (BINS, ROWS, ROWS, ROWS), P("data"))(
        binsT, node, g, h)


@functools.partial(jax.jit, static_argnames=("mesh", "offset", "n_bins"))
def _route(binsT, node, feature, split_bin, default_left, mesh, offset: int,
           n_bins: int):
    return _chips(
        mesh, lambda b, node, f, s, d: ref.route(b, node, offset, f, s, d,
                                                 n_bins),
        (BINS, ROWS, P(), P(), P()), ROWS)(
            binsT, node, feature, split_bin, default_left)


@functools.partial(jax.jit, static_argnames=("mesh",))
def _leaf_update(pred, node, values, rate, mesh):
    return _chips(
        mesh, lambda pred, node, values, rate: pred + rate * ref.lookup(
            values, node), (ROWS, ROWS, P(), P()), ROWS)(
                pred, node, values, rate)


def _summed(parts):
    """The chips' partial histograms, (chips, ...), added on the host in
    the order of the chips, in the dtype they were accumulated in."""
    parts = np.asarray(parts)
    total = parts[0]
    for part in parts[1:]:
        total = total + part
    return total


def follow(config, data, trees, control: bool = False, n_trees=None):
    """`gbt_reference.follow` over rows that lie on several chips: the
    same readings of the same trees. The per-row state (prediction,
    gradients, node) lies as the rows lie; every step of the reference is
    its own function on a chip's own rows, the chips side by side in one
    program (`_chips`: compiled once, not once a chip), and a level's
    histogram is the sum of the chips' (`_summed`)."""
    depth, n_bins = config["max_depth"], config["n_bins"]
    binsT, y, w = data["binsT"], data["y"], data["w"]
    mesh = binsT.sharding.mesh
    n_trees = min(n_trees or ref.COMPARED_TREES, len(trees["feature"]))
    n_pieces = 1 if config.get("matmul_operand_dtype") == "bfloat16" else 3
    got, low = ref._Readings(), ref._Readings()
    pred = jnp.zeros(y.shape, jnp.float32, device=y.sharding)

    def histograms(node, g, h, d, dt, pieces):
        return _summed(_partial_histograms(
            binsT, node, g, h, mesh, d == depth, 2 ** d - 1, 2 ** d, n_bins,
            dt, pieces))

    for t in range(n_trees):
        tree = {k: np.asarray(v[t]) for k, v in trees.items()}
        feature = np.where(tree["is_leaf"], -1, tree["feature"])
        g, h = _gradients(y, pred, w, mesh, config["loss"])
        node = jnp.zeros(y.shape, jnp.int32, device=y.sharding)
        root_gain = None
        for d in range(depth + 1):
            offset, n_level = 2 ** d - 1, 2 ** d
            hist = histograms(node, g, h, d, "float32", n_pieces)
            gains, g_tot, h_tot = ref.split_gains(config, hist[0], hist[1])
            flat = gains.reshape(n_level, -1)
            best = flat.max(axis=1)
            if d == 0:
                root_gain = float(best[0])
            if control:
                hist_low = jnp.asarray(histograms(node, g, h, d, "bfloat16",
                                                  1))
                gains_low, g_low, h_low = ref.split_gains(
                    config, hist_low[0], hist_low[1], xp=jnp,
                    dt=jnp.bfloat16)
                leaf_low = ref._host64(ref._leaf_value(config, g_low, h_low))
                flat_low = ref._host64(gains_low).reshape(n_level, -1)
            for k in range(n_level):
                i = offset + k
                if h_tot[k] <= 0.0:        # no row came here
                    continue
                ref_leaf = float(ref._leaf_value(config, g_tot[k], h_tot[k]))
                if d < depth and feature[i] >= 0:
                    b, side = int(tree["bin"][i]), int(tree["default_left"][i])
                    chosen = gains[k, feature[i], b, side] \
                        if b < n_bins - 2 else -np.inf
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
                node = _route(binsT, node, feature[level], tree["bin"][level],
                              tree["default_left"][level], mesh, offset,
                              n_bins)
        pred = _leaf_update(pred, node, tree["leaf_value"],
                            config["learning_rate"], mesh)
    found = {"checks": got.checks(config["limits"])}
    if control:
        found["control_checks"] = low.checks(config["limits"])
    return found


def check(config, traffic, data, job_seed: int, got, control: bool = False):
    return follow(config, data, got, control=control)


def faults(config, traffic, data, job_seed: int, got):
    """The `gbt` family's three, `half_batch` as every chip's first half,
    and the one only a mesh can have: a chip whose rows reach no
    reduction."""
    mesh = data["binsT"].sharding.mesh
    rows = config["train_rows"]
    n_chips = mesh.shape["data"]

    def built_on(part):
        return outputs(make_call(config, traffic, part, job_seed)())

    def half_batch():
        def first_half(binsT, y, w):
            half = y.shape[0] // 2
            return binsT[:, :half], y[:half], w[:half]

        cut = jax.jit(jax.shard_map(first_half, mesh=mesh, in_specs=BY_ROW,
                                    out_specs=BY_ROW, check_vma=False))
        return built_on(dict(zip(("binsT", "y", "w"), cut(
            data["binsT"], data["y"], data["w"]))))

    def shard_dropped():
        # the last chip's rows weigh nothing: its local histograms are
        # zero at every level, as if they never reached the all-reduce
        kept = rows - rows // n_chips
        weigh = jax.jit(lambda w: jnp.where(jnp.arange(rows) < kept, w, 0.0),
                        out_shardings=NamedSharding(mesh, ROWS))
        return built_on({**data, "w": weigh(data["w"])})

    return {**gbt.faults(config, traffic, data, job_seed, got),
            "half_batch": half_batch, "shard_dropped": shard_dropped}
