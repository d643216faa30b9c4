"""Plain reference for mini-batch Wide & Deep training, and the comparison
that decides `correct` for the `wdl` family.

Imports nothing of the program and takes nothing it made. From the job's
seed, by the rules the program documents, it draws its own

- initial weights: the bag's key is `split(PRNGKey(seed), bags)[0]`; that
  key splits three ways (embeddings, wide, deep); the embedding table is
  N(0, `embed_init_std`^2) of shape (sum V, E), every wide weight starts at
  zero, the deep layers are `weight_init: xavier` as the `mlp` family draws
  them (a fresh subkey a layer, uniform in +-sqrt(6 / (fan_in + fan_out)));
- row order: numpy's `default_rng(0xB47C4 ^ seed).permutation(rows)`, cut
  into batches of `batch_rows` in that order;
- batch order of each epoch: from the bag's key, an epoch does `key, _ =
  split(key)`, `key, pkey = split(key)`, runs the batches in the order
  `jax.random.permutation(pkey, n_batches)` and splits `key` once a batch.

Everything is jax.numpy in float32 (bfloat16 throughout for the control):
plain `take` for the lookups, `.at[].add` for the gradient's way into the
tables, dense AdaGrad over every parameter (optax's rule: accumulator from
`adagrad_initial_accumulator`, update -lr g / sqrt(acc + 1e-7)). The
operands of a matrix product are rounded to `matmul_operand_dtype` where the
configuration names one (products exact, summed in float32: one MXU pass),
`highest` where it does not; a product onto a single output unit (the last
deep layer, the dense wide term) is a multiply-reduce and keeps its dtype.
A batch is the row block, so the reference fits beside the data.

`simulate(..., fault=...)` plants one of the table path's faults in the
reference itself, for `families/wdl.py::faults`.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LOSS_EPS = 1e-7
ADAGRAD_EPS = 1e-7
ROW_ORDER_SALT = 0xB47C4
TABLE_FAULTS = ("column_dropped", "ids_shifted", "duplicates_last_wins")


def table_offsets(config):
    sizes = np.asarray(config["vocab_sizes"], np.int64)
    return (np.cumsum(sizes) - sizes).astype(np.int32)


def bag_key(config, job_seed: int):
    if config["bags"] != 1:
        raise ValueError("reference knows one bag")
    return jax.random.split(jax.random.PRNGKey(job_seed), config["bags"])[0]


def deep_dims(config):
    deep_in = config["dense_dim"] + len(config["vocab_sizes"]) \
        * config["embed_size"]
    return [deep_in, *config["hidden_dims"], 1]


def init_params(config, job_seed: int):
    if config["weight_init"] != "xavier":
        raise ValueError("reference knows xavier initialisation")
    k_embed, _, key = jax.random.split(bag_key(config, job_seed), 3)
    rows = int(sum(config["vocab_sizes"]))
    deep = []
    dims = deep_dims(config)
    for fan_in, fan_out in zip(dims[:-1], dims[1:]):
        key, sub = jax.random.split(key)
        limit = math.sqrt(6.0 / (fan_in + fan_out))
        deep.append({"w": jax.random.uniform(
            sub, (fan_in, fan_out), minval=-limit,
            maxval=limit).astype(jnp.float32),
            "b": jnp.zeros((fan_out,), jnp.float32)})
    return {"embed": jax.random.normal(k_embed, (rows, config["embed_size"]))
            * config["embed_init_std"],
            "wide_cat": jnp.zeros((rows,), jnp.float32),
            "wide_dense": jnp.zeros((config["dense_dim"],), jnp.float32),
            "wide_bias": jnp.zeros((), jnp.float32),
            "deep": deep}


def row_order(job_seed: int, n_rows: int):
    return np.random.default_rng(
        np.uint64(ROW_ORDER_SALT) ^ np.uint64(job_seed)).permutation(n_rows)


def batch_orders(config, job_seed: int, n_epochs: int, n_batches: int):
    key, orders = bag_key(config, job_seed), []
    for _ in range(n_epochs):
        key, _ = jax.random.split(key)
        key, pkey = jax.random.split(key)
        orders.append(jax.random.permutation(pkey, n_batches))
        for _ in range(n_batches):
            key, _ = jax.random.split(key)
    return orders


def table_rows(config, ids, fault):
    sizes = np.asarray(config["vocab_sizes"], np.int32)
    if fault == "ids_shifted":
        ids = ids + 1
    return jnp.clip(ids, 0, sizes - 1) + table_offsets(config)


def scores(config, small, emb, wide_rows, dense, dt, fault):
    """Probabilities of a block from its looked-up rows: emb (n, C, E),
    wide_rows (n, C), dense (n, D), in dtype dt."""
    operand = jnp.dtype(config.get("matmul_operand_dtype") or dt)

    def dot(a, b):
        op = operand if b.shape[-1] > 1 else dt
        return jnp.dot(a.astype(op), b.astype(op), precision=HIGHEST,
                       preferred_element_type=dt)

    if fault == "column_dropped":
        keep = jnp.arange(emb.shape[1]) != config["fault_column"]
        emb = emb * keep[None, :, None].astype(dt)
        wide_rows = wide_rows * keep[None, :].astype(dt)
    dense = dense.astype(dt)
    logit = jnp.sum(wide_rows, axis=1)
    logit = logit + jnp.dot(dense, small["wide_dense"], precision=HIGHEST)
    logit = logit + small["wide_bias"]
    h = jnp.concatenate([dense, emb.reshape(emb.shape[0], -1)], axis=1)
    for layer in small["deep"][:-1]:
        h = jax.nn.relu(dot(h, layer["w"]) + layer["b"])
    last = small["deep"][-1]
    logit = logit + (dot(h, last["w"]) + last["b"])[:, 0]
    return jax.nn.sigmoid(logit)


def _split(params):
    tables = {k: params[k] for k in ("embed", "wide_cat")}
    return tables, {k: v for k, v in params.items() if k not in tables}


def _adagrad(p, acc, g, lr):
    acc = acc + g * g
    scale = jnp.where(acc > 0, jax.lax.rsqrt(acc + ADAGRAD_EPS), 0.0)
    return p - (lr * (scale * g)).astype(p.dtype), acc


@functools.partial(jax.jit, static_argnames=("config_key", "dt", "fault"),
                   donate_argnums=(1, 2))
def _epoch(config_key, params, acc, batches, order, dt, fault):
    """One epoch of mini-batch updates in the given batch order; returns
    the new state and the epoch's training loss (batch losses weighted by
    batch mass)."""
    config = dict(config_key)
    lr = config["learning_rate"]

    def step(state, b):
        params, acc = state
        dense, ids, y, w = (t[b] for t in batches)
        tables, small = _split(params)
        rows = table_rows(config, ids, fault)
        y, w = y.astype(dt), w.astype(dt)

        def loss_of(small, emb, wide_rows):
            p = scores(config, small, emb, wide_rows, dense, dt, fault)
            eps = jnp.asarray(LOSS_EPS, dt)
            per = -(y * jnp.log(p + eps) + (1 - y) * jnp.log(1 - p + eps))
            return jnp.sum(per * w) / jnp.maximum(jnp.sum(w), 1e-12)

        loss, (g_small, g_emb, g_wide) = jax.value_and_grad(
            loss_of, argnums=(0, 1, 2))(
            small, jnp.take(tables["embed"], rows, axis=0),
            jnp.take(tables["wide_cat"], rows, axis=0))
        into = (lambda z, r, g: z.at[r].set(g)) \
            if fault == "duplicates_last_wins" else \
            (lambda z, r, g: z.at[r].add(g))
        grads = {**g_small,
                 "embed": into(jnp.zeros_like(tables["embed"]), rows, g_emb),
                 "wide_cat": into(jnp.zeros_like(tables["wide_cat"]), rows,
                                  g_wide)}
        new = jax.tree.map(lambda p, a, g: _adagrad(p, a, g, lr),
                           params, acc, grads)
        params = jax.tree.map(lambda p, n: n[0], params, new)
        acc = jax.tree.map(lambda p, n: n[1], acc, new)
        return (params, acc), (loss, jnp.sum(w))

    (params, acc), (losses, mass) = jax.lax.scan(step, (params, acc), order)
    losses, mass = losses.astype(jnp.float32), mass.astype(jnp.float32)
    return params, acc, jnp.sum(losses * mass) / jnp.maximum(
        jnp.sum(mass), 1e-12)


@functools.partial(jax.jit, static_argnames=("config_key", "dt", "fault",
                                             "block"))
def _squared_error(config_key, params, dense, ids, y, w, dt, fault, block):
    config = dict(config_key)
    tables, small = _split(params)
    n = y.shape[0]

    def one(i, sums):
        start = jnp.minimum(i * block, n - block)
        fresh = (start + jnp.arange(block)) >= i * block
        d, c, yb, wb = (jax.lax.dynamic_slice_in_dim(a, start, block)
                        for a in (dense, ids, y, w))
        rows = table_rows(config, c, fault)
        p = scores(config, small, jnp.take(tables["embed"], rows, axis=0),
                   jnp.take(tables["wide_cat"], rows, axis=0), d, dt, fault)
        wb = (wb * fresh).astype(jnp.float32)
        err = jnp.square(yb - p.astype(jnp.float32))
        return sums[0] + jnp.sum(err * wb), sums[1] + jnp.sum(wb)

    total, mass = jax.lax.fori_loop(0, -(-n // block), one, (0.0, 0.0))
    return total / jnp.maximum(mass, 1e-12)


def _hashable(config):
    keys = ("vocab_sizes", "matmul_operand_dtype", "learning_rate",
            "fault_column")
    freeze = lambda v: tuple(v) if isinstance(v, list) else v  # noqa: E731
    return tuple((k, freeze(config.get(k))) for k in keys)


def _batches(arrays, order, n_batches: int, batch_rows: int):
    """Rows in the job's order, zero rows (zero weight) filling the last
    batch, cut into (n_batches, batch_rows, ...)."""
    pad = n_batches * batch_rows - len(order)
    out = []
    for a in arrays:
        a = jnp.take(a, order, axis=0)
        a = jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
        out.append(a.reshape((n_batches, batch_rows) + a.shape[1:]))
    return tuple(out)


def simulate(config, traffic, data, job_seed: int, dtype: str = "float32",
             fault=None):
    """Train as the configuration states, from the job's seed, for the
    call's epochs; returns what a job call returns. Parameters stay on
    the device (`params_by_epoch`, `init`): `compare` fetches what it
    reads."""
    if config["optimizer"] != "ADAGRAD" or config["loss"] != "log":
        raise ValueError("reference knows AdaGrad on the log loss")
    if fault not in (None, *TABLE_FAULTS):
        raise ValueError(f"no fault {fault!r}")
    dt = jnp.dtype(dtype)
    ck = _hashable(config)
    n_epochs = traffic["steps_per_call"]
    n_rows, batch_rows = data["y"].shape[0], config["batch_rows"]
    n_batches = -(-n_rows // batch_rows)
    init = init_params(config, job_seed)
    # copies: an epoch donates its state, and `init` outlives it
    params = jax.tree.map(lambda a: jnp.array(a, dt), init)
    acc = jax.tree.map(lambda a: jnp.full_like(
        a, config["adagrad_initial_accumulator"]), params)
    batches = _batches((data["dense"], data["ids"], data["y"], data["w"]),
                       jnp.asarray(row_order(job_seed, n_rows)), n_batches,
                       batch_rows)
    orders = batch_orders(config, job_seed, n_epochs, n_batches)
    block = min(batch_rows, data["yv"].shape[0])
    train_errors, val_errors, by_epoch = [], [], []
    for order in orders:
        params, acc, loss = _epoch(ck, params, acc, batches, order, dt, fault)
        val = _squared_error(ck, params, data["dense_v"], data["ids_v"],
                             data["yv"], data["wv"], dt, fault, block)
        train_errors.append(float(loss))
        val_errors.append(float(val))
        by_epoch.append(jax.tree.map(lambda a: jnp.array(a, jnp.float32),
                                     params))
    best = int(np.argmin(val_errors))
    return {"train_errors": np.asarray(train_errors, np.float64),
            "val_errors": np.asarray(val_errors, np.float64),
            "best_epoch": best, "params": by_epoch[best],
            "params_by_epoch": by_epoch, "init": init}


def _rel_gap(got, want, steps):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape:
        return math.inf
    gap = np.abs(got[steps] - want[steps]) / np.abs(want[steps])
    return float(np.max(gap)) if np.all(np.isfinite(gap)) else math.inf


@jax.jit
def _leaf_changes(got, want, init):
    """Per leaf: (norm of got - want, norm of want - init)."""
    norm = lambda a: jnp.sqrt(jnp.sum(jnp.square(a)))  # noqa: E731
    return (jax.tree.map(lambda g, r: norm(g - r), got, want),
            jax.tree.map(lambda r, i: norm(r - i), want, init))


@jax.jit
def _untouched_changed(got, init, touched):
    """Table rows no training batch looked up whose value in `got` is not
    its initial one, bit for bit (embedding rows and wide weights)."""
    moved = jnp.any(got["embed"] != init["embed"], axis=1).astype(jnp.int32) \
        + (got["wide_cat"] != init["wide_cat"]).astype(jnp.int32)
    return jnp.sum(jnp.where(touched, 0, moved))


def compare(config, data, got, ref):
    """The numbers compared, each with its limit from the configuration.
    `got` is what a job call returned (or a fault's or the control's
    stand-in), `ref` the reference's.

    The entry returns one training loss an epoch (its batches' losses,
    each read before that batch's update, weighted by batch mass) and no
    batch's loss alone, so no number here is read before any update as
    the mlp family's `first_loss_gap` is. The first epoch's loss is the
    one the updates have moved least (`epoch0_loss_gap`: 127 of its 128
    batch losses at the cell's size come after an update); it, the
    later epochs' (`loss_gap`), the validation error after every epoch
    (`val_gap`) and the change of every parameter leaf (`change_gap`) all
    pass through the gradient and the update. A leaf's change gap is the
    norm of (returned - reference) over the norm of the reference's
    (final - initial) of that leaf, or of the median leaf where that is
    larger: direction counts, not only size. The entry returns the
    parameters of its best validation epoch: the epoch it chose is judged
    by what it gives up (`best_epoch_regret`) and the parameters are
    compared at that epoch. `untouched_changed` counts the table rows no
    training batch looked up that do not hold their initial value bit for
    bit: dense AdaGrad leaves a row of zero gradient where it was."""
    n_epochs = len(ref["train_errors"])
    epoch = int(got["best_epoch"])
    if not 0 <= epoch < n_epochs:
        epoch, regret = 0, math.inf
    else:
        regret = float(ref["val_errors"][epoch] / ref["val_errors"].min() - 1)
    on_device = jax.tree.map(jnp.asarray, got["params"])
    same = jax.tree.structure(on_device) == jax.tree.structure(ref["init"]) \
        and all(a.shape == b.shape for a, b in zip(
            jax.tree.leaves(on_device), jax.tree.leaves(ref["init"])))
    if same:
        off, moved = _leaf_changes(on_device, ref["params_by_epoch"][epoch],
                                   ref["init"])
        off = np.asarray(jax.tree.leaves(off), np.float64)
        moved = np.asarray(jax.tree.leaves(moved), np.float64)
        leaf_gap = off / np.maximum(moved, np.median(moved))
        change = float(np.max(leaf_gap)) \
            if np.all(np.isfinite(leaf_gap)) else math.inf
        touched = jnp.zeros((on_device["wide_cat"].shape[0],), bool).at[
            table_rows(config, data["ids"], None).reshape(-1)].set(True)
        untouched = float(_untouched_changed(on_device, ref["init"], touched))
    else:
        change = untouched = math.inf
    steps = slice(1, n_epochs)
    values = {
        "epoch0_loss_gap": _rel_gap(got["train_errors"], ref["train_errors"],
                                    slice(0, 1)),
        "loss_gap": _rel_gap(got["train_errors"], ref["train_errors"], steps),
        "val_gap": _rel_gap(got["val_errors"], ref["val_errors"],
                            slice(0, n_epochs)),
        "change_gap": change,
        "best_epoch_regret": regret,
        "untouched_changed": untouched,
    }
    return [{"name": name, "value": value, "limit": config["limits"][name]}
            for name, value in values.items()]
