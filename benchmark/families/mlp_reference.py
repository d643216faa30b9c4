"""Plain reference for full-batch MLP training, and the comparison that
decides `correct` for the `mlp` family.

Imports nothing of the program and takes nothing it made: the initial
weights are drawn here from the job's seed by the rule the configuration
states (`weight_init`), the data comes from the harness. Everything is
jax.numpy at the precision the configuration states: float32, with the
operands of a matrix product rounded to `matmul_operand_dtype` where it
names one (products then exact, summed in float32: what one MXU pass does;
a product onto a single output unit is a multiply-reduce on the vector
unit and keeps float32 operands) and `highest` where it does not; bfloat16
throughout for the control. Rows go through in blocks, features on the
leading axis, so a block's activations are (width, rows)
and the reference fits beside the data whatever the row count.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_ROWS = 1 << 19
HIGHEST = jax.lax.Precision.HIGHEST
ACTIVATIONS = {"tanh": jnp.tanh, "sigmoid": jax.nn.sigmoid,
               "relu": jax.nn.relu, "linear": lambda v: v}
ADAM_EPS = 1e-8
COMPARED_STEPS = 3


def init_params(config, job_seed: int):
    """`weight_init: xavier` from the job's seed: one key per bag split
    off the seed's key, then per layer a fresh subkey and a uniform draw
    in +-sqrt(6 / (fan_in + fan_out)); biases start at zero."""
    if config["weight_init"] != "xavier" or config["bags"] != 1:
        raise ValueError("reference knows xavier initialisation of one bag")
    key = jax.random.split(jax.random.PRNGKey(job_seed), config["bags"] + 1)[0]
    dims = [config["input_dim"], *config["hidden_dims"], config["output_dim"]]
    params = []
    for fan_in, fan_out in zip(dims[:-1], dims[1:]):
        key, sub = jax.random.split(key)
        limit = math.sqrt(6.0 / (fan_in + fan_out))
        w = jax.random.uniform(sub, (fan_in, fan_out), minval=-limit,
                               maxval=limit)
        params.append({"w": w.astype(jnp.float32),
                       "b": jnp.zeros((fan_out,), jnp.float32)})
    return params


def forward_T(config, params, xT, dt):
    """Scores of the rows of xT, a (features, rows) block, in dtype dt."""
    act = ACTIVATIONS[config["activation"]]
    operand = jnp.dtype(config.get("matmul_operand_dtype") or dt)

    def dot(a, b):
        # a product onto one output unit is a multiply-reduce, no MXU
        # pass: its operands keep the dtype they have
        op = operand if a.shape[0] > 1 else dt
        return jnp.dot(a.astype(op), b.astype(op),
                       precision=HIGHEST, preferred_element_type=dt)

    hT = xT.astype(dt)
    for layer in params[:-1]:
        hT = act(dot(layer["w"].astype(dt).T, hT)
                 + layer["b"].astype(dt)[:, None])
    last = params[-1]
    out = dot(last["w"].astype(dt).T, hT) + last["b"].astype(dt)[:, None]
    return ACTIVATIONS[config["output_activation"]](out)[0]


def _blocks(n: int):
    block = min(BLOCK_ROWS, n)
    return -(-n // block), block


def _block_of(arrays, i, n, block):
    """Block i of row-leading arrays, and the mask of its rows that no
    earlier block held (the last block is shifted back to end at n)."""
    start = jnp.minimum(i * block, n - block)
    fresh = (start + jnp.arange(block)) >= i * block
    return [jax.lax.dynamic_slice_in_dim(a, start, block) for a in arrays], fresh


def _row_loss(config, pred, y):
    if config["loss"] != "squared":
        raise ValueError("reference knows the squared loss")
    return 0.5 * jnp.square(y - pred)


@functools.partial(jax.jit, static_argnames=("config_key", "dt"))
def _loss_and_grad(config_key, params, x, y, w, dt):
    config = dict(config_key)
    n = x.shape[0]
    n_blocks, block = _blocks(n)

    def one(i, acc):
        (xb, yb, wb), fresh = _block_of((x, y, w), i, n, block)
        wb = (wb * fresh).astype(dt)

        def block_sum(p):
            pred = forward_T(config, p, xb.T, dt)
            return jnp.sum(_row_loss(config, pred, yb.astype(dt)) * wb)

        loss, grad = jax.value_and_grad(block_sum)(params)
        total, gsum, wsum = acc
        return (total + loss, jax.tree.map(jnp.add, gsum, grad),
                wsum + jnp.sum(wb))

    zero = jnp.zeros((), dt)
    total, gsum, wsum = jax.lax.fori_loop(
        0, n_blocks, one, (zero, jax.tree.map(jnp.zeros_like, params), zero))
    wsum = jnp.maximum(wsum, jnp.asarray(1e-12, dt))
    return total / wsum, jax.tree.map(lambda g: g / wsum, gsum)


@functools.partial(jax.jit, static_argnames=("config_key", "dt"))
def _squared_error(config_key, params, x, y, w, dt):
    config = dict(config_key)
    n = x.shape[0]
    n_blocks, block = _blocks(n)

    def one(i, acc):
        (xb, yb, wb), fresh = _block_of((x, y, w), i, n, block)
        wb = (wb * fresh).astype(dt)
        pred = forward_T(config, params, xb.T, dt)
        total, wsum = acc
        return (total + jnp.sum(jnp.square(yb.astype(dt) - pred) * wb),
                wsum + jnp.sum(wb))

    zero = jnp.zeros((), dt)
    total, wsum = jax.lax.fori_loop(0, n_blocks, one, (zero, zero))
    return total / jnp.maximum(wsum, jnp.asarray(1e-12, dt))


@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2"))
def _adam(params, grads, mu, nu, count, lr, b1, b2):
    """ADAM as Kingma & Ba give it, bias-corrected, epsilon outside the
    root, in the dtype of the parameters."""
    count = count + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1 = 1 - b1 ** count
    c2 = 1 - b2 ** count
    params = jax.tree.map(
        lambda p, m, v: p - (lr * (m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS)
                             ).astype(p.dtype), params, mu, nu)
    return params, mu, nu, count


def _hashable(config):
    keys = ("activation", "output_activation", "loss",
            "matmul_operand_dtype")
    return tuple((k, config.get(k)) for k in keys)


def simulate(config, traffic, data, job_seed: int, dtype: str = "float32",
             n_steps=None):
    """Train as the configuration states, from the job's seed, for the
    call's steps; returns what a job call returns, as host arrays."""
    if config["optimizer"] != "ADAM" or config["batch"] != "full":
        raise ValueError("reference knows full-batch ADAM")
    dt = jnp.dtype(dtype)
    ck = _hashable(config)
    n_steps = n_steps or traffic["steps_per_call"]
    init = init_params(config, job_seed)
    params = jax.tree.map(lambda a: a.astype(dt), init)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    count = jnp.zeros((), jnp.float32)
    host = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: np.asarray(a.astype(jnp.float32)), tree)
    train_errors, val_errors, by_epoch, first_grad = [], [], [], None
    for _ in range(n_steps):
        loss, grads = _loss_and_grad(ck, params, data["x"], data["y"],
                                     data["w"], dt)
        if first_grad is None:
            first_grad = grads
        params, mu, nu, count = _adam(
            params, grads, mu, nu, count, config["learning_rate"],
            config["adam_beta1"], config["adam_beta2"])
        val = float(_squared_error(ck, params, data["xv"], data["yv"],
                                   data["wv"], dt))
        train_errors.append(float(loss))
        val_errors.append(val)
        by_epoch.append(host(params))
    best = int(np.argmin(val_errors))
    return {"train_errors": np.asarray(train_errors, np.float64),
            "val_errors": np.asarray(val_errors, np.float64),
            "best_epoch": best, "params": by_epoch[best],
            "params_by_epoch": by_epoch, "init": host(init),
            "first_grad": host(first_grad)}


def _leaf_norms(tree):
    return np.asarray([float(np.linalg.norm(np.asarray(a, np.float64)))
                       for a in jax.tree.leaves(tree)])


def _rel_gap(got, want, steps):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape:
        return math.inf
    gap = np.abs(got[steps] - want[steps]) / np.abs(want[steps])
    return float(np.max(gap)) if np.all(np.isfinite(gap)) else math.inf


def compare(config, got, ref):
    """The numbers compared, each with its limit from the configuration.
    `got` is what a job call returned (or the control's stand-in), `ref`
    the reference's.

    The first step's loss is read before any update and is steady from
    seed to seed (`first_loss_gap`). The entry returns no optimizer state,
    so the first gradient's norm cannot be read off it; the loss of steps 2
    and 3 (`loss_gap`), the validation error after steps 1 to 3
    (`val_gap`) and the change of every parameter leaf (`change_gap`)
    exist only through the gradient and the update, and stand for it. They
    swing more: ADAM's first steps have the same size whatever the
    gradient's, so an element whose gradient lies within rounding of zero
    steps the other way. A leaf's change is the gap between the two norms of
    (final - initial), against the reference's norm of that leaf or of the
    median leaf, whichever is larger; a leaf whose first gradient in the
    reference is under a thousandth of the median leaf's moves by round-off
    alone and is left out.

    The entry returns the parameters of its best validation epoch. Late
    epochs tie to rounding, so the epoch it chose is judged by what it
    gives up (`best_epoch_regret`: the reference's validation error there
    over its own best), and the parameters are compared at that epoch."""
    n_epochs = len(ref["train_errors"])
    epoch = int(got["best_epoch"])
    if not 0 <= epoch < n_epochs:
        epoch, regret = 0, math.inf
    else:
        regret = float(ref["val_errors"][epoch] / ref["val_errors"].min() - 1)
    change = lambda params: _leaf_norms(jax.tree.map(  # noqa: E731
        lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64),
        params, ref["init"]))
    moved_ref = change(ref["params_by_epoch"][epoch])
    moved_got = change(got["params"])
    grad = _leaf_norms(ref["first_grad"])
    counted = grad >= 1e-3 * np.median(grad)
    floor = np.maximum(moved_ref, np.median(moved_ref))
    leaf_gap = np.abs(moved_got - moved_ref) / floor
    leaf_gap = leaf_gap[counted]
    values = {
        "first_loss_gap": _rel_gap(got["train_errors"], ref["train_errors"],
                                   slice(0, 1)),
        "loss_gap": _rel_gap(got["train_errors"], ref["train_errors"],
                             slice(1, COMPARED_STEPS)),
        "val_gap": _rel_gap(got["val_errors"], ref["val_errors"],
                            slice(0, COMPARED_STEPS)),
        "change_gap": (float(np.max(leaf_gap))
                       if np.all(np.isfinite(leaf_gap)) else math.inf),
        "best_epoch_regret": regret,
    }
    return [{"name": name, "value": value, "limit": config["limits"][name]}
            for name, value in values.items()]
