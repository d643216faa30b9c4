"""Plain reference for SE variable selection, and the comparison that
decides `correct` for the `varsel` family.

Imports nothing of the program. The quick train is followed with
`mlp_reference`'s own functions and compared by four of its limits'
names (`first_loss_gap`, `loss_gap`, `val_gap`, `change_gap`): the
validSetRate split is drawn here by the rule `train_nn` documents (the
round(rows x rate) rows of smallest draw from the job seed's
`numpy.random.default_rng`, with at least one row on each side), and
the held-out rows weigh 0 in the training loss and the training rows 0
in the validation error.

The ablation is the plain one, on the program's OWN returned
parameters: every column set to 0 in x and the whole forward pass run
again, in row and column blocks. A column set to 0 adds nothing to the
first layer's sums, so a block's forward passes are one product of the
rows with the first layer's weights, each column's row of them set to 0
in its own copy: the same terms summed as over the wiped rows. The
precision is the configuration's, as `mlp_reference.forward_T` computes
it: float32, the operands of a product onto more than one unit rounded
to `matmul_operand_dtype`, products exact (`highest`) and summed in
float32; the control computes all of it in bfloat16, the sums too.

`delta_gap` is the widest gap of any column's delta against the
reference's, over the largest reference delta. `selection_miss` counts
the columns that the reference ranks inside the top `filter_num` by more
than `delta_gap`'s limit over the first column outside it, and that the
program did not select; its limit is 0.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families import mlp_reference

BLOCK_ROWS = 1 << 13
BLOCK_COLS = 128
HIGHEST = jax.lax.Precision.HIGHEST


def split(config, data, job_seed: int):
    """`mlp_reference.simulate`'s data: every row, the training rows'
    weights in `w` and the held-out rows' in `wv`."""
    n = int(data["y"].shape[0])
    rate = config["valid_set_rate"]
    is_val = np.zeros(n, bool)
    if rate > 0.0:
        count = max(min(int(round(n * rate)), n - 1), 1)
        draw = np.random.default_rng(job_seed).random(n)
        is_val[np.argsort(draw, kind="stable")[:count]] = True
    held = jnp.asarray(is_val, jnp.float32)
    return {"x": data["x"], "y": data["y"], "w": data["w"] * (1.0 - held),
            "xv": data["x"], "yv": data["y"], "wv": data["w"] * held}


def _hashable(config):
    keys = ("activation", "output_activation", "matmul_operand_dtype")
    return tuple((k, config.get(k)) for k in keys)


def _dot(config, a, b, dt):
    """a (..., d_in) . b (d_in, d_out) as `forward_T` computes it: the
    operands of a product onto more than one unit rounded to the
    configuration's operand dtype, products exact, sums in dt."""
    op = jnp.dtype(config.get("matmul_operand_dtype") or dt) \
        if b.shape[-1] > 1 else dt
    return jnp.einsum("...i,io->...o", a.astype(op), b.astype(op),
                      precision=HIGHEST, preferred_element_type=dt)


def _rounded(config, a, dt):
    """a in dt with its values rounded to the operand dtype, as a
    product's operand is: `reduce_precision`, which the compiler keeps
    where it may drop a cast down and back up (excess precision)."""
    op = jnp.dtype(config.get("matmul_operand_dtype") or dt)
    if op == jnp.dtype(jnp.bfloat16):
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    return a


def _scores(config, params, z, dt):
    """Scores from the first layer's sums z (..., width)."""
    act = mlp_reference.ACTIVATIONS[config["activation"]]
    h = z
    for layer in params[1:]:
        h = _dot(config, act(h), layer["w"].astype(dt), dt) \
            + layer["b"].astype(dt)
    return mlp_reference.ACTIVATIONS[config["output_activation"]](h)[..., 0]


@functools.partial(jax.jit, static_argnames=("config_key", "dt", "wipe"))
def _deltas(config_key, params, x, dt, wipe):
    config = dict(config_key)
    n, cols = x.shape
    rb, cb = min(BLOCK_ROWS, n), min(BLOCK_COLS, cols)
    params = jax.tree.map(lambda a: a.astype(dt), params)
    w1, b1 = params[0]["w"], params[0]["b"]
    width = w1.shape[1]

    def rows_of(i):
        start = jnp.minimum(i * rb, n - rb)
        fresh = (start + jnp.arange(rb)) >= i * rb
        return jax.lax.dynamic_slice_in_dim(x, start, rb).astype(dt), fresh

    def column_block(j, sums):
        c0 = jnp.minimum(j * cb, cols - cb)
        wiped = c0 + jnp.arange(cb)
        # (columns, cb, width): column c0 + k's weights set to 0 in copy k
        keep = 1.0 - jax.nn.one_hot(wiped, cols, dtype=dt).T
        w_cut = (w1[:, None, :] * keep[:, :, None]).reshape(cols, cb * width)
        # a column set to `wipe` adds wipe * its weights back
        back = wipe * jax.lax.dynamic_slice_in_dim(w1, c0, cb)  # (cb, width)

        def row_block(i, acc):
            xb, fresh = rows_of(i)
            base = _scores(config, params, _dot(config, xb, w1, dt) + b1, dt)
            z = _dot(config, xb, w_cut, dt).reshape(rb, cb, width) + b1
            if wipe:
                z = z + _rounded(config, back, dt)
            sq = jnp.square(_scores(config, params, z, dt) - base[:, None])
            return acc + jnp.sum(jnp.where(fresh[:, None], sq,
                                           jnp.zeros((), dt)), axis=0)

        got = jax.lax.fori_loop(0, -(-n // rb), row_block,
                                jnp.zeros((cb,), dt))
        new = wiped >= j * cb
        now = jax.lax.dynamic_slice_in_dim(sums, c0, cb)
        return jax.lax.dynamic_update_slice(sums, jnp.where(new, got, now),
                                            (c0,))

    sums = jax.lax.fori_loop(0, -(-cols // cb), column_block,
                             jnp.zeros((cols,), dt))
    return sums / n


def ablate(config, params, x, dtype: str = "float32", wipe: float = 0.0):
    """(C,) mean over every row of x of the squared change of the score
    when column c is set to `wipe`, as float64 on the host."""
    if config["weight_init"] != "xavier":
        raise ValueError("reference knows the configuration's own net")
    params = jax.tree.map(jnp.asarray, params)
    d = _deltas(_hashable(config), params, x, jnp.dtype(dtype), float(wipe))
    return np.asarray(d.astype(jnp.float32), np.float64)


def top(deltas, k: int):
    """The k columns of largest delta, largest first."""
    return np.argsort(-np.asarray(deltas), kind="stable")[:k]


def compare(config, got, ref_train, ref_deltas):
    """`mlp_reference.compare` on the quick train but for
    `best_epoch_regret`, then `delta_gap` and `selection_miss` against
    `ref_deltas`, the reference's ablation of the parameters `got`
    returned. The epoch the quick train chose is not judged: no fault
    reads its regret far enough above the program's own readings for a
    limit to lie between them (the parameters are still compared at
    that epoch, by `change_gap`)."""
    limits = config["limits"]
    judged = {**config, "limits": {"best_epoch_regret": math.inf, **limits}}
    checks = [c for c in mlp_reference.compare(judged, got, ref_train)
              if c["name"] != "best_epoch_regret"]
    k = config["filter_num"]
    deltas = np.asarray(got["deltas"], np.float64)
    want = np.asarray(ref_deltas, np.float64)
    largest = float(np.max(want))
    gap = (float(np.max(np.abs(deltas - want))) / largest
           if deltas.shape == want.shape and largest > 0 else math.inf)
    selected = np.asarray(got["selected"])
    if (selected.shape != (k,) or len(set(selected.tolist())) != k
            or not math.isfinite(gap)):
        miss = math.inf
    else:
        order = np.argsort(-want, kind="stable")
        first_out = want[order[k]] if k < len(want) else -math.inf
        clear = want > first_out + limits["delta_gap"] * largest
        clear[selected] = False
        miss = float(np.sum(clear))
    checks += [{"name": "delta_gap", "value": gap,
                "limit": limits["delta_gap"]},
               {"name": "selection_miss", "value": miss,
                "limit": limits["selection_miss"]}]
    return checks


def check(config, traffic, data, job_seed: int, got, control: bool = False):
    parts = split(config, data, job_seed)
    ref = mlp_reference.simulate(config, traffic, parts, job_seed)
    found = {"checks": compare(config, got, ref,
                               ablate(config, got["params"], data["x"]))}
    if control:
        dt = config["control_precision"]
        low = mlp_reference.simulate(config, traffic, parts, job_seed,
                                     dtype=dt)
        deltas = ablate(config, low["params"], data["x"], dtype=dt)
        low = {**low, "deltas": deltas,
               "selected": top(deltas, config["filter_num"])}
        found["control_checks"] = compare(
            config, low, ref, ablate(config, low["params"], data["x"]))
    return found
