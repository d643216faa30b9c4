"""epsilon-shaped synthetic rows from a key, made on the device.

The shape is the PASCAL Large Scale Learning Challenge 2008's epsilon
table (LIBSVM's datasets page: 400,000 training rows, 2,000 dense numeric
columns, a binary target): N(0,1) columns with 0.5% of the values
missing. The label is drawn from a logit over `informative` columns, a
subset drawn from the table's key, with weights 1 / sqrt(rank + 1), so
that a ranking of the columns by what they carry has a true head and a
long tail. Columns lead a block, (n_cols, n): rows ride the lane axis on
a TPU.
"""

import jax
import jax.numpy as jnp

MISSING_RATE = 0.005
MAX_BLOCK_ROWS = 1 << 14


def logit_weights(key, n_cols: int, informative: int):
    """(n_cols,) weight of each column in the label's logit: the first
    `informative` columns of a permutation drawn from `key` get 1, 1/sqrt(2),
    1/sqrt(3), ... in that order, every other column 0."""
    cols = jax.random.permutation(key, n_cols)[:informative]
    weights = jax.lax.rsqrt(jnp.arange(1, informative + 1, dtype=jnp.float32))
    return jnp.zeros((n_cols,), jnp.float32).at[cols].set(weights)


def block(key, n: int, beta):
    """One block of n rows: ((n_cols, n) float32 with NaN where a value
    is missing, (n,) float32 labels in {0, 1})."""
    kx, ky, km = jax.random.split(key, 3)
    xT = jax.random.normal(kx, (beta.shape[0], n), jnp.float32)
    logit = jnp.dot(beta, xT, precision=jax.lax.Precision.HIGHEST)
    y = jax.random.uniform(ky, (n,)) < jax.nn.sigmoid(logit)
    miss = jax.random.uniform(km, xT.shape) < MISSING_RATE
    return jnp.where(miss, jnp.nan, xT), y.astype(jnp.float32)


def fill(key, n_rows: int, n_cols: int, informative: int, outs, write):
    """Fill preallocated arrays block by block inside one program.
    `write(outs, xT, y, start)` returns outs with rows [start, start+b)
    written; temporaries stay one block large, whatever n_rows is. The
    last block is shifted back to end at n_rows."""
    kbeta, krows = jax.random.split(key)
    beta = logit_weights(kbeta, n_cols, informative)
    n_blocks = -(-n_rows // MAX_BLOCK_ROWS)
    b = -(-n_rows // n_blocks)

    def body(i, outs):
        start = jnp.minimum(i * b, n_rows - b)
        xT, y = block(jax.random.fold_in(krows, i), b, beta)
        return write(outs, xT, y, start)

    return jax.lax.fori_loop(0, n_blocks, body, outs)


def seed_key(seed: int, stream: int = 0):
    """A key from any whole number a command line can carry (seeds pass
    2**31), and a stream number."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)
