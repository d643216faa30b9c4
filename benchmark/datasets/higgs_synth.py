"""HIGGS-shaped synthetic rows from a key, made on the device.

The shape is the UCI HIGGS table's (28 numeric columns, a binary target);
the rows are chip_smoke.py's `_frame` (PR 21): 28 N(0,1) columns, a logit
of the first twelve, 0.5% missing in columns 20..23. Columns are the
leading axis, so a block is (28, n): rows ride the lane axis on a TPU.
"""

import jax
import jax.numpy as jnp

N_COLS = 28
MISSING_FROM, MISSING_TO, MISSING_RATE = 20, 24, 0.005
MAX_BLOCK_ROWS = 1 << 20


def block(key, n: int, scale=1.0, shift=0.0):
    """One block of n rows: ((28, n) float32 with NaN where a value is
    missing, (n,) float32 labels in {0, 1}). `scale` and `shift` (numbers
    or (n,) arrays) stretch and offset the rows' logit; at 1 and 0 the
    rows are chip_smoke.py's."""
    kx, ky, km = jax.random.split(key, 3)
    xT = jax.random.normal(kx, (N_COLS, n), jnp.float32)
    w = jnp.linspace(1.0, 0.2, 8, dtype=jnp.float32)
    logit = (jnp.sum(xT[:8] * w[:, None], axis=0) + 0.9 * xT[8] * xT[9]
             + 0.6 * (xT[10] ** 2 - 1.0) - 0.4 * jnp.abs(xT[11]))
    y = jax.random.uniform(ky, (n,)) < jax.nn.sigmoid(1.2 * scale * logit + shift)
    miss = jax.random.uniform(km, (MISSING_TO - MISSING_FROM, n)) < MISSING_RATE
    part = jnp.where(miss, jnp.nan, xT[MISSING_FROM:MISSING_TO])
    xT = xT.at[MISSING_FROM:MISSING_TO].set(part)
    return xT, y.astype(jnp.float32)


def blocking(n_rows: int):
    """(number of blocks, rows a block): blocks of at most MAX_BLOCK_ROWS
    that tile n_rows; the last block is shifted back to end at n_rows."""
    n_blocks = -(-n_rows // MAX_BLOCK_ROWS)
    return n_blocks, -(-n_rows // n_blocks)


def fill(key, n_rows: int, outs, write, drift=None):
    """Fill preallocated arrays block by block inside one program.
    `write(outs, xT, y, start)` returns outs with rows [start, start+b)
    written; temporaries stay one block large, whatever n_rows is.
    `drift(position)`, where given, maps a row's place in the table, in
    [0, 1), to the (scale, shift) of its logit."""
    n_blocks, b = blocking(n_rows)

    def body(i, outs):
        start = jnp.minimum(i * b, n_rows - b)
        along = () if drift is None else drift(
            (start + jnp.arange(b)).astype(jnp.float32) / n_rows)
        xT, y = block(jax.random.fold_in(key, i), b, *along)
        return write(outs, xT, y, start)

    return jax.lax.fori_loop(0, n_blocks, body, outs)


def seed_key(seed: int, stream: int = 0):
    """A key from any whole number a command line can carry (seeds pass
    2**31), and a stream number that keeps train and validation apart."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)
